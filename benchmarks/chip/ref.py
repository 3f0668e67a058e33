"""Plain float32 reference of the served model.

The configuration the benchmark serves states its arithmetic: a pre-norm
decoder (RMSNorm, rotary attention with grouped KV heads, SwiGLU MLP,
tied or separate LM head), every projection with 4-bit symmetric
per-output-channel weights and 8-bit symmetric per-row activations,
accumulated exactly. This file writes that down in straightforward
``jax.numpy`` at float32 and ``highest`` matmul precision, from the
configuration's numbers and the benchmark's float weights. It imports
nothing of the program and takes nothing the program made: it quantizes
the float weights itself.

``act_bits`` below the stated 8 gives the control: the same reference in
the next precision down.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

EPS_SCALE = 1e-8


def quantize(x, bits: int, axis: int):
    """Symmetric quantization to ``bits``-bit integers along ``axis``:
    scale = max|x| / (2^(bits-1) - 1) (+1e-8), levels rounded to nearest
    even and clipped to [-2^(bits-1), 2^(bits-1) - 1]."""
    hi = 2 ** (bits - 1) - 1
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / hi + EPS_SCALE
    return jnp.clip(jnp.round(x / s), -hi - 1, hi), s


def qproj(x, w, *, weight_bits: int, act_bits: int):
    """x [S, in] float32 through a quantized [in, out] float weight."""
    qw, sw = quantize(w, weight_bits, axis=0)
    qx, sx = quantize(x, act_bits, axis=-1)
    return (qx @ qw) * sx * sw


def rmsnorm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta: float):
    """x [S, H, D]; rotate the two halves of D (the served convention)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(h, p, c: Dict, *, act_bits: int):
    """One decoder layer over a whole causal sequence h [S, d]."""
    s = h.shape[0]
    nh, nkv, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    proj = functools.partial(qproj, weight_bits=c["weight_bits"],
                             act_bits=act_bits)
    pos = jnp.arange(s)
    a = rmsnorm(h, p["ln1"]["scale"], c["norm_eps"])
    at = p["attn"]
    q = rope(proj(a, at["wq"]["w"]).reshape(s, nh, hd), pos, c["rope_theta"])
    k = rope(proj(a, at["wk"]["w"]).reshape(s, nkv, hd), pos,
             c["rope_theta"])
    v = proj(a, at["wv"]["w"]).reshape(s, nkv, hd)
    rep = nh // nkv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    h = h + proj(att.reshape(s, nh * hd), at["wo"]["w"])
    m = rmsnorm(h, p["ln2"]["scale"], c["norm_eps"])
    mp = p["mlp"]
    up, gate = proj(m, mp["wi"]["w"]), proj(m, mp["wg"]["w"])
    return h + proj(jax.nn.silu(gate) * up, mp["wo"]["w"])


def logits(params, c: Dict, tokens, *, act_bits: int):
    """tokens [S] int32 -> logits [S, vocab] float32."""
    with jax.default_matmul_precision("highest"):
        h = params["embed"]["table"][tokens].astype(jnp.float32)

        def body(h, p):
            return layer(h, p, c, act_bits=act_bits), None
        h, _ = jax.lax.scan(body, h, params["layers"])
        h = rmsnorm(h, params["final_norm"]["scale"], c["norm_eps"])
        if c["tie_embeddings"]:
            return h @ params["embed"]["table"].T
        return h @ params["lm_head"]["w"]


@functools.partial(jax.jit, static_argnames=("cfg_items", "act_bits",
                                             "ctrl_bits"))
def _gaps(params, tokens, targets, mask, *, cfg_items, act_bits, ctrl_bits):
    c = dict(cfg_items)
    ref = logits(params, c, tokens, act_bits=act_bits)
    best = ref.max(-1)
    served = jnp.take_along_axis(ref, targets[:, None], -1)[:, 0]
    gap = jnp.where(mask, best - served, 0.0)
    if not ctrl_bits:
        return gap, jnp.zeros_like(gap)
    low = logits(params, c, tokens, act_bits=ctrl_bits)
    pick = jnp.argmax(low, -1)
    ctrl = best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return gap, jnp.where(mask, ctrl, 0.0)


def served_gaps(params, c: Dict, prompt, served, *, length: int,
                ctrl_bits: int = 0):
    """Widest gaps of one served request against the reference.

    Runs the reference once over ``prompt + served`` (right-padded to
    ``length`` rows; the causal mask keeps the padding out). At each
    position that produced a served token, the gap is the reference's
    best logit minus its logit for the served token. With ``ctrl_bits``
    it also reads, at the same positions, the gap of the token the
    reference at ``ctrl_bits``-bit activations puts first (the control).
    Returns (max served gap, max control gap or None, tokens compared)."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served])
    n = len(served)
    if len(seq) > length:
        raise ValueError(f"{len(seq)} rows exceed the reference's {length}")
    tokens = np.zeros(length, np.int32)
    tokens[:len(seq)] = seq
    targets = np.zeros(length, np.int32)
    mask = np.zeros(length, bool)
    # position plen-1+i produced served token i
    targets[len(prompt) - 1:len(prompt) - 1 + n] = served
    mask[len(prompt) - 1:len(prompt) - 1 + n] = True
    gap, ctrl = _gaps(params, jnp.asarray(tokens), jnp.asarray(targets),
                      jnp.asarray(mask), cfg_items=tuple(sorted(c.items())),
                      act_bits=c["act_bits"], ctrl_bits=ctrl_bits)
    gap, ctrl = np.asarray(gap), np.asarray(ctrl)
    return (float(gap.max()), float(ctrl.max()) if ctrl_bits else None, n)
