"""Plain float32 reference of the served model: the llama block.

The configuration the benchmark serves states its arithmetic: a pre-norm
decoder (RMSNorm, rotary attention over every head dimension with
grouped KV heads, a sequential residual, SwiGLU MLP, tied or separate
LM head), every projection with 4-bit symmetric per-output-channel
weights and 8-bit symmetric per-row activations, accumulated exactly.
This file writes that down in straightforward ``jax.numpy`` at float32
and ``highest`` matmul precision, from the configuration's numbers and
the benchmark's float weights. It imports nothing of the program and
takes nothing the program made: it quantizes the float weights itself.

:data:`BLOCK` declares the block this reference models; the harness
refuses a configuration whose block differs. A configuration names its
reference in its file (``"reference"``, default ``ref.py``), and a
reference of another block is a module of its own beside this one, with
its ``BLOCK`` and a ``served_gaps``: it writes down its block's
:func:`prepare`, :func:`layer` and :func:`logits`, and hands them to this
module's :func:`served_gaps`, which drives them layer by layer, with
:func:`quantize`, :func:`qproj` and :func:`rope` to build on.

It runs layer by layer over every compared request together: layer l
for every request, then layer l + 1, with only layer l's weights made
and quantized at a time, so it fits one chip at 12B widths.

``act_bits`` below the stated 8 gives the control: the same reference in
the next precision down.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

EPS_SCALE = 1e-8

# the block-key values this reference models (ModelConfig field names)
BLOCK = {"norm": "rms", "rotary_fraction": 1.0, "qk_norm": False,
         "parallel_residual": False, "qkv_bias": False}

# the projections of one layer: (sub-block, name)
PROJECTIONS = (("attn", "wq"), ("attn", "wk"), ("attn", "wv"),
               ("attn", "wo"), ("mlp", "wi"), ("mlp", "wg"), ("mlp", "wo"))


def quantize(x, bits: int, axis: int):
    """Symmetric quantization to ``bits``-bit integers along ``axis``:
    scale = max|x| / (2^(bits-1) - 1) (+1e-8), levels rounded to nearest
    even and clipped to [-2^(bits-1), 2^(bits-1) - 1]."""
    hi = 2 ** (bits - 1) - 1
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / hi + EPS_SCALE
    return jnp.clip(jnp.round(x / s), -hi - 1, hi), s


def prepare(p, *, bits: int):
    """One layer's float weights -> the weights the layer reads: every
    projection as int8 levels and float32 per-output-channel scales,
    quantized here; the norm scales as they are."""
    if bits > 8:
        raise ValueError(f"{bits}-bit weights do not fit int8 levels")
    out = {"ln1": p["ln1"]["scale"], "ln2": p["ln2"]["scale"]}
    for blk, name in PROJECTIONS:
        q, s = quantize(p[blk][name]["w"], bits, axis=0)
        out[f"{blk}.{name}"] = {"q": q.astype(jnp.int8), "s": s}
    return out


def qproj(x, w, *, act_bits: int):
    """x [S, in] float32 through a quantized [in, out] weight."""
    qx, sx = quantize(x, act_bits, axis=-1)
    return (qx @ w["q"].astype(jnp.float32)) * sx * w["s"]


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
    """One decoder layer over a whole causal sequence h [S, d], with the
    weights :func:`prepare` made."""
    s = h.shape[0]
    nh, nkv, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    proj = functools.partial(qproj, act_bits=act_bits)
    pos = jnp.arange(s)
    a = rmsnorm(h, p["ln1"], c["norm_eps"])
    q = rope(proj(a, p["attn.wq"]).reshape(s, nh, hd), pos, c["rope_theta"])
    k = rope(proj(a, p["attn.wk"]).reshape(s, nkv, hd), pos,
             c["rope_theta"])
    v = proj(a, p["attn.wv"]).reshape(s, nkv, hd)
    rep = nh // nkv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    h = h + proj(att.reshape(s, nh * hd), p["attn.wo"])
    m = rmsnorm(h, p["ln2"], c["norm_eps"])
    up, gate = proj(m, p["mlp.wi"]), proj(m, p["mlp.wg"])
    return h + proj(jax.nn.silu(gate) * up, p["mlp.wo"])


# each runs one block's function, given as a static argument, in a
# jitted call of its own

@functools.partial(jax.jit, static_argnames=("fn", "bits"))
def _prepare(p, *, fn, bits):
    return fn(p, bits=bits)


@functools.partial(jax.jit, static_argnames=("fn", "cfg_items", "act_bits"))
def _layer(h, p, *, fn, cfg_items, act_bits):
    with jax.default_matmul_precision("highest"):
        return fn(h, p, dict(cfg_items), act_bits=act_bits)


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


def logits(h, top, c: Dict):
    """Final norm and LM head: h [S, d] -> logits [S, vocab] float32."""
    h = rmsnorm(h, top["final_norm"]["scale"], c["norm_eps"])
    if c["tie_embeddings"]:
        return h @ top["embed"]["table"].T
    return h @ top["lm_head"]["w"]


@functools.partial(jax.jit, static_argnames=("fn", "cfg_items", "ctrl"))
def _gaps(h, low, top, targets, mask, *, fn, cfg_items, ctrl):
    c = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        ref = fn(h, top, c)
        best = ref.max(-1)
        served = jnp.take_along_axis(ref, targets[:, None], -1)[:, 0]
        gap = jnp.where(mask, best - served, 0.0)
        if not ctrl:
            return gap, jnp.zeros_like(gap)
        pick = jnp.argmax(fn(low, top, c), -1)
    ctrl_gap = best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return gap, jnp.where(mask, ctrl_gap, 0.0)


def _rows(prompt, served, length: int):
    """(tokens, targets, mask) of one request, right-padded to
    ``length`` rows; position plen-1+i produced served token i."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served])
    if len(seq) > length:
        raise ValueError(f"{len(seq)} rows exceed the reference's {length}")
    n, at = len(served), len(prompt) - 1
    tokens = np.zeros(length, np.int32)
    tokens[:len(seq)] = seq
    targets = np.zeros(length, np.int32)
    targets[at:at + n] = served
    mask = np.zeros(length, bool)
    mask[at:at + n] = True
    return tokens, targets, mask


def served_gaps(weights, c: Dict, requests: Sequence[Tuple], *,
                length: int, ctrl_bits: int = 0, prepare: Callable = prepare,
                layer: Callable = layer, logits: Callable = logits
                ) -> List[Tuple[float, object, int]]:
    """Widest gaps of served requests against the reference.

    ``weights`` gives the float weights of the seed in pieces (``top()``:
    embedding, final norm, head; ``layer(l)``: layer l); ``requests`` is
    a list of (prompt, served tokens). The reference runs once over each
    ``prompt + served`` (right-padded to ``length`` rows; the causal
    mask keeps the padding out), layer by layer over all requests. At
    each position that produced a served token, the gap is the
    reference's best logit minus its logit for the served token. With
    ``ctrl_bits`` it also reads, at the same positions, the gap of the
    token the reference at ``ctrl_bits``-bit activations puts first (the
    control). Returns, per request, (max served gap, max control gap or
    None, tokens compared).

    ``prepare``, ``layer`` and ``logits`` are the block's equations (this
    module's llama block by default): one layer's float weights to what
    the layer reads, one layer over a sequence, and the final norm with
    the LM head. Each must be a module-level function, so that the same
    function finds its compiled program again."""
    items = tuple(sorted(c.items()))
    rows = [_rows(p, s, length) for p, s in requests]
    top = weights.top()
    hs = [_embed(top["embed"]["table"], jnp.asarray(t)) for t, _, _ in rows]
    lows = list(hs) if ctrl_bits else []
    for i in range(c["n_layers"]):
        p = _prepare(weights.layer(i), fn=prepare, bits=c["weight_bits"])
        hs = [_layer(h, p, fn=layer, cfg_items=items, act_bits=c["act_bits"])
              for h in hs]
        lows = [_layer(h, p, fn=layer, cfg_items=items, act_bits=ctrl_bits)
                for h in lows]
        del p
    out = []
    for j, (_, targets, mask) in enumerate(rows):
        gap, ctrl = _gaps(hs[j], lows[j] if ctrl_bits else hs[j], top,
                          jnp.asarray(targets), jnp.asarray(mask),
                          fn=logits, cfg_items=items, ctrl=bool(ctrl_bits))
        gap, ctrl = np.asarray(gap), np.asarray(ctrl)
        out.append((float(gap.max()),
                    float(ctrl.max()) if ctrl_bits else None,
                    int(mask.sum())))
    return out
