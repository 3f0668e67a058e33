"""Shared layers: dense (with PPAC modes), norm, embeddings, RoPE, MLP.

Conventions:
  * Every ``*_init`` returns ``(params, axes)`` — parallel pytrees where
    ``axes`` holds logical-axis tuples consumed by sharding.rules.
  * Every ``*_apply`` is a pure function of (params, inputs, config).
  * Compute dtype is cfg.dtype (bf16 by default); params are fp32 masters
    unless converted for serving.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.engine import (
    QuantContainer,
    qat_dense,
    serve_dense,
    serve_dense_grouped,
)
from ..configs.base import ModelConfig, PPACModeConfig


def _normal(key, shape, dtype=jnp.float32, stddev=0.02):
    return jax.random.normal(key, shape, dtype) * stddev


# -- dense -------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, axes: Tuple, *, bias: bool = False,
               stddev: float = 0.02):
    p = {"w": _normal(key, (d_in, d_out), stddev=stddev)}
    a = {"w": axes}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
        a["b"] = (axes[-1],)
    return p, a


def dense_apply(p, x, *, ppac: Optional[PPACModeConfig] = None,
                mode: str = "float", dtype=jnp.bfloat16):
    """Projection with optional PPAC execution.

    mode: 'float' | 'qat' | 'serve' | 'draft'. In 'serve' mode ``p['w']``
    may be a quantized container produced by pack_weight_for_serving;
    'draft' serves the container's resident packed1 rung (speculative
    drafting) and degrades to the target rung / plain matmul when no
    draft rung or no container exists.
    """
    w = p["w"]
    use_ppac = (ppac is not None and ppac.enabled and mode != "float"
                and not isinstance(w, QuantContainer)
                and min(w.shape) >= ppac.min_features)
    if isinstance(w, QuantContainer):  # resident quantized weight
        y = serve_dense(x, w, act_bits=ppac.act_bits if ppac else 8,
                        act_format=ppac.act_format if ppac else "int",
                        backend=ppac.backend if ppac else "mxu",
                        rung="draft" if mode == "draft" else "target")
    elif use_ppac and mode == "qat":
        y = qat_dense(x, w, weight_bits=ppac.weight_bits,
                      act_bits=ppac.act_bits,
                      weight_format=ppac.weight_format,
                      act_format=ppac.act_format)
    else:
        y = jnp.einsum("...i,io->...o", x, w.astype(dtype))
    if "b" in p:
        y = y + p["b"].astype(dtype)
    return y


def grouped_dense_apply(p, x, *, ppac: Optional[PPACModeConfig] = None,
                        mode: str = "serve"):
    """Serving fast path for a fused projection group: one resident
    container covers several same-input projections (wq/wk/wv, wi/wg);
    returns the tuple of member outputs. Only exists post-conversion —
    ``convert_params_for_serving`` creates these nodes."""
    w = p["w"]
    assert isinstance(w, QuantContainer) and w.splits, w
    return serve_dense_grouped(x, w,
                               act_bits=ppac.act_bits if ppac else 8,
                               act_format=ppac.act_format if ppac else "int",
                               backend=ppac.backend if ppac else "mxu",
                               rung="draft" if mode == "draft" else "target")


# -- norm --------------------------------------------------------------------

def rmsnorm_init(d: int, axes=("embed",)):
    return {"scale": jnp.ones((d,), jnp.float32)}, {"scale": axes}


def rmsnorm_apply(p, x, *, eps: float = 1e-5, dtype=jnp.bfloat16):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    return y.astype(dtype)


def gated_rmsnorm_apply(p, x, z, *, eps: float = 1e-5, dtype=jnp.bfloat16):
    """Mamba2's gated RMSNorm: norm(x * silu(z))."""
    xf = x.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * p["scale"]).astype(dtype)


# -- embedding ---------------------------------------------------------------

def embed_init(key, vocab: int, d: int):
    p = {"table": _normal(key, (vocab, d))}
    a = {"table": ("vocab", "embed")}
    return p, a


def embed_apply(p, tokens, *, dtype=jnp.bfloat16):
    return jnp.take(p["table"].astype(dtype), tokens, axis=0)


def unembed_apply(p, x, *, dtype=jnp.bfloat16):
    """Logits projection (optionally tied). Returns fp32 logits."""
    return jnp.einsum("...d,vd->...v", x.astype(dtype),
                      p["table"].astype(dtype)).astype(jnp.float32)


# -- RoPE --------------------------------------------------------------------

def rope(x, positions, *, theta: float = 1e4):
    """x: [..., S, H, D] (D even); positions: [..., S] int32."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., None, :]  # broadcast over heads
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# -- MLP (SwiGLU) -------------------------------------------------------------

def mlp_init(key, d: int, d_ff: int):
    k1, k2, k3 = jax.random.split(key, 3)
    wi, ai = dense_init(k1, d, d_ff, ("embed", "mlp"))
    wg, ag = dense_init(k2, d, d_ff, ("embed", "mlp"))
    wo, ao = dense_init(k3, d_ff, d, ("mlp", "embed"))
    return ({"wi": wi, "wg": wg, "wo": wo}, {"wi": ai, "wg": ag, "wo": ao})


def mlp_apply(p, x, cfg: ModelConfig, *, mode: str = "float"):
    """SwiGLU; the up+gate and down projections run under the named
    scopes ``proj/wig`` and ``proj/down``."""
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("proj/wig"):
        if "wig" in p:  # fused up+gate group (serving fast path)
            h, g = grouped_dense_apply(p["wig"], x, ppac=cfg.ppac, mode=mode)
        else:
            h = dense_apply(p["wi"], x, ppac=cfg.ppac, mode=mode,
                            dtype=dtype)
            g = dense_apply(p["wg"], x, ppac=cfg.ppac, mode=mode,
                            dtype=dtype)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(dtype) * h
    with jax.named_scope("proj/down"):
        return dense_apply(p["wo"], h, ppac=cfg.ppac, mode=mode, dtype=dtype)
