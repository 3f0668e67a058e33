"""Pure-jnp oracles for the fused bit-serial MVP kernels."""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def _popcount_rows(planes):
    """[..., rows, W] uint32 -> [..., rows] int32 set bits per row."""
    pc = lax.population_count(jnp.asarray(planes, jnp.uint32))
    return jnp.sum(pc.astype(jnp.int32), axis=-1)


def bitserial_matmul_packed_ref(x_planes, a_planes, weights):
    """Same contract as bitserial_matmul_packed, O(K1*L1*B*M*W) jnp.

    ``weights`` may be the plain [K1, L1] plane-pair matrix or the
    extended [K1+1, L1+1] one (mask popcount row/col + constant corner —
    see kernel.py); the extended terms reproduce the kernels' in-body
    popcount accumulation exactly.
    """
    x = jnp.asarray(x_planes, jnp.uint32)  # [L1,B,W]
    a = jnp.asarray(a_planes, jnp.uint32)  # [K1,M,W]
    w = jnp.asarray(weights, jnp.int32)
    l1, k1 = x.shape[0], a.shape[0]
    bits = jnp.bitwise_and(x[None, :, :, None, :], a[:, None, None, :, :])
    pc = lax.population_count(bits).astype(jnp.int32)  # [K1,L1,B,M,W]
    s = jnp.sum(pc, axis=-1)                           # [K1,L1,B,M]
    # weighted sums as elementwise int32 products: an integer dot may not
    # be exact on every backend's matrix unit
    y = jnp.sum(w[:k1, :l1, None, None] * s, axis=(0, 1))
    if w.shape == (k1 + 1, l1 + 1):
        pop_a = _popcount_rows(a)                      # [K1, M]
        pop_x = _popcount_rows(x)                      # [L1, B]
        y = y + jnp.sum(w[:k1, l1, None] * pop_a, axis=0)[None, :]
        y = y + jnp.sum(w[k1, :l1, None] * pop_x, axis=0)[:, None]
        y = y + w[k1, l1]
    return y.astype(jnp.int32)


def integer_matmul_ref(x_int, a_int):
    """Ground-truth y[b,m] = <a_m, x_b> on integer operands."""
    return jnp.asarray(x_int, jnp.int32) @ jnp.asarray(a_int, jnp.int32).T
