"""Pallas TPU kernel: packed binary matmul (the PPAC bit-cell array + row popcount).

Computes, for packed uint32 operands,

    S[b, m] = sum_w popcount( op( x[b, w], a[m, w] ) )        op in {xor, and}

which is the TPU-native form of the PPAC array: each uint32 lane holds 32
bit-cells; ``a`` is the resident latch matrix (weight-stationary, like the
paper's envisioned use case of a static A with streaming x, §IV-A); the
popcount + lane reduction is the subrow/row population count of Fig. 2.

From S the wrapper derives all 1-bit modes:
    xnor (h̄)      : h̄ = N_valid - S_xor
    and-dot        : S_and
    GF(2)          : S_and & 1
    inner product  : 2*h̄ - N  (eq. 1)

Tiling, padding and lane streaming come from :mod:`repro.kernels.tiling`;
the kernel body walks the tile's streamed rows one at a time (one PPAC
array cycle each), so arbitrarily large B/M/W stream through fixed VMEM
tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..tiling import for_each_row, lane_stream_call, plan_tiles, popcount_row


def _binary_matmul_kernel(x_ref, a_ref, o_ref, *, op: str):
    """x_ref: [tb, tw] uint32; a_ref: [tm, tw] uint32; o_ref: [tb, tm] int32."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bit_op = jnp.bitwise_xor if op == "xor" else jnp.bitwise_and

    def row(r):
        o_ref[r, :] += popcount_row(bit_op(x_ref[r, :], a_ref[...]))

    for_each_row(x_ref.shape[0], row)


@functools.partial(
    jax.jit,
    static_argnames=("op", "block_b", "block_m", "block_w", "interpret"),
)
def binary_matmul_packed(
    x_packed,
    a_packed,
    *,
    op: str = "xor",
    block_b: int = 64,
    block_m: int = 256,
    block_w: int = 256,
    interpret: bool = False,
):
    """S[b,m] = sum_w popcount(op(x[b,w], a[m,w])).

    x_packed: [B, W] uint32, a_packed: [M, W] uint32 -> [B, M] int32.
    Shapes are padded up to tile multiples internally (padding lanes are
    zero: xor-popcount of equal zeros adds 0; and of zeros adds 0 — so
    padding never changes S).
    """
    assert op in ("xor", "and")
    b, w = x_packed.shape
    m, w2 = a_packed.shape
    assert w == w2, (w, w2)

    plan = plan_tiles(b, m, w, block_b=block_b, block_m=block_m,
                      block_w=block_w)
    return lane_stream_call(
        functools.partial(_binary_matmul_kernel, op=op),
        x_packed, a_packed, plan, interpret=interpret)
