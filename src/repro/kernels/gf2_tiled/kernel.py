"""Pallas TPU kernel: tiled GF(2) matmul over streamed packed-bit operands.

The crypto/FEC primitive of the paper's §III-D at scale: for packed uint32
inputs x [B, W] against a resident matrix a [M, W] (W lanes of 32 bit-cells
each), compute

    y[b, m] = ⊕_j  x[b, j] & a[m, j]          (GF(2) inner product)
            = parity( sum_w popcount(x[b, w] & a[m, w]) )

for arbitrarily large n = 32·W (n ≫ 256, i.e. many PPAC arrays side by
side).  The lane-streamed grid comes from :mod:`repro.kernels.tiling`;
each lane tile contributes the parity of its local AND-popcount and the
revisited output block *XOR-accumulates* the per-tile parities — the TPU
analogue of chaining the single-bit GF(2) outputs of adjacent PPAC arrays
through an XOR tree instead of an adder tree.  Operands stay in packed
uint32 form throughout; bits are never unpacked to uint8 planes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..tiling import for_each_row, lane_stream_call, plan_tiles, popcount_row


def _gf2_matmul_kernel(x_ref, a_ref, o_ref):
    """x_ref: [tb, tw] uint32; a_ref: [tm, tw] uint32; o_ref: [tb, tm] int32
    holding the running parity (0/1), XOR-accumulated over grid dim 2."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def row(r):
        o_ref[r, :] ^= popcount_row(x_ref[r, :] & a_ref[...]) & 1

    for_each_row(x_ref.shape[0], row)


@functools.partial(
    jax.jit,
    static_argnames=("block_b", "block_m", "block_w", "interpret"),
)
def gf2_matmul_packed(
    x_packed,
    a_packed,
    *,
    block_b: int = 64,
    block_m: int = 256,
    block_w: int = 256,
    interpret: bool = False,
):
    """y[b,m] = parity(sum_w popcount(x[b,w] & a[m,w])) — int32 in {0,1}.

    x_packed: [B, W] uint32, a_packed: [M, W] uint32 -> [B, M] int32.
    Shapes are padded up to tile multiples internally (padding lanes are
    zero: AND against zero contributes 0 to every popcount, so padding
    never flips a parity).
    """
    b, w = x_packed.shape
    m, w2 = a_packed.shape
    assert w == w2, (w, w2)

    plan = plan_tiles(b, m, w, block_b=block_b, block_m=block_m,
                      block_w=block_w)
    return lane_stream_call(_gf2_matmul_kernel, x_packed, a_packed, plan,
                            interpret=interpret)
