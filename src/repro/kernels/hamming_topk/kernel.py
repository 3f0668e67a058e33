"""Pallas TPU kernel: fused Hamming top-k over a streamed packed-bit matrix.

The associative-retrieval primitive of the paper's §III-A CAM mode at
scale: for packed uint32 queries x [B, W] against a resident database
a [M, W] (W lanes of 32 bit-cells each), return the k most similar rows
per query

    h[b, m] = n - popcount(x[b] ^ a[m])        (Hamming similarity)

*without ever materializing the [B, M] score matrix*. The grid streams the
database in [tm] row tiles (grid dim 1, innermost); the running per-query
top-k (scores + global row indices) lives in the revisited output block in
VMEM and is merged with each tile's scores as they are produced — the TPU
analogue of the PPAC array computing M similarities per cycle while a
peripheral priority encoder drains the k winners.

Tie handling is bit-exact against ``lax.top_k`` on the full score matrix:
selection order is (score descending, global index ascending). The merge
extracts the k best of [running ∪ tile] by k rounds of (max score, then
min index among the argmaxes) — exactly that ordering.

Row validity (deletes / padding) comes in as a [1, M] int32 mask; invalid
rows score ``MASKED_SCORE`` (-1), below any real similarity, and keep
index-ascending order among themselves, matching ref.py.

A second kernel fuses the threshold (CAM δ) match: it emits the per-tile
match lines y[b, m] = (h >= δ) directly — the match matrix *is* the CAM
output (one match wire per row in hardware), so it is written tile-by-tile
with no score matrix either.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ..tiling import for_each_row, lane_tile, popcount_row
from ..tiling import round_up as _round_up
from .ref import MASKED_SCORE

_NEG_INIT = -(2**30)       # running-slot init: below every candidate score
_NEG_TAKEN = jnp.iinfo(jnp.int32).min  # extracted candidates never re-win
_IDX_SENTINEL = 2**30      # index init / argmin mask: above every row index


def _row_scores(x_row, a, valid, *, n: int):
    """Masked similarity scores [1, tm] of one query against one tile."""
    h = n - popcount_row(x_row ^ a)
    return jnp.where(valid > 0, h, MASKED_SCORE)


def _merge_topk(run_s, run_i, tile_s, tile_i, *, k: int):
    """k best of [running ∪ tile] by (score desc, index asc) — exact.

    One query row: run_* [1, k], tile_* [1, tm]. Each of the k rounds
    takes the best score left on either side, then the least index among
    the entries that hold it, and retires that one entry."""
    slot = lax.broadcasted_iota(jnp.int32, (1, k), 1)

    def best_of(s, i, best):
        return jnp.min(jnp.where(s == best, i, _IDX_SENTINEL), axis=1,
                       keepdims=True)

    def select(j, carry):
        rs, ts, outs, outi = carry
        best = jnp.maximum(jnp.max(rs, axis=1, keepdims=True),
                           jnp.max(ts, axis=1, keepdims=True))      # [1, 1]
        bidx = jnp.minimum(best_of(rs, run_i, best),
                           best_of(ts, tile_i, best))               # [1, 1]
        outs = jnp.where(slot == j, best, outs)
        outi = jnp.where(slot == j, bidx, outi)
        rs = jnp.where((rs == best) & (run_i == bidx), _NEG_TAKEN, rs)
        ts = jnp.where((ts == best) & (tile_i == bidx), _NEG_TAKEN, ts)
        return rs, ts, outs, outi

    zeros = jnp.zeros((1, k), jnp.int32)
    _, _, outs, outi = lax.fori_loop(0, k, select,
                                     (run_s, tile_s, zeros, zeros))
    return outs, outi


def _hamming_topk_kernel(x_ref, a_ref, valid_ref, os_ref, oi_ref, *,
                         n: int, k: int):
    """x_ref [tb, W] u32; a_ref [tm, W] u32; valid_ref [1, tm] i32;
    os_ref/oi_ref [tb, k] i32 — the running top-k, revisited over grid dim 1.
    """
    tm = a_ref.shape[0]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        os_ref[...] = jnp.full_like(os_ref, _NEG_INIT)
        oi_ref[...] = jnp.full_like(oi_ref, _IDX_SENTINEL)

    tile_i = j * tm + lax.broadcasted_iota(jnp.int32, (1, tm), 1)

    def row(r):
        tile_s = _row_scores(x_ref[r, :], a_ref[...], valid_ref[...], n=n)
        outs, outi = _merge_topk(os_ref[r, :], oi_ref[r, :], tile_s, tile_i,
                                 k=k)
        os_ref[r, :] = outs
        oi_ref[r, :] = outi

    for_each_row(x_ref.shape[0], row)


def _hamming_threshold_kernel(x_ref, a_ref, valid_ref, o_ref, *,
                              n: int, delta: int):
    """o_ref [tb, tm] i32: CAM match lines (h >= δ) for live rows."""

    def row(r):
        s = _row_scores(x_ref[r, :], a_ref[...], valid_ref[...], n=n)
        o_ref[r, :] = (s >= delta).astype(jnp.int32)

    for_each_row(x_ref.shape[0], row)


def _pad_operands(x_packed, a_packed, valid, bb, mp):
    """Pad queries to the batch tile and the database to ``mp`` rows; the
    packed words stay whole (one lane block spans them)."""
    b, w = x_packed.shape
    m, w2 = a_packed.shape
    assert w == w2, (w, w2)
    bp = _round_up(b, bb)
    x_p = jnp.pad(x_packed.astype(jnp.uint32), ((0, bp - b), (0, 0)))
    a_p = jnp.pad(a_packed.astype(jnp.uint32), ((0, mp - m), (0, 0)))
    if valid is None:
        valid = jnp.ones((m,), jnp.int32)
    v_p = jnp.pad(jnp.asarray(valid, jnp.int32)[None, :], ((0, 0), (0, mp - m)))
    return x_p, a_p, v_p, bp


def _db_specs(bb, bm, w):
    return [pl.BlockSpec((bb, w), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, w), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bm), lambda i, j: (0, j))]


@functools.partial(
    jax.jit,
    static_argnames=("n", "k", "block_q", "block_m", "interpret"),
)
def hamming_topk_packed(
    x_packed,
    a_packed,
    valid=None,
    *,
    n: int,
    k: int,
    block_q: int = 8,
    block_m: int = 256,
    interpret: bool = False,
):
    """Fused top-k: (scores [B, k], indices [B, k]) int32.

    x_packed [B, W] uint32, a_packed [M, W] uint32, valid [M] (int/bool,
    optional). Requires k <= M. Padding lanes must be zero (xor of equal
    zeros adds 0 to the popcount, so they never change h).
    """
    b, w = x_packed.shape
    m = a_packed.shape[0]
    assert 1 <= k <= m, (k, m)

    bb = min(block_q, _round_up(b, 8))
    bm, mp = lane_tile(m, max(block_m, k))  # a tile must hold k candidates
    x_p, a_p, v_p, bp = _pad_operands(x_packed, a_packed, valid, bb, mp)
    scores, idx = pl.pallas_call(
        functools.partial(_hamming_topk_kernel, n=n, k=k),
        grid=(bp // bb, mp // bm),
        in_specs=_db_specs(bb, bm, w),
        out_specs=(
            pl.BlockSpec((bb, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, k), lambda i, j: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bp, k), jnp.int32),
            jax.ShapeDtypeStruct((bp, k), jnp.int32),
        ),
        interpret=interpret,
    )(x_p, a_p, v_p)
    return scores[:b], idx[:b]


@functools.partial(
    jax.jit,
    static_argnames=("n", "delta", "block_q", "block_m", "interpret"),
)
def hamming_threshold_packed(
    x_packed,
    a_packed,
    valid=None,
    *,
    n: int,
    delta: int,
    block_q: int = 8,
    block_m: int = 256,
    interpret: bool = False,
):
    """Fused CAM δ-match: match lines [B, M] int32 (1 iff h >= δ, row live)."""
    b, w = x_packed.shape
    m = a_packed.shape[0]

    bb = min(block_q, _round_up(b, 8))
    bm, mp = lane_tile(m, block_m)
    x_p, a_p, v_p, bp = _pad_operands(x_packed, a_packed, valid, bb, mp)
    out = pl.pallas_call(
        functools.partial(_hamming_threshold_kernel, n=n, delta=delta),
        grid=(bp // bb, mp // bm),
        in_specs=_db_specs(bb, bm, w),
        out_specs=pl.BlockSpec((bb, bm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, mp), jnp.int32),
        interpret=interpret,
    )(x_p, a_p, v_p)
    return out[:b, :m]
