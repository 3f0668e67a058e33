"""Pallas TPU kernels: fused multi-bitplane (bit-serial) MVP — paper §III-C.

PPAC computes a K-bit-matrix × L-bit-vector MVP over K*L clock cycles of
1-bit AND/XNOR popcounts with shift-add accumulation in the two row-ALU
accumulators. On TPU we fuse the whole K×L schedule into one kernel: the
accumulator lives in VMEM across the lane-tile grid dimension, and each
"cycle" processes a [tb × tm × tw] tile instead of one word:

    y[b, m] = sum_{k<K1} sum_{l<L1} W[k, l] * sum_w popcount(a[k,m,w] & x[l,b,w])

The plane-pair weight matrix W encodes the entire number-format algebra
(Table I + eqs. (2)/(3) offsets). Affine offsets (oddint's -(2^L - 1), the
eq. (2)/(3) precompute) ride in an *extended* [K1+1, L1+1] weight matrix
instead of concatenated mask planes:

    W_ext[k, L1]   weights popcount(a_k)[m]   (x-side all-ones mask folded
                                               into the resident planes —
                                               padding lanes are zero, so
                                               popcount(a & 1...1) == popcount(a))
    W_ext[K1, l]   weights popcount(x_l)[b]   (a-side mask, same argument)
    W_ext[K1, L1]  a constant added once per output block

so no kernel launch ever concatenates or broadcasts onto the resident
[K, M, W] weight — the zero-repack invariant of the serving fast path.
A resident weight *may* carry a stored mask plane (packed at load time by
``core.engine.pack_weight_for_serving`` for offset formats); it is just an
ordinary K1-th plane here.

``bitserial_matmul_sliced`` is the decode fast path: the streaming operand
arrives as L-bit *level codes* (uint32, bit-transposed to [32, B, W]) and
the per-plane packed words are built inside the kernel body with one
shift/AND per plane — no ``to_bitplanes``/``pack_bits`` XLA round trip
around the launch.

Tiling, padding and lane streaming come from :mod:`repro.kernels.tiling`:
the plane stacks ride along as leading block dims (whole stack resident
per tile), and the body walks the tile's streamed rows one at a time, so
arbitrarily large B/M/W stream through fixed VMEM tiles exactly like the
1-bit kernels. The plane-pair weights sit in scalar memory.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tiling import (
    for_each_row,
    lane_stream_call,
    plan_for,
    popcount_row,
    smem_spec,
)


def _lane_popcounts(tile):
    """[rows, tw] uint32 -> [rows, 1] int32 total set bits of this lane tile."""
    return jnp.sum(lax.population_count(tile).astype(jnp.int32), axis=-1,
                   keepdims=True)


def _accumulate_bitserial(x_ref, a_ref, w_ref, o_ref, *, k1: int, l1: int,
                          pop_a: bool, pop_x: bool, const: bool):
    """Shared body: x_ref holds the [l1, tb, tw] packed activation planes;
    a_ref the resident [k1, tm, tw] plane stack; w_ref (scalar memory) the
    extended [k1+1, l1+1] weight matrix (see module docstring)."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        if const:
            # the offset·offset constant lands once per output block
            o_ref[...] = jnp.full(o_ref.shape, w_ref[k1, l1], jnp.int32)
        else:
            o_ref[...] = jnp.zeros_like(o_ref)

    if pop_a:  # a-plane popcounts: the same [1, tm] row for every x row
        o_ref[...] += sum(w_ref[k, l1] * popcount_row(a_ref[k])
                          for k in range(k1))
    if pop_x:  # x-plane popcounts: the same [tb, 1] column for every a row
        o_ref[...] += sum(w_ref[k1, l] * _lane_popcounts(x_ref[l])
                          for l in range(l1))

    def row(r):
        acc = jnp.zeros((1, o_ref.shape[1]), jnp.int32)
        for k in range(k1):      # static unroll: K1*L1 <= ~36 "cycles"
            a_k = a_ref[k]
            for l in range(l1):
                acc += w_ref[k, l] * popcount_row(x_ref[l, r, :] & a_k)
        o_ref[r, :] += acc

    for_each_row(o_ref.shape[0], row)


def _bitserial_kernel(x_ref, a_ref, w_ref, o_ref, *, k1: int, l1: int,
                      pop_a: bool, pop_x: bool, const: bool):
    """x_ref [l1, tb, tw] u32 packed planes; a_ref [k1, tm, tw] u32;
    w_ref [k1+1, l1+1] i32; o_ref [tb, tm] i32 (lane-grid accumulated)."""
    _accumulate_bitserial(x_ref, a_ref, w_ref, o_ref, k1=k1, l1=l1,
                          pop_a=pop_a, pop_x=pop_x, const=const)


def _bitserial_sliced_kernel(u_ref, a_ref, w_ref, o_ref, x_ref, *, k1: int,
                             l1: int, pop_a: bool, pop_x: bool, const: bool):
    """In-kernel bit-slicing body. u_ref [32, tb, tw] u32 holds level codes
    bit-transposed (u_ref[t, b, w] = level code of logical bit 32w+t); each
    of the l1 packed activation planes is built into the x_ref scratch with
    one shift/AND and a shift-weighted reduce over the 32 bit positions —
    the streaming operand never round-trips through XLA bitplanes. The
    reduce runs in int32 (the 32 terms hold disjoint bits, so the sum is
    their OR) and is bitcast back."""
    shifts = lax.broadcasted_iota(jnp.int32, (32, 1, 1), 0).astype(jnp.uint32)
    u = u_ref[...]
    for l in range(l1):
        bits = ((u >> jnp.uint32(l)) & jnp.uint32(1)) << shifts
        plane = jnp.sum(lax.bitcast_convert_type(bits, jnp.int32), axis=0)
        x_ref[l] = lax.bitcast_convert_type(plane, jnp.uint32)
    _accumulate_bitserial(x_ref, a_ref, w_ref, o_ref, k1=k1, l1=l1,
                          pop_a=pop_a, pop_x=pop_x, const=const)


def _normalize_weights(weights, k1: int, l1: int, pop_a, pop_x, const):
    """Accept a plain [k1, l1] plane-pair matrix (pad a zero mask row/col)
    or an extended [k1+1, l1+1] one. Returns (w_ext, pop_a, pop_x, const)
    with unspecified flags resolved conservatively."""
    weights = jnp.asarray(weights, jnp.int32)
    if weights.shape == (k1, l1):
        weights = jnp.pad(weights, ((0, 1), (0, 1)))
        flags = (False, False, False)
    elif weights.shape == (k1 + 1, l1 + 1):
        flags = (True, True, True)  # unknown contents: keep every term
    else:
        raise ValueError(f"weights shape {weights.shape} matches neither "
                         f"[{k1},{l1}] nor [{k1 + 1},{l1 + 1}]")
    pop_a = flags[0] if pop_a is None else pop_a
    pop_x = flags[1] if pop_x is None else pop_x
    const = flags[2] if const is None else const
    return weights, pop_a, pop_x, const


@functools.partial(
    jax.jit,
    static_argnames=("pop_a", "pop_x", "const", "block_b", "block_m",
                     "block_w", "interpret"),
)
def bitserial_matmul_packed(
    x_planes,
    a_planes,
    weights,
    *,
    pop_a=None,
    pop_x=None,
    const=None,
    block_b=None,
    block_m=None,
    block_w=None,
    interpret: bool = False,
):
    """y[b,m] = sum_{k,l} W[k,l] * sum_w popcount(a[k,m,w] & x[l,b,w])
    (+ the extended popcount/constant terms when W is [K1+1, L1+1]).

    x_planes: [L1, B, W] uint32; a_planes: [K1, M, W] uint32; weights:
    [K1, L1] int32 (plain) or [K1+1, L1+1] (extended; ``pop_a``/``pop_x``/
    ``const`` switch the mask-row/col/corner terms on). Returns [B, M]
    int32. Padding lanes must be 0 in every plane. Blocks default to the
    plan cache / decode-aware heuristics (:func:`repro.kernels.tiling.plan_for`).
    """
    l1, b, w = x_planes.shape
    k1, m, w2 = a_planes.shape
    assert w == w2
    weights, pop_a, pop_x, const = _normalize_weights(
        weights, k1, l1, pop_a, pop_x, const)

    plan = plan_for("bitserial", b, m, w, block_b=block_b, block_m=block_m,
                    block_w=block_w)
    return lane_stream_call(
        functools.partial(_bitserial_kernel, k1=k1, l1=l1,
                          pop_a=pop_a, pop_x=pop_x, const=const),
        x_planes, a_planes, plan,
        x_leading=l1, a_leading=k1,
        extra_inputs=(weights,), extra_specs=(smem_spec(),),
        interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("l_bits", "pop_a", "pop_x", "const", "block_b",
                     "block_m", "block_w", "interpret"),
)
def bitserial_matmul_sliced(
    u_stack,
    a_planes,
    weights,
    *,
    l_bits: int,
    pop_a=None,
    pop_x=None,
    const=None,
    block_b=None,
    block_m=None,
    block_w=None,
    interpret: bool = False,
):
    """Decode fast path: same contract as :func:`bitserial_matmul_packed`
    but the streaming operand is ``u_stack`` [32, B, W] uint32 — L-bit
    level codes bit-transposed so u_stack[t, b, w] codes logical bit
    32w+t — and the per-plane packed words are built inside the kernel.
    Zero-padded entries (level code 0) contribute no set bits.
    """
    _, b, w = u_stack.shape
    k1, m, w2 = a_planes.shape
    assert w == w2
    weights, pop_a, pop_x, const = _normalize_weights(
        weights, k1, l_bits, pop_a, pop_x, const)

    plan = plan_for("bitserial_sliced", b, m, w, block_b=block_b,
                    block_m=block_m, block_w=block_w)
    return lane_stream_call(
        functools.partial(_bitserial_sliced_kernel, k1=k1, l1=l_bits,
                          pop_a=pop_a, pop_x=pop_x, const=const),
        u_stack, a_planes, plan,
        x_leading=32, a_leading=k1,
        extra_inputs=(weights,), extra_specs=(smem_spec(),),
        scratch_shapes=(pltpu.VMEM((l_bits, plan.bb, plan.bw), jnp.uint32),),
        interpret=interpret)
