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

``bitserial_matmul_sliced`` is the serving fast path (decode, prefill,
speculative rounds): the streaming operand arrives as L-bit *level codes*
(uint32, bit-transposed to [32, B, W]) — no ``to_bitplanes``/``pack_bits``
XLA round trip around the launch. It has two bodies:

* the MXU body (``pallas_call`` name ``bitserial_mxu``) runs when the
  caller passes ``plane_factors`` (wa, wx) with W[:K1, :L1] = outer(wa, wx),
  which holds for every Table-I pair (``ops.plane_factors``). Then

      sum_{k,l} W[k,l] * popcount(a_k & x_l) = sum_t V_t @ Q_t^T,
      Q_t[m, w] = sum_k wa_k * ((a[k,m,w] >> t) & 1)
      V_t[b, w] = sum_l wx_l * ((u[t,b,w] >> l) & 1)

  are the integer weight and activation at logical column 32w+t, rebuilt
  in VMEM from the resident planes and the streamed codes, and contracted
  on the MXU. The result is exact: int8 x int8 -> int32 when every V and
  Q fits int8 (int/int and uint/uint at 4 x 8 bits, 1-bit ±1), else
  bf16 x bf16 -> f32 where every V and Q is a bfloat16 and one dot's
  lane-tile of products stays below 2^24 (oddint's V reaches 510), each
  dot converted to int32 before it is summed over t and lane tiles;
* the vector-unit body runs otherwise (a plane-pair matrix that need not
  be rank one): per tile the packed activation planes are built in a
  scratch with one shift/AND per plane, and the tile's streamed rows are
  walked one at a time, each AND-popcounted against every resident plane.

Both bodies keep the kernel's signature — one custom call, u32[32, B, W]
codes then the u32[K1, M, W] plane stack in, s32[B, M] out — and return
the same int32 accumulator.

Tiling, padding and lane streaming come from :mod:`repro.kernels.tiling`:
the plane stacks ride along as leading block dims (whole stack resident
per tile), so arbitrarily large B/M/W stream through fixed VMEM tiles
exactly like the 1-bit kernels. The plane-pair weights sit in scalar
memory.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

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

# The MXU body's pallas_call name: a device trace tells the two bodies of
# ``bitserial_matmul_sliced`` apart by it.
MXU_KERNEL_NAME = "bitserial_mxu"


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


def _bit_values(words, coeffs):
    """sum_l coeffs[l] * ((words >> l) & 1) as int32: the value whose bit
    planes are the low bits of ``words``. Table-I coefficients are
    s * (1, 2, .., 2^(L-2), ±2^(L-1)), which is s * words with the top
    bit's sign flipped: a closed form of a few ops, not L shift/ANDs."""
    n = len(coeffs)
    s = coeffs[0]
    low = [s << l for l in range(n - 1)]
    if s > 0 and list(coeffs[:-1]) == low and abs(coeffs[-1]) == s << (n - 1):
        v = lax.bitcast_convert_type(words & jnp.uint32(2**n - 1), jnp.int32)
        if coeffs[-1] < 0:  # two's complement top bit: subtract 2 * 2^(L-1)
            top = lax.bitcast_convert_type(
                (words >> jnp.uint32(n - 1)) & jnp.uint32(1), jnp.int32)
            v = v - (top << n)
        return v * s if s != 1 else v
    return sum(c * lax.bitcast_convert_type(
        (words >> jnp.uint32(l)) & jnp.uint32(1), jnp.int32)
        for l, c in enumerate(coeffs) if c)


def _bitserial_mxu_kernel(u_ref, a_ref, w_ref, o_ref, *, wa, wx, dtype,
                          pop_a: bool, pop_x: bool, const: bool):
    """MXU body for a rank-one plane-pair block W[:K1, :L1] = outer(wa, wx).

    u_ref [32, tb, tw] u32 level codes (u_ref[t, b, w] codes logical
    column 32w+t); a_ref [k1, tm, tw] u32 resident planes; w_ref (scalar
    memory) the extended weights, read only for the popcount/constant
    terms. For each bit position t the kernel rebuilds the integers at
    logical columns 32w+t of both operands in VMEM,

        Q_t[m, w] = sum_k wa[k] * ((a[k, m, w] >> t) & 1)
        V_t[b, w] = sum_l wx[l] * ((u[t, b, w] >> l) & 1),

    and contracts them on the MXU: sum_{k,l} W[k,l] * popcount(a_k & x_l)
    = sum_t V_t @ Q_t^T. Every term is an integer. ``dtype`` int8 takes
    an int8 x int8 -> int32 product; bfloat16 a bf16 x bf16 -> f32 one,
    whose result is exact because each dot's tw terms keep every partial
    sum below 2^24 (checked by the wrapper), and is converted to int32
    before it is added across t."""
    k1, l1 = len(wa), len(wx)
    acc_type = jnp.int32 if dtype == jnp.int8 else jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _init():
        if const:
            o_ref[...] = jnp.full(o_ref.shape, w_ref[k1, l1], jnp.int32)
        else:
            o_ref[...] = jnp.zeros_like(o_ref)

    if pop_a:  # a-plane popcounts: the same [1, tm] row for every x row
        o_ref[...] += sum(w_ref[k, l1] * popcount_row(a_ref[k])
                          for k in range(k1))

    def cast(v):
        if dtype == jnp.int8:
            return v.astype(jnp.int8)
        return v.astype(jnp.float32).astype(dtype)

    acc = jnp.zeros(o_ref.shape, jnp.int32)
    xpop = 0
    for t in range(32):  # static unroll: measured faster than a loop
        u_t = u_ref[t]                                        # [tb, tw]
        q = sum(c * lax.bitcast_convert_type(
            (a_ref[k] >> jnp.uint32(t)) & jnp.uint32(1), jnp.int32)
            for k, c in enumerate(wa) if c)                   # [tm, tw]
        d = lax.dot_general(cast(_bit_values(u_t, wx)), cast(q),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=acc_type)
        acc = acc + d.astype(jnp.int32)
        if pop_x:  # x-plane popcounts: sum_l W[K1, l] * bit l, per column
            xpop = xpop + sum(w_ref[k1, l] * lax.bitcast_convert_type(
                (u_t >> jnp.uint32(l)) & jnp.uint32(1), jnp.int32)
                for l in range(l1))
    if pop_x:
        acc = acc + jnp.sum(xpop, axis=-1, keepdims=True)
    o_ref[...] += acc


def _mxu_operand_dtype(wa, wx, block_w: int):
    """The MXU input type on which ``sum_t V_t @ Q_t^T`` is exact for the
    factors ``(wa, wx)`` at lane tile ``block_w``, or None.

    int8 when every value V and Q can take fits int8 (the product and
    sum are int32); else bfloat16 when every value is a bfloat16 and one
    dot's ``block_w`` products sum below 2^24 in magnitude (float32 holds
    every integer there); else None, and the caller runs the loop body.
    """
    def values(coeffs):  # every value the factor can take, one per bit code
        codes = np.arange(2 ** len(coeffs))[:, None]
        bits = (codes >> np.arange(len(coeffs))) & 1
        return bits @ np.asarray(coeffs, np.int64)

    vv, qv = values(wx), values(wa)
    if all(v.min() >= -128 and v.max() <= 127 for v in (vv, qv)):
        return jnp.int8
    exact = all(np.array_equal(
        v.astype(np.float32).astype(jnp.bfloat16).astype(np.int64), v)
        for v in (vv, qv))
    peak = block_w * np.abs(vv).max() * np.abs(qv).max()
    if exact and peak < 2**24:
        return jnp.bfloat16
    return None


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
    static_argnames=("l_bits", "plane_factors", "pop_a", "pop_x", "const",
                     "block_b", "block_m", "block_w", "interpret"),
)
def bitserial_matmul_sliced(
    u_stack,
    a_planes,
    weights,
    *,
    l_bits: int,
    plane_factors=None,
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
    32w+t. Zero-padded entries (level code 0) contribute no set bits.

    ``plane_factors`` = (wa, wx), tuples of ints, states that the
    plane-pair block of ``weights`` is rank one, W[:K1, :L] =
    outer(wa, wx) (the caller vouches for it). The launch then runs the
    MXU body, named ``MXU_KERNEL_NAME``: per bit position the integer
    operands are rebuilt from the planes in VMEM and contracted on the
    MXU. Without factors, or where no MXU input type is exact for them,
    it runs the vector-unit body: per tile the packed activation planes
    are built in scratch and each streamed row is AND-popcounted against
    every resident plane. Both return the same int32 result.
    """
    _, b, w = u_stack.shape
    k1, m, w2 = a_planes.shape
    assert w == w2
    weights, pop_a, pop_x, const = _normalize_weights(
        weights, k1, l_bits, pop_a, pop_x, const)

    blocks = dict(block_b=block_b, block_m=block_m, block_w=block_w)
    plan = plan_for("bitserial_sliced", b, m, w, **blocks)
    dtype = None
    if plane_factors is not None:
        wa, wx = plane_factors
        assert (len(wa), len(wx)) == (k1, l_bits), (plane_factors, k1, l_bits)
        dtype = _mxu_operand_dtype(wa, wx, plan.bw)
    if dtype is not None:
        return lane_stream_call(
            functools.partial(_bitserial_mxu_kernel, wa=tuple(wa),
                              wx=tuple(wx), dtype=dtype, pop_a=pop_a,
                              pop_x=pop_x, const=const),
            u_stack, a_planes, plan,
            x_leading=32, a_leading=k1,
            extra_inputs=(weights,), extra_specs=(smem_spec(),),
            name=MXU_KERNEL_NAME, interpret=interpret)
    # the loop body holds the whole [32, tb, tw] level-code block as a
    # value: it keeps the thin batch tiles of the packed-plane kernel
    plan = plan_for("bitserial_sliced_loop", b, m, w, **blocks)
    return lane_stream_call(
        functools.partial(_bitserial_sliced_kernel, k1=k1, l1=l_bits,
                          pop_a=pop_a, pop_x=pop_x, const=const),
        u_stack, a_planes, plan,
        x_leading=32, a_leading=k1,
        extra_inputs=(weights,), extra_specs=(smem_spec(),),
        scratch_shapes=(pltpu.VMEM((l_bits, plan.bb, plan.bw), jnp.uint32),),
        interpret=interpret)
