"""Public API for multi-bit PPAC MVPs (paper §III-C) on TPU.

``ppac_matmul`` takes integer operands + number formats (Table I), builds the
logical bitplanes and the plane-pair weight matrix, and dispatches to the
fused Pallas kernel ('pallas'), the jnp oracle ('ref'), or an int8 MXU
lowering ('mxu').

``ppac_matmul_planes`` is the serving variant: the K-bit matrix arrives
already decomposed into packed bitplane lanes (the resident weight layout
of ``core.engine.pack_weight_for_serving``) and only the L-bit vector batch
is decomposed on the fly — the matrix is weight-stationary, exactly the
paper's premise of a static A with streaming x (§IV-A).
``ppac_matmul_resident`` is its decode fast path: the streaming operand is
the quantized integer activation batch itself, bit-sliced *inside* the
Pallas body (no ``to_bitplanes``/``pack_bits`` XLA round trip), and the
optional ``a_int8`` shadow gives the MXU lowering a load-time resident
operand too.

Weight-matrix construction. For an operand with format f and L bits, the
value decomposes over logical planes b_l in {0,1} as

    value = sum_l w_l * b_l + c
      uint   : w_l = 2^l,                      c = 0
      int    : w_l = 2^l, w_{L-1} = -2^{L-1},  c = 0          (2's complement)
      oddint : w_l = 2^{l+1},                  c = -(2^L - 1)

Nonzero offsets c are the TPU generalization of the paper's h̄(a,1)/h̄(a,0)
precompute in eqs. (2)/(3). They are folded into the *extended* weight
matrix consumed by the kernels — coefficients on in-kernel plane popcounts
plus a constant — so the zero-repack invariant holds: nothing is ever
concatenated or broadcast onto an operand at call time. A resident weight
packed by ``pack_weight_for_serving`` may carry its offset as a stored
all-ones mask plane instead (``a_has_mask=True``), in which case the
offset column rides the ordinary plane-pair weights of that plane.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...core.backend import auto_interpret as _auto_interpret
from ...core.formats import (
    NumberFormat,
    fmt,
    from_bitplanes,
    pack_bits,
    plane_weights,
    to_bitplanes,
    to_levels,
    unpack_bits,
    value_range,
)
from ...sharding.compat import split_over_mesh
from .kernel import bitserial_matmul_packed, bitserial_matmul_sliced
from .ref import bitserial_matmul_packed_ref


def _operand_decomposition(f: NumberFormat, bits: int) -> Tuple[np.ndarray, int]:
    """(per-plane weights w_l, constant offset c) for a Table-I format."""
    f = fmt(f)
    if f is NumberFormat.ODDINT:
        w = np.asarray([2 ** (l + 1) for l in range(bits)], np.int64)
        c = -(2**bits - 1)
    else:
        w = plane_weights(f, bits)
        c = 0
    return w, int(c)


def format_needs_mask(f) -> bool:
    """True when the Table-I format carries an affine offset (oddint) —
    the case where ``pack_weight_for_serving`` stores a resident all-ones
    mask plane alongside the value planes."""
    return _operand_decomposition(f, 1)[1] != 0


def extended_weights(fmt_a, k_bits: int, fmt_x, l_bits: int, *, n: int,
                     a_has_mask: bool = False):
    """Build the extended [K1+1, L+1] weight matrix + static term flags.

    Returns (w_ext int32 numpy, k1, pop_a, pop_x, const):
      w_ext[:K1, :L]  plane-pair AND-popcount weights
      w_ext[:K1, L]   coefficients on in-kernel popcount(a_plane_k)[m]
      w_ext[K1, :L]   coefficients on in-kernel popcount(x_plane_l)[b]
      w_ext[K1, L]    constant ca*cx*n, added once per output block

    ``a_has_mask`` means the resident matrix already stores its offset as
    a (K+1)-th all-ones plane: the a-side offset then rides that plane's
    ordinary pair weights and its pop_a column carries the corner term
    (popcount of the mask plane is n, yielding ca*cx*n exactly).
    """
    wa, ca = _operand_decomposition(fmt_a, k_bits)
    wx, cx = _operand_decomposition(fmt_x, l_bits)
    if a_has_mask:
        if ca == 0:
            raise ValueError(f"format {fmt(fmt_a)} carries no offset; "
                             "no resident mask plane expected")
        wa = np.concatenate([wa, [ca]])
        ca = 0
    k1, l1 = len(wa), len(wx)
    w = np.zeros((k1 + 1, l1 + 1), np.int64)
    w[:k1, :l1] = np.outer(wa, wx)
    w[:k1, l1] = wa * cx
    w[k1, :l1] = ca * np.asarray(wx)
    w[k1, l1] = ca * cx * n
    assert np.abs(w).max() < 2**31, "plane weights overflow int32"
    pop_a = bool(np.any(w[:k1, l1]))
    pop_x = bool(np.any(w[k1, :l1]))
    const = bool(w[k1, l1])
    return np.asarray(w, np.int32), k1, pop_a, pop_x, const


def build_planes_and_weights(x_int, a_int, k_bits: int, l_bits: int,
                             fmt_a, fmt_x):
    """Returns (x_planes [L,B,W], a_planes [K,M,W], w_ext [K+1,L+1], flags).

    Offsets live entirely in the extended weight matrix — neither operand
    grows a mask plane."""
    fmt_a, fmt_x = fmt(fmt_a), fmt(fmt_x)
    n = x_int.shape[1]
    assert a_int.shape[1] == n
    w_ext, _, pop_a, pop_x, const = extended_weights(
        fmt_a, k_bits, fmt_x, l_bits, n=n)
    xp = pack_bits(to_bitplanes(x_int, l_bits, fmt_x))  # (L,B,W)
    ap = pack_bits(to_bitplanes(a_int, k_bits, fmt_a))  # (K,M,W)
    return xp, ap, jnp.asarray(w_ext), (pop_a, pop_x, const)


def _int8_operands(fmt_a, k_bits: int, fmt_x, l_bits: int) -> bool:
    """True when both Table-I value ranges fit int8 (the accumulation is
    int32 either way, so the narrow input dtype is purely a speed lever)."""
    ranges = (value_range(fmt_a, k_bits), value_range(fmt_x, l_bits))
    return all(lo >= -128 and hi <= 127 for lo, hi in ranges)


def _mxu_dot(x_int, a_int, k_bits: int, l_bits: int, fmt_a="int",
             fmt_x="int"):
    """Beyond-paper MXU lowering on integer operands (bit-true int32
    accumulation; int8 inputs when the format ranges fit)."""
    xi = jnp.asarray(x_int, jnp.int32)
    ai = jnp.asarray(a_int, jnp.int32)
    dt = jnp.int8 if _int8_operands(fmt_a, k_bits, fmt_x, l_bits) \
        else jnp.int32
    return jax.lax.dot_general(
        xi.astype(dt), ai.astype(dt), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("k_bits", "l_bits", "fmt_a", "fmt_x",
                                    "backend"))
def ppac_matmul(x_int, a_int, *, k_bits: int, l_bits: int,
                fmt_a="int", fmt_x="int", backend: str = "pallas"):
    """y[b,m] = <a_m, x_b> for K-bit A (resident matrix) and L-bit x.

    Bit-true int32 result; equivalent PPAC cost is K*L cycles per MVP.
    """
    fa, fx = fmt(fmt_a), fmt(fmt_x)
    if backend == "mxu":
        return _mxu_dot(x_int, a_int, k_bits, l_bits, fa, fx)
    xp, ap, w, (pop_a, pop_x, const) = build_planes_and_weights(
        x_int, a_int, k_bits, l_bits, fa, fx)
    if backend == "pallas":
        return bitserial_matmul_packed(xp, ap, w, pop_a=pop_a, pop_x=pop_x,
                                       const=const,
                                       interpret=_auto_interpret())
    if backend == "ref":
        return bitserial_matmul_packed_ref(xp, ap, w)
    raise ValueError(f"unknown backend {backend}")


def _planes_to_int(a_planes, n: int, k_bits: int, fa) -> jnp.ndarray:
    """Fold resident value planes (mask plane excluded) back to integers —
    the legacy MXU fallback when no load-time int8 shadow exists."""
    a_bits = unpack_bits(jnp.asarray(a_planes[:k_bits], jnp.uint32), n)
    return from_bitplanes(a_bits, fa)


@functools.partial(jax.jit,
                   static_argnames=("n", "k_bits", "l_bits", "fmt_a", "fmt_x",
                                    "a_has_mask", "backend"))
def ppac_matmul_planes(x_int, a_planes, *, n: int, k_bits: int, l_bits: int,
                       fmt_a="int", fmt_x="int", a_has_mask: bool = False,
                       backend: str = "pallas"):
    """y[b,m] = <a_m, x_b> against a *pre-packed* K-plane resident matrix.

    a_planes: [K1, M, ceil(n/32)] uint32 — the K logical bitplanes of the
    K-bit matrix in packed lane form (lanes beyond ``n`` zero, as
    ``core.formats.pack_bits`` guarantees), plus a stored all-ones mask
    plane when ``a_has_mask`` (offset formats packed at load time);
    x_int: [B, n] integers in the ``fmt_x`` L-bit range, decomposed on the
    fly. Bit-true int32 result, identical across backends and to
    ``ppac_matmul`` on the unpacked ints. Never concatenates onto or
    broadcasts over the resident planes.
    """
    fa, fx = fmt(fmt_a), fmt(fmt_x)
    assert a_planes.shape[0] == k_bits + bool(a_has_mask), \
        (a_planes.shape, k_bits, a_has_mask)

    if backend == "mxu":
        return _mxu_dot(x_int, _planes_to_int(a_planes, n, k_bits, fa),
                        k_bits, l_bits, fa, fx)

    w_ext, _, pop_a, pop_x, const = extended_weights(
        fa, k_bits, fx, l_bits, n=n, a_has_mask=a_has_mask)
    xp = pack_bits(to_bitplanes(x_int, l_bits, fx))    # [L, B, W]
    ap = jnp.asarray(a_planes, jnp.uint32)
    w = jnp.asarray(w_ext)
    if backend == "pallas":
        return bitserial_matmul_packed(xp, ap, w, pop_a=pop_a, pop_x=pop_x,
                                       const=const,
                                       interpret=_auto_interpret())
    if backend == "ref":
        return bitserial_matmul_packed_ref(xp, ap, w)
    raise ValueError(f"unknown backend {backend}")


def levels_to_stack(u, w: int) -> jnp.ndarray:
    """[B, n] level codes -> the bit-transposed [32, B, w] uint32 stack the
    sliced kernel streams (u_stack[t, b, j] codes logical bit 32j+t).
    Zero-padded in the level-code domain, so padding contributes no bits."""
    b, n = u.shape
    u = jnp.asarray(u, jnp.uint32)
    u = jnp.pad(u, ((0, 0), (0, w * 32 - n)))
    return u.reshape(b, w, 32).transpose(2, 0, 1)


@functools.partial(jax.jit,
                   static_argnames=("n", "k_bits", "l_bits", "fmt_a", "fmt_x",
                                    "a_has_mask", "backend", "block_b",
                                    "block_m", "block_w"))
def ppac_matmul_resident(x_int, a_planes, *, n: int, k_bits: int,
                         l_bits: int, fmt_a="int", fmt_x="int",
                         a_has_mask: bool = False, backend: str = "pallas",
                         a_int8=None, block_b=None, block_m=None,
                         block_w=None):
    """The decode fast path: quantized [B, n] activations against resident
    packed planes, activation bit-slicing *inside* the kernel.

    Bit-identical to :func:`ppac_matmul_planes` (tested); differences are
    purely in data movement:
      * 'pallas' streams L-bit level codes and builds the packed planes
        per tile in the kernel body — no to_bitplanes/pack_bits round trip;
      * 'mxu' consumes ``a_int8`` — the int8 shadow materialized at load
        time by ``pack_weight_for_serving`` — instead of unpacking the
        planes per call (falls back to the legacy unpack when absent);
      * 'ref' is the jnp oracle on XLA-built planes.
    Tile blocks default to the autotune cache / decode-aware heuristics.
    """
    fa, fx = fmt(fmt_a), fmt(fmt_x)
    assert a_planes.shape[0] == k_bits + bool(a_has_mask), \
        (a_planes.shape, k_bits, a_has_mask)

    if backend == "mxu":
        if a_int8 is not None:
            # load-time shadow [n, M]: contract directly against its
            # leading dim — no per-call transpose of the resident operand
            dt = (jnp.int8 if _int8_operands(fa, k_bits, fx, l_bits)
                  else jnp.int32)
            return jax.lax.dot_general(
                jnp.asarray(x_int, jnp.int32).astype(dt), a_int8.astype(dt),
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
        return _mxu_dot(x_int, _planes_to_int(a_planes, n, k_bits, fa),
                        k_bits, l_bits, fa, fx)

    w_ext, _, pop_a, pop_x, const = extended_weights(
        fa, k_bits, fx, l_bits, n=n, a_has_mask=a_has_mask)
    ap = jnp.asarray(a_planes, jnp.uint32)
    w = jnp.asarray(w_ext)
    if backend == "ref":
        xp = pack_bits(to_bitplanes(x_int, l_bits, fx))
        return bitserial_matmul_packed_ref(xp, ap, w)
    if backend == "pallas":
        u = levels_to_stack(to_levels(x_int, l_bits, fx), ap.shape[-1])
        kernel = functools.partial(
            bitserial_matmul_sliced, l_bits=l_bits, pop_a=pop_a, pop_x=pop_x,
            const=const, block_b=block_b, block_m=block_m, block_w=block_w,
            interpret=_auto_interpret())
        return split_over_mesh(kernel, u, ap, w, x_rows=1, a_rows=1)
    raise ValueError(f"unknown backend {backend}")


def ppac_cycles(k_bits: int, l_bits: int) -> int:
    """Emulated-PPAC cycle cost of one multi-bit MVP (§III-C)."""
    return k_bits * l_bits
