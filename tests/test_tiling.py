"""Tiled-kernel regression guards for the shared tiling engine.

The lane-streamed kernels must reproduce, tile-for-tile, the result of a
single whole-matrix launch (block sizes >= the padded operand — exactly
the pre-refactor whole-matrix behavior) on shapes spanning several tiles
in every grid dimension, and both must match the jnp oracles.
"""
import functools

import jax
import numpy as np
import pytest

from repro.core import formats as F
from repro.kernels.binary_mvp.kernel import binary_matmul_packed
from repro.kernels.binary_mvp.ref import binary_matmul_packed_ref
from repro.kernels.bitserial_mvp.kernel import (
    MXU_KERNEL_NAME,
    bitserial_matmul_packed,
    bitserial_matmul_sliced,
)
from repro.kernels.bitserial_mvp.ops import (
    extended_weights,
    levels_to_stack,
    plane_factors,
)
from repro.kernels.bitserial_mvp.ref import (
    bitserial_matmul_packed_ref,
    integer_matmul_ref,
)
from repro.kernels.gf2_tiled.kernel import gf2_matmul_packed
from repro.kernels.gf2_tiled.ref import gf2_matmul_packed_ref
from repro.kernels.tiling import (
    PlanCache,
    autotune_plan,
    plan_cache,
    plan_for,
    plan_tiles,
    round_up,
)

# b=70 > block_b=64, m=300 > block_m=256, n=9000 -> W=282 > block_w=256 ->
# the default plans stream several tiles along every grid dimension.
MULTI_TILE = (70, 300, 9000)


def test_plan_tiles_invariants():
    for b, m, w in [(1, 1, 1), (7, 9, 3), (70, 300, 188), (64, 128, 64)]:
        p = plan_tiles(b, m, w)
        assert p.bp % p.bb == 0 and p.mp % p.bm == 0 and p.wp % p.bw == 0
        # the TPU block rule: sublane tiles in 8s; lane-dim tiles (rows of
        # the output, packed words of the operands) in 128s or whole
        assert p.bb % 8 == 0
        assert p.bm == p.mp or p.bm % 128 == 0
        assert p.bw == p.wp or p.bw % 128 == 0
        assert p.bp >= b and p.mp >= m and p.wp >= w
        gb, gm, gw = p.grid
        assert gb * p.bb == p.bp and gm * p.bm == p.mp and gw * p.bw == p.wp


def test_plan_tiles_single_tile_when_blocks_cover():
    b, m, w = MULTI_TILE
    wl = F.packed_width(w)
    p = plan_tiles(b, m, wl, block_b=round_up(b, 8), block_m=round_up(m, 8),
                   block_w=round_up(wl, 128))
    assert p.grid == (1, 1, 1)


@pytest.mark.parametrize("op", ["xor", "and"])
def test_binary_streamed_vs_whole_matrix(rng, op):
    b, m, n = MULTI_TILE
    x = F.pack_bits(rng.integers(0, 2, (b, n)))
    a = F.pack_bits(rng.integers(0, 2, (m, n)))
    wl = x.shape[1]
    assert wl > 256  # more than one default lane tile
    streamed = np.asarray(binary_matmul_packed(x, a, op=op, interpret=True))
    whole = np.asarray(binary_matmul_packed(
        x, a, op=op, block_b=round_up(b, 8), block_m=round_up(m, 8),
        block_w=round_up(wl, 128), interpret=True))
    ref = np.asarray(binary_matmul_packed_ref(x, a, op=op))
    assert np.array_equal(streamed, whole)
    assert np.array_equal(streamed, ref)


def test_bitserial_streamed_vs_whole_matrix(rng):
    l1, k1, b, m, wl = 3, 2, 20, 300, 300  # > block_m, block_w: streaming
    xp = rng.integers(0, 2**32, (l1, b, wl), dtype=np.uint32)
    ap = rng.integers(0, 2**32, (k1, m, wl), dtype=np.uint32)
    w = rng.integers(-8, 8, (k1, l1)).astype(np.int32)
    streamed = np.asarray(bitserial_matmul_packed(xp, ap, w, interpret=True))
    whole = np.asarray(bitserial_matmul_packed(
        xp, ap, w, block_b=round_up(b, 8), block_m=round_up(m, 8),
        block_w=round_up(wl, 128), interpret=True))
    ref = np.asarray(bitserial_matmul_packed_ref(xp, ap, w))
    assert np.array_equal(streamed, whole)
    assert np.array_equal(streamed, ref)


def test_gf2_streamed_vs_whole_matrix(rng):
    b, m, n = 24, 300, 9000  # W=282 > block_w=256 -> several lane tiles
    x = F.pack_bits(rng.integers(0, 2, (b, n)))
    a = F.pack_bits(rng.integers(0, 2, (m, n)))
    wl = x.shape[1]
    streamed = np.asarray(gf2_matmul_packed(x, a, interpret=True))
    whole = np.asarray(gf2_matmul_packed(
        x, a, block_b=round_up(b, 8), block_m=round_up(m, 8),
        block_w=round_up(wl, 128), interpret=True))
    ref = np.asarray(gf2_matmul_packed_ref(x, a))
    assert np.array_equal(streamed, whole)
    assert np.array_equal(streamed, ref)


def test_plan_tiles_rounds_row_tile_up_to_chunk():
    """Row and lane tiles land on the TPU's 128-lane chunk: a request that
    does not cover the extent rounds UP to a 128 multiple (a 64-word lane
    tile once broke the block rule for every row wider than one tile),
    and a tile that covers the extent is the whole unpadded extent."""
    p = plan_tiles(8, 300, 4, block_m=13)
    assert p.bm == 128 and p.mp == 384
    # a rounded tile that covers the rows becomes the whole extent
    p2 = plan_tiles(8, 100, 4, block_m=13)
    assert p2.bm == p2.mp == 100
    # lanes follow the same rule: 64 words round up to 128
    p3 = plan_tiles(8, 8, 300, block_w=64)
    assert p3.bw == 128 and p3.wp == 384
    p4 = plan_tiles(8, 8, 80, block_w=64)
    assert p4.bw == p4.wp == 80


def test_prime_row_tile_result_unchanged(rng):
    """Tiling geometry is invisible: the rounded-up prime-tile plan still
    reproduces the oracle bitwise."""
    x = F.pack_bits(rng.integers(0, 2, (13, 700)))
    a = F.pack_bits(rng.integers(0, 2, (37, 700)))
    ref = np.asarray(binary_matmul_packed_ref(x, a, op="xor"))
    got = np.asarray(binary_matmul_packed(x, a, op="xor", block_m=13,
                                          interpret=True))
    assert np.array_equal(got, ref)


def test_bitserial_sliced_matches_packed(rng):
    """The in-kernel bit-slicing variant == the packed-plane kernel ==
    the oracle, on a multi-tile lane-streamed shape."""
    k1, b, m, n, l_bits = 2, 20, 140, 2201, 3  # wl=69 > default lane tile
    ap = rng.integers(0, 2**32, (k1, m, F.packed_width(n)), dtype=np.uint32)
    x = rng.integers(-(2 ** (l_bits - 1)), 2 ** (l_bits - 1), (b, n))
    u = levels_to_stack(F.to_levels(x, l_bits, "int"), F.packed_width(n))
    xp = F.pack_bits(F.to_bitplanes(x, l_bits, "int"))
    w = rng.integers(-8, 8, (k1, l_bits)).astype(np.int32)
    sliced = np.asarray(bitserial_matmul_sliced(u, ap, w, l_bits=l_bits,
                                                interpret=True))
    packed = np.asarray(bitserial_matmul_packed(xp, ap, w, interpret=True))
    ref = np.asarray(bitserial_matmul_packed_ref(xp, ap, w))
    assert np.array_equal(sliced, packed)
    assert np.array_equal(sliced, ref)


def _format_operand(rng, f, bits, shape):
    lo, hi = F.value_range(f, bits)
    v = rng.integers(lo, hi + 1, shape)
    return np.clip(v | 1, lo, hi) if f == "oddint" else v


# (fmt_a, k_bits, fmt_x, l_bits, stored mask plane): every pair the serving
# path builds — packed4 int/uint, packed4 oddint with its mask plane, and
# the packed1 ±1 rung as 1-bit oddint
SERVED_PAIRS = [("int", 4, "int", 8, False), ("uint", 4, "uint", 8, False),
                ("oddint", 4, "oddint", 8, True),
                ("oddint", 1, "oddint", 1, False)]
# (b, m, n, blocks): a decode batch with 2 batch, 3 row and 3 lane tiles;
# a prefill batch on the mode's own default plan (2 x 2 x 2 tiles)
MXU_SHAPES = [(64, 300, 9000, dict(block_b=32, block_m=128, block_w=128)),
              (264, 300, 4200, {})]


@pytest.mark.parametrize("shape", MXU_SHAPES, ids=["decode", "prefill"])
@pytest.mark.parametrize("pair", SERVED_PAIRS,
                         ids=["int4x8", "uint4x8", "oddint4x8mask",
                              "packed1"])
def test_bitserial_mxu_body_matches_loop(rng, pair, shape):
    """With plane factors the sliced kernel runs its MXU body: bit-identical
    to the vector-unit loop, to the packed-plane oracle and to the integer
    product, for every format pair the serving path hands it."""
    fa, kb, fx, lb, mask = pair
    b, m, n, blocks = shape
    a = _format_operand(rng, fa, kb, (m, n))
    x = _format_operand(rng, fx, lb, (b, n))
    planes = F.to_bitplanes(a, kb, fa)
    if mask:
        planes = np.concatenate([planes, np.ones((1, m, n), np.uint8)])
    ap = F.pack_bits(planes)
    w_ext, k1, pop_a, pop_x, const = extended_weights(
        fa, kb, fx, lb, n=n, a_has_mask=mask)
    factors = plane_factors(fa, kb, fx, lb, a_has_mask=mask)
    assert np.array_equal(w_ext[:k1, :lb], np.outer(*factors))

    u = levels_to_stack(F.to_levels(x, lb, fx), F.packed_width(n))
    kw = dict(l_bits=lb, pop_a=pop_a, pop_x=pop_x, const=const,
              interpret=True, **blocks)
    with_factors = functools.partial(bitserial_matmul_sliced,
                                     plane_factors=factors, **kw)
    assert f"name={MXU_KERNEL_NAME}" in str(
        jax.make_jaxpr(with_factors)(u, ap, w_ext))
    mxu = np.asarray(with_factors(u, ap, w_ext))
    loop = np.asarray(bitserial_matmul_sliced(u, ap, w_ext, **kw))
    xp = F.pack_bits(F.to_bitplanes(x, lb, fx))
    ref = np.concatenate([  # the oracle's [K1, L1, rows, m, W] in row chunks
        np.asarray(bitserial_matmul_packed_ref(xp[:, r:r + 16], ap, w_ext))
        for r in range(0, b, 16)])
    assert np.array_equal(mxu, loop)
    assert np.array_equal(mxu, ref)
    assert np.array_equal(mxu, np.asarray(integer_matmul_ref(x, a)))


def test_autotune_cache_roundtrip(rng, tmp_path, monkeypatch):
    """autotune_plan persists the winning blocks; plan_for and a fresh
    PlanCache instance both read them back."""
    monkeypatch.setenv("PPAC_TILE_CACHE", str(tmp_path / "plans.json"))
    b, m, n = 20, 24, 300
    wl = F.packed_width(n)
    xp = F.pack_bits(rng.integers(0, 2, (2, b, n)))
    ap = F.pack_bits(rng.integers(0, 2, (2, m, n)))
    w = rng.integers(-4, 4, (2, 2)).astype(np.int32)
    candidates = [dict(block_b=8, block_m=256, block_w=256),
                  dict(block_b=32, block_m=256, block_w=256)]

    def run(plan):
        return bitserial_matmul_packed(xp, ap, w, interpret=True,
                                       **plan.blocks)

    tuned = autotune_plan("bitserial", b, m, wl, run, candidates=candidates,
                          reps=1)
    resolved = [plan_tiles(b, m, wl, **c).blocks for c in candidates]
    assert tuned.blocks in resolved  # winner is one of the candidates
    # plan_for (same process) returns the tuned geometry, not the default
    assert plan_for("bitserial", b, m, wl).blocks == tuned.blocks
    # a fresh cache object re-reads the persisted JSON
    fresh = PlanCache(str(tmp_path / "plans.json"))
    stored = fresh.get("bitserial", b, m, wl)
    assert stored is not None
    assert plan_tiles(b, m, wl, **stored).blocks == tuned.blocks
    # explicit overrides still beat the cache
    assert plan_for("bitserial", b, m, wl, block_b=16).bb == 16


def test_decode_defaults_use_thin_batch_tile(monkeypatch):
    monkeypatch.delenv("PPAC_TILE_CACHE", raising=False)
    p = plan_for("bitserial", 2, 512, 64)
    assert p.bb == 8          # decode-shaped: tiny batch tile
    assert p.bm >= 128        # ... traded for a fatter row tile
    big = plan_for("bitserial", 128, 512, 64)
    assert big.bb == 64
    # the sliced kernel's MXU body takes a prefill batch in 256-row tiles;
    # its loop body keeps the thin ones
    assert plan_for("bitserial_sliced", 1024, 5120, 30).bb == 256
    assert plan_for("bitserial_sliced", 64, 5120, 30).bb == 64
    assert plan_for("bitserial_sliced_loop", 1024, 5120, 30).bb == 64


def test_binary_block_sweep_agrees(rng):
    """Any legal block geometry produces the same S (tiling is invisible)."""
    x = F.pack_bits(rng.integers(0, 2, (13, 700)))
    a = F.pack_bits(rng.integers(0, 2, (37, 700)))
    ref = np.asarray(binary_matmul_packed_ref(x, a, op="xor"))
    for bb, bm, bw in [(8, 8, 128), (16, 24, 128), (64, 128, 16)]:
        got = np.asarray(binary_matmul_packed(
            x, a, op="xor", block_b=bb, block_m=bm, block_w=bw,
            interpret=True))
        assert np.array_equal(got, ref), (bb, bm, bw)
