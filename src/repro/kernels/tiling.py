"""Shared tiling machinery for the PPAC Pallas kernels.

Every PPAC matmul-like kernel in this package streams the same way: packed
uint32 operands x [.., B, W] and a [.., M, W] are padded up to tile
multiples, a (B/bb, M/bm, W/bw) grid walks batch × row × lane tiles with
the lane dimension innermost, and the revisited [bb, bm] int32 output
block accumulates one contribution per lane tile (integer add for the
popcount modes, XOR for GF(2) parity). Inside a tile the body walks the
streamed rows one at a time (:func:`for_each_row`): each row is one PPAC
array cycle, its vector broadcast against the resident [bm, bw] tile and
every row's popcount landing on its output lane (:func:`popcount_row`).
That keeps the per-step intermediate at one resident tile — the bound the
paper's subrow partitioning (Fig. 2) puts on adder fan-in in hardware.

Tiles obey the TPU block rule: batch tiles are multiples of 8 sublanes;
row tiles (the output's lane dim) and lane tiles are multiples of 128 or
the whole unpadded extent (:func:`lane_tile`).

This module owns that machinery once: tile planning (:func:`plan_tiles`),
zero-padding (:func:`pad_lanes`), the row loop and the canonical
lane-streamed ``pallas_call`` (:func:`lane_stream_call`). The per-mode
kernels (``binary_mvp``, ``bitserial_mvp``, ``gf2_tiled``) are thin bodies
on top; ``hamming_topk`` reuses the planning + row loop with its own 2-D
grid (its output is a running top-k, not a revisited matmul block).

Tile-plan selection (:func:`plan_for`) is three-tiered: an explicit block
override always wins; otherwise a measured autotune result from the JSON
file ``PPAC_TILE_CACHE`` names, when it names one (keyed on mode × logical
shape × platform, refreshed via :func:`autotune_plan`); otherwise
shape-aware defaults — decode steps have tiny batches, so small-B
launches get a thin batch tile.

Padding is always with zero lanes, which every mode tolerates by
construction: XOR of equal zeros and AND against zero both popcount to 0,
so padded bit-cells never change a sum or flip a parity.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from ..obs import ledger as _flight

# TPU layout friendliness: lane (last) dims in multiples of 128, sublane
# (second-to-last) dims in multiples of 8.
LANE_MULTIPLE = 128
SUBLANE_MULTIPLE = 8

# Batch/row/lane tiles stream independently; only the lane (accumulation)
# dimension carries a loop dependence through the revisited output block.
GRID_SEMANTICS = ("parallel", "parallel", "arbitrary")


def round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """Resolved tile geometry for one lane-streamed kernel launch."""

    b: int          # logical batch rows
    m: int          # logical matrix rows
    w: int          # logical packed lanes
    bb: int         # batch tile
    bm: int         # row tile
    bw: int         # lane tile
    bp: int         # padded batch
    mp: int         # padded rows
    wp: int         # padded lanes

    @property
    def grid(self):
        """(batch tiles, row tiles, lane tiles) — lane dim innermost."""
        return (self.bp // self.bb, self.mp // self.bm, self.wp // self.bw)

    @property
    def blocks(self) -> Dict[str, int]:
        """The three tunable knobs, as kwargs for the kernel wrappers."""
        return dict(block_b=self.bb, block_m=self.bm, block_w=self.bw)


def lane_tile(n: int, block: int):
    """(tile, padded extent) of a dim that lands on TPU lanes in some block
    (the packed words of an operand, the rows of the output). Either one
    tile spans the whole unpadded extent, or tiles are whole multiples of
    128 lanes — the two shapes the TPU block rule admits."""
    n = max(n, 1)
    tile = round_up(block, LANE_MULTIPLE)
    if n <= block or n <= tile:
        return n, n
    return tile, round_up(n, tile)


def plan_tiles(b: int, m: int, w: int, *, block_b: int = 64,
               block_m: int = 256, block_w: int = 256) -> TilePlan:
    """Clamp requested block sizes to the operand shape and derive the
    padded geometry. Batch tiles are sublane multiples; row and lane tiles
    follow :func:`lane_tile` (rows are the output block's lane dim)."""
    bb = min(round_up(block_b, SUBLANE_MULTIPLE), round_up(b, SUBLANE_MULTIPLE))
    bm, mp = lane_tile(m, block_m)
    bw, wp = lane_tile(w, block_w)
    plan = TilePlan(b, m, w, bb, bm, bw, round_up(b, bb), mp, wp)
    # flight recorder: attach the resolved plan to the launch currently
    # being recorded (no-op unless a ledger is open AND a launch is live)
    _flight.note_plan(plan)
    return plan


# ---------------------------------------------------------------------------
# Decode-aware defaults + persisted autotune cache
# ---------------------------------------------------------------------------

CACHE_ENV = "PPAC_TILE_CACHE"


def default_blocks(b: int, m: int, w: int) -> Dict[str, int]:
    """Shape-aware default blocks. Decode steps stream a tiny batch (a few
    tokens) against a large resident matrix: an 8-row batch tile keeps the
    per-grid-step row loop short while the resident tile stays fat, so
    the K·L popcount schedule amortizes over more resident rows."""
    if b <= 8:
        return dict(block_b=SUBLANE_MULTIPLE, block_m=256, block_w=256)
    if b <= 32:
        return dict(block_b=32, block_m=256, block_w=256)
    return dict(block_b=64, block_m=256, block_w=256)


class PlanCache:
    """Persisted (mode, shape, platform) -> block-dict autotune cache.

    One tiny JSON file named by the ``PPAC_TILE_CACHE`` env var; loaded
    lazily once per process, rewritten atomically on every :meth:`put`.
    """

    def __init__(self, path: str):
        self.path = os.path.expanduser(path)
        self._data: Optional[Dict[str, Dict[str, int]]] = None

    @staticmethod
    def key(mode: str, b: int, m: int, w: int,
            platform: Optional[str] = None) -> str:
        platform = platform or jax.default_backend()
        return f"{mode}|b{b}|m{m}|w{w}|{platform}"

    def _load(self) -> Dict[str, Dict[str, int]]:
        if self._data is None:
            try:
                with open(self.path) as f:
                    self._data = json.load(f)
            except (OSError, ValueError):
                self._data = {}
        return self._data

    def get(self, mode: str, b: int, m: int, w: int) -> Optional[Dict[str, int]]:
        hit = self._load().get(self.key(mode, b, m, w))
        if hit is None:
            return None
        return {k: int(hit[k]) for k in ("block_b", "block_m", "block_w")
                if k in hit}

    def put(self, mode: str, b: int, m: int, w: int,
            blocks: Dict[str, int], *, us: Optional[float] = None) -> None:
        data = self._load()
        entry = dict(blocks)
        if us is not None:
            entry["us"] = round(float(us), 2)
        data[self.key(mode, b, m, w)] = entry
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        tmp = f"{self.path}.tmp"
        with open(tmp, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
        os.replace(tmp, self.path)


_CACHES: Dict[str, PlanCache] = {}


def plan_cache() -> Optional[PlanCache]:
    """Process-wide cache for the file ``PPAC_TILE_CACHE`` names, or None
    when it names none: launches then use the shape defaults, so no file
    outside the caller's control changes what a run compiles."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        return None
    path = os.path.expanduser(path)
    if path not in _CACHES:
        _CACHES[path] = PlanCache(path)
    return _CACHES[path]


def plan_for(mode: str, b: int, m: int, w: int, *,
             block_b: Optional[int] = None, block_m: Optional[int] = None,
             block_w: Optional[int] = None) -> TilePlan:
    """Resolve the tile plan for one launch: explicit overrides win, then
    the autotune cache (when one is named), then the decode-aware
    defaults."""
    blocks = default_blocks(b, m, w)
    cache = plan_cache()
    if cache is not None:
        blocks.update(cache.get(mode, b, m, w) or {})
    for name, val in (("block_b", block_b), ("block_m", block_m),
                      ("block_w", block_w)):
        if val is not None:
            blocks[name] = val
    return plan_tiles(b, m, w, **blocks)


def _distinct_geometries(b: int, m: int, w: int, trial):
    """Drop block dicts that resolve to an already-seen geometry (clamping
    makes many candidates collapse on small shapes)."""
    seen, out = set(), []
    for blocks in trial:
        plan = plan_tiles(b, m, w, **blocks)
        sig = (plan.bb, plan.bm, plan.bw)
        if sig not in seen:
            seen.add(sig)
            out.append(blocks)
    return out


def candidate_blocks(b: int, m: int, w: int):
    """Small measured-search space around the defaults."""
    return _distinct_geometries(b, m, w, [
        dict(block_b=bb, block_m=bm, block_w=bw)
        for bb in (SUBLANE_MULTIPLE, 32, 64)
        for bm in (128, 256, 512)
        for bw in (128, 256, 512)])


def quick_candidates(b: int, m: int, w: int):
    """A handful of variations around the shape defaults — the compile
    cost per candidate dominates off-TPU, so the serving autotune sweeps
    this trimmed set by default (full sweep: :func:`candidate_blocks`)."""
    base = default_blocks(b, m, w)
    return _distinct_geometries(b, m, w, [
        base, {**base, "block_m": 128}, {**base, "block_m": 512},
        {**base, "block_w": 128}])


def autotune_plan(mode: str, b: int, m: int, w: int,
                  run: Callable[[TilePlan], object], *,
                  candidates=None, reps: int = 3,
                  cache: Optional[PlanCache] = None) -> TilePlan:
    """Measure ``run(plan)`` over candidate block geometries, persist the
    winner in the plan cache, and return its plan.

    ``run`` must execute the kernel under test with the plan's blocks and
    return the jax result (blocked on for timing). The first call per
    candidate compiles and is discarded; the best median-of-``reps`` wins.
    """
    cache = cache or plan_cache()
    if cache is None:
        raise ValueError(f"autotuning persists plans: set {CACHE_ENV} to "
                         "the JSON file that should hold them")
    best_blocks, best_us, last_err = None, None, None
    for blocks in (candidates or candidate_blocks(b, m, w)):
        plan = plan_tiles(b, m, w, **blocks)
        try:
            jax.block_until_ready(run(plan))  # compile + warm
        except Exception as e:  # geometry rejected by the backend: skip
            last_err = e
            continue
        samples = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(run(plan))
            samples.append((time.perf_counter() - t0) * 1e6)
        us = sorted(samples)[len(samples) // 2]
        if best_us is None or us < best_us:
            best_blocks, best_us = blocks, us
    if best_blocks is None:
        # every candidate failed -> the problem is the run callable, not
        # the geometry; surface the real error
        raise RuntimeError(f"no viable tile candidate for {mode} "
                           f"b={b} m={m} w={w}") from last_err
    cache.put(mode, b, m, w, best_blocks, us=best_us)
    return plan_tiles(b, m, w, **best_blocks)


# ---------------------------------------------------------------------------
# Kernel plumbing
# ---------------------------------------------------------------------------

def pad_lanes(arr, rows_to: int, lanes_to: int) -> jnp.ndarray:
    """Zero-pad the trailing [rows, lanes] dims of a packed uint32 operand;
    leading (bit-plane) dims pass through untouched."""
    arr = jnp.asarray(arr, jnp.uint32)
    pads = ([(0, 0)] * (arr.ndim - 2)
            + [(0, rows_to - arr.shape[-2]), (0, lanes_to - arr.shape[-1])])
    return jnp.pad(arr, pads)


def popcount_row(bits):
    """[tm, tw] uint32 -> [1, tm] int32: each row's total set bits, laid
    along the lanes of one output row.

    Called on ``bit_op(x_row, a)`` — one streamed [1, tw] vector broadcast
    against the resident [tm, tw] tile — it is one PPAC array cycle: every
    latch row's population count lands on that row's output lane.
    """
    pc = lax.population_count(bits).astype(jnp.int32)
    return jnp.sum(pc, axis=-1)[None, :]


def for_each_row(n: int, body: Callable[[object], None]) -> None:
    """Run ``body(rows)`` for each streamed row of the tile, ``rows`` being
    the ``pl.ds`` that selects that one row. A loop, not an unroll, so the
    per-step intermediate stays one [tm, tw] tile whatever the batch tile."""

    def step(r, carry):
        body(pl.ds(r, 1))
        return carry

    lax.fori_loop(0, n, step, 0)


def _x_spec(plan: TilePlan, leading: int):
    if leading:
        return pl.BlockSpec((leading, plan.bb, plan.bw),
                            lambda i, j, k: (0, i, k))
    return pl.BlockSpec((plan.bb, plan.bw), lambda i, j, k: (i, k))


def _a_spec(plan: TilePlan, leading: int):
    if leading:
        return pl.BlockSpec((leading, plan.bm, plan.bw),
                            lambda i, j, k: (0, j, k))
    return pl.BlockSpec((plan.bm, plan.bw), lambda i, j, k: (j, k))


def smem_spec():
    """A whole small operand (scalar coefficients) in scalar memory."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def lane_stream_call(kernel_body, x_packed, a_packed, plan: TilePlan, *,
                     x_leading: int = 0, a_leading: int = 0,
                     extra_inputs=(), extra_specs=(), scratch_shapes=(),
                     interpret: bool = False):
    """Run ``kernel_body`` on the canonical lane-streamed grid.

    Pads the operands per ``plan``, streams x tiles along grid dims (0, 2)
    and a tiles along (1, 2), hands any ``extra_inputs`` through with their
    ``extra_specs``, and revisits the [bb, bm] int32 output block across
    grid dim 2 (the lane stream) — the body must init it at
    ``pl.program_id(2) == 0`` and accumulate into it. Returns the result
    cropped back to the logical [b, m].

    ``x_leading``/``a_leading`` carry a bit-plane stack (bitserial MVP):
    nonzero values make the operand [leading, rows, lanes] with the whole
    plane stack resident per tile.

    On the native TPU lowering, the grid is annotated with
    ``GRID_SEMANTICS``: batch/row tiles are parallel, only the lane
    (accumulation) dim is order-dependent — letting Mosaic reorder and
    pipeline the independent output tiles.
    """
    x_p = pad_lanes(x_packed, plan.bp, plan.wp)
    a_p = pad_lanes(a_packed, plan.mp, plan.wp)
    extra = {}
    if not interpret:
        extra["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=GRID_SEMANTICS)
    out = pl.pallas_call(
        kernel_body,
        grid=plan.grid,
        in_specs=[_x_spec(plan, x_leading), _a_spec(plan, a_leading),
                  *extra_specs],
        out_specs=pl.BlockSpec((plan.bb, plan.bm), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((plan.bp, plan.mp), jnp.int32),
        scratch_shapes=list(scratch_shapes),
        interpret=interpret,
        **extra,
    )(x_p, a_p, *extra_inputs)
    return out[:plan.b, :plan.m]
