"""Serving executors: the device half of the scheduler/executor split.

``LMServer`` (launch/serve_lm.py) is the *scheduler* — it owns admission,
paging, and retirement, and never touches a jitted entry point directly.
Everything device-side lives behind an executor object from this module:

  * :class:`LocalExecutor` — prefill + decode colocated, the PR<=8
    layout. Optionally *mesh-sharded*: given a mesh, the resident packed
    weights shard via the logical-axis rules (TP over 'model', grouped
    wqkv/wig containers and draft rungs included —
    :func:`repro.launch.specs.serving_param_shardings`) and the resident
    slot cache shards slot-parallel over 'data' (DP). Every jitted entry
    point still donates the cache pytree, so the PR 4–7 invariants
    (donation aliasing, zero weight-repack, in-place scatter) hold
    unchanged on the sharded path.

  * :class:`DisaggExecutor` — disaggregated serving: a pool of
    :class:`PrefillWorker` s on their own device slices and a resident
    decode side on a disjoint mesh. Prefill runs against a *scratch*
    cache on the prefill worker's devices; the finished K/V state then
    moves to the decode mesh via ``jax.device_put`` (per-slot rows for
    contiguous caches, whole page pools adopted through the block table
    for paged caches) — so a long prompt costs the resident decoders one
    cheap scatter, never a multi-thousand-token prefill stall.

Worker attribution rides along: every executor dispatch is wrapped in a
``obs.ledger.phase`` carrying a worker tag (``p0``/``d0``/…), and the
executors record per-worker labeled series (``lm_worker_dispatches``,
``lm_prefill_s{worker=...}``, ``lm_handoff_latency``) next to the
scheduler's unlabeled aggregates.

Chaos hardening (PR 10): every executor accepts an optional
:class:`repro.launch.faults.FaultPlan` and calls ``fire`` at its seams
(prefill dispatch, handoff, decode dispatch) — with no plan the seams
cost one ``is not None`` check. A :class:`WorkerCrash` escaping a seam
is the scheduler's signal to retry/requeue; ``DisaggExecutor.
on_worker_crash`` owns the pool-side recovery (bounded restart, drop,
and graceful degradation to decode-mesh prefill when the pool is gone),
with in-process heartbeat supervision via ``launch/ft.py``'s
:class:`HeartbeatBook`.
"""
from __future__ import annotations

import contextlib
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig
from ..models import lm
from ..obs import ledger as _flight
from ..obs.metrics import MetricsRegistry
from ..serve.step import (
    make_decode_select_step,
    make_prefill_select_step,
    make_speculative_decode_step,
)
from ..sharding.rules import default_rules, fitted_shardings
from .faults import FaultPlan, WorkerCrash  # noqa: F401  (re-exported)
from .ft import HeartbeatBook
from .mesh import carve_devices, make_serving_mesh
from .specs import serving_param_shardings


def _place_params(mesh, rules, params, cfg):
    return jax.device_put(params,
                          serving_param_shardings(mesh, rules, params, cfg))


def _replicate_on(mesh, tree):
    """device_put a pytree fully replicated onto ``mesh`` — the handoff
    transfer: prefill-side results resharded onto the decode mesh."""
    rep = NamedSharding(mesh, P())
    return jax.tree.map(lambda x: jax.device_put(x, rep), tree)


class _PrefillHandle:
    """Opaque prefill result the scheduler passes back to ``write_slot``:
    the scratch cache plus the worker that produced it (the handoff needs
    the producer's extraction jit and mesh)."""

    def __init__(self, worker, cache):
        self.worker = worker
        self.cache = cache


class _DecodeSide:
    """Shared decode-side machinery: the resident params + the donated
    jitted entry points, optionally on a mesh."""

    def __init__(self, cfg: ModelConfig, params, *, mode: str, rules,
                 mesh, temperature: float, top_k: int, paged: bool,
                 spec_decode: bool, draft_k: int,
                 metrics: Optional[MetricsRegistry], worker: str,
                 faults: Optional[FaultPlan] = None):
        self.cfg, self.mode, self.mesh = cfg, mode, mesh
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.worker = worker
        self.faults = faults
        self.temperature, self.top_k = temperature, top_k
        if mesh is not None:
            rules = (rules if rules is not None
                     else default_rules()).for_mesh(mesh)
            params = _place_params(mesh, rules, params, cfg)
        self.rules = rules
        self.params = params
        self.paged = paged

        self._decode = make_decode_select_step(
            cfg, rules, mode, temperature=temperature, top_k=top_k)
        self._spec = (make_speculative_decode_step(
            cfg, rules, mode, draft_k=draft_k, temperature=temperature,
            top_k=top_k) if spec_decode else None)

        if paged:
            def table_write(cache, slot_ids, rows):
                out = dict(cache)
                out["table"] = cache["table"].at[slot_ids].set(rows)
                return out
            self._table_write = jax.jit(table_write, donate_argnums=(0,))

            def copy_page(cache, src, dst):
                """Copy-on-write: duplicate physical page ``src`` into the
                private page ``dst`` across every pool leaf, in place."""
                def leaf(x):
                    row = lax.dynamic_index_in_dim(x, src, 1, keepdims=False)
                    return x.at[:, dst].set(row)
                out = dict(cache)
                for grp in ("layers", "dense_layers"):
                    if grp in cache:
                        out[grp] = jax.tree.map(leaf, cache[grp])
                return out
            self._copy_page = jax.jit(copy_page, donate_argnums=(0,))
        else:
            def write_slot(cache, src, row, slot):
                """Copy sequence ``row`` of a prefill cache into ``slot``
                of the resident cache — on device, resident cache
                donated."""
                def leaf(full, one):
                    if full.ndim == 1:  # per-sequence pos vector
                        return full.at[slot].set(
                            lax.dynamic_index_in_dim(one, row, 0,
                                                     keepdims=False))
                    r = lax.dynamic_slice_in_dim(one, row, 1, axis=1)
                    return lax.dynamic_update_slice_in_dim(
                        full, r.astype(full.dtype), slot, axis=1)
                return jax.tree.map(leaf, cache, src)
            self._write = jax.jit(write_slot, donate_argnums=(0,))

    def _ctx(self):
        """Mesh context for dispatches (nullcontext on a single device):
        sharding constraints inside the model only bind to mesh axes
        while a mesh is active."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    def _tag(self):
        """Ledger worker attribution for the dispatches inside; the empty
        tag/zero window keep untagged launches' phase accounting
        unchanged."""
        return _flight.phase("", window=0, worker=self.worker)

    def place_cache(self, cache, axes):
        """Shard the resident cache over the mesh: the slot ('batch')
        dim of every slot-indexed leaf — contiguous K/V, pos, the block
        table — goes slot-parallel over 'data'; paged pool leaves follow
        their own annotations (kv_heads over 'model'). Non-divisible
        dims fall back to replicated (``fit_spec``)."""
        if self.mesh is None:
            return cache
        with jax.set_mesh(self.mesh):
            sh = fitted_shardings(self.mesh, self.rules, axes, cache)
            return jax.device_put(cache, sh)

    def _fire(self, seam: str, wid: Optional[str] = None) -> None:
        """Fault seam: consume + act on this dispatch's scheduled faults.
        Always fires BEFORE the jitted (donating) call so an injected
        raise leaves the caller's cache pytree untouched and a retry is
        clean."""
        if self.faults is not None:
            w = wid if wid is not None else self.worker
            self.faults.raise_any(self.faults.fire(seam, worker=w), wid=w)

    # -- decode-side entry points (scheduler-facing) -------------------------

    def decode(self, toks, cache, key):
        self._fire("decode")
        t0 = time.perf_counter()
        with self._ctx(), self._tag():
            out = self._decode(self.params, toks, cache, key)
        self._account("decode", t0)
        return out

    def spec_round(self, toks, cache, key):
        self._fire("decode")
        t0 = time.perf_counter()
        with self._ctx(), self._tag():
            out = self._spec(self.params, toks, cache, key)
        self._account("decode", t0)
        return out

    def table_write(self, cache, slot_ids, rows):
        with self._ctx():
            return self._table_write(cache, slot_ids, rows)

    def copy_page(self, cache, src, dst):
        with self._ctx():
            return self._copy_page(cache, src, dst)

    def _account(self, kind: str, t0: float):
        m = self.metrics
        m.counter("lm_worker_dispatches", worker=self.worker,
                  role=self.role, kind=kind).inc()
        m.histogram(f"lm_{kind}_worker_s", worker=self.worker,
                    role=self.role).record(time.perf_counter() - t0)

    # -- integrity / recovery hooks (scheduler-facing) -----------------------

    def read_pages(self, cache, page_ids) -> np.ndarray:
        """Host byte image ``[P, nbytes]`` of the given physical pages,
        concatenated across every pool leaf (layers then dense_layers, in
        tree-leaf order) — the unit the KV CRC scrub tags and re-checks.
        Deterministic: leaf order and dtype byte layout are fixed by the
        cache pytree."""
        ids = [int(p) for p in page_ids]
        idx = jnp.asarray(ids, jnp.int32)
        per_page: List[List[bytes]] = [[] for _ in ids]
        for grp in ("layers", "dense_layers"):
            if grp not in cache:
                continue
            for leaf in jax.tree.leaves(cache[grp]):
                rows = np.asarray(jnp.take(leaf, idx, axis=1))
                rows = np.moveaxis(rows, 1, 0)  # [P, n_layers, ...]
                for i in range(len(ids)):
                    per_page[i].append(rows[i].tobytes())
        blobs = [b"".join(parts) for parts in per_page]
        if not blobs:
            return np.zeros((0, 0), np.uint8)
        return np.frombuffer(b"".join(blobs),
                             np.uint8).reshape(len(ids), -1)

    def corrupt_page(self, cache, page: int, bit: int):
        """Flip one bit of physical page ``page`` in the first pool leaf
        (host round-trip) — the chaos injector's KV bit-flip. Returns the
        updated cache; the page's stored CRC tag no longer matches."""
        grp = "layers" if "layers" in cache else "dense_layers"
        leaves, treedef = jax.tree.flatten(cache[grp])
        leaf = leaves[0]
        block = np.asarray(leaf[:, page])
        raw = np.frombuffer(block.tobytes(), np.uint8).copy()
        raw[(bit // 8) % len(raw)] ^= np.uint8(1 << (bit % 8))
        fixed = np.frombuffer(raw.tobytes(),
                              block.dtype).reshape(block.shape)
        leaves[0] = leaf.at[:, page].set(jnp.asarray(fixed))
        out = dict(cache)
        out[grp] = jax.tree.unflatten(treedef, leaves)
        return out

    def reload_params(self, params) -> None:
        """Swap in (repaired) resident weights — the scrub path after a
        shadow repack. Re-places onto the mesh when sharded."""
        if self.mesh is not None:
            params = _place_params(self.mesh, self.rules, params, self.cfg)
        self.params = params

    def on_worker_crash(self, wid: str) -> str:
        """Recovery verdict for a crashed worker. The unified executor
        has no pool to lose — a crash is always retryable in place."""
        return "retry"


class LocalExecutor(_DecodeSide):
    """Unified executor: prefill + decode share one device (or one
    sharded mesh) and the resident cache — prefill writes land in place,
    no handoff."""

    role = "unified"

    def __init__(self, cfg: ModelConfig, params, *, mode: str = "float",
                 rules=None, mesh=None, temperature: float = 0.0,
                 top_k: int = 0, paged: bool = False,
                 spec_decode: bool = False, draft_k: int = 4,
                 max_seq: int = 128, cache_dtype=None,
                 metrics: Optional[MetricsRegistry] = None,
                 worker: str = "w0", faults: Optional[FaultPlan] = None):
        super().__init__(cfg, params, mode=mode, rules=rules, mesh=mesh,
                         temperature=temperature, top_k=top_k, paged=paged,
                         spec_decode=spec_decode, draft_k=draft_k,
                         metrics=metrics, worker=worker, faults=faults)
        self.max_seq = max_seq
        del cache_dtype  # resident cache dtype is the scheduler's concern
        # compiles once per (batch-bucket, length-bucket) pair
        self._prefill = make_prefill_select_step(
            cfg, self.rules, mode, temperature=temperature, top_k=top_k,
            paged=paged)
        self._prefill_hit = (make_prefill_select_step(
            cfg, self.rules, mode, temperature=temperature, top_k=top_k,
            paged=True, history=True) if paged else None)

    def prefill(self, toks, lens, key):
        """Contiguous prefill into a fresh scratch cache; returns
        (first tokens [B] np, scratch handle for ``write_slot``).
        The scratch cache uses the config's native KV dtype (matching
        the single-executor server); ``write_slot`` casts at the copy."""
        self._fire("prefill")
        blen = int(toks.shape[0])
        t0 = time.perf_counter()
        with self._ctx(), self._tag():
            c1, _ = lm.init_cache(self.cfg, blen, self.max_seq)
            tok0, c1 = self._prefill(self.params, toks, lens, c1, key)
            tok0 = np.asarray(tok0)
        self._account("prefill", t0)
        return tok0, _PrefillHandle(None, c1)

    def write_slot(self, cache, handle: _PrefillHandle, row, slot):
        with self._ctx():
            return self._write(cache, handle.cache, jnp.int32(row),
                               jnp.int32(slot))

    def prefill_paged(self, toks, lens, starts, slot_ids, rows, cache, key,
                      *, history: bool):
        """Paged prefill straight through the block table into the
        resident pools (cold prompts or prefix-hit suffixes)."""
        self._fire("prefill")
        fn = self._prefill_hit if history else self._prefill
        t0 = time.perf_counter()
        with self._ctx(), self._tag():
            tok0, cache = fn(self.params, toks, lens, starts, slot_ids,
                             rows, cache, key)
            tok0 = np.asarray(tok0)
        self._account("prefill", t0)
        return tok0, cache


class PrefillWorker:
    """One prefill worker: a TP slice of the prefill pool with its own
    resident copy of the weights and a scratch cache per admission batch.
    Produces finished K/V state for the decode side to adopt."""

    def __init__(self, wid: str, cfg: ModelConfig, params, devices, *,
                 mode: str, rules, temperature: float, top_k: int,
                 paged: bool, page_size: int, max_seq: int, cache_dtype,
                 metrics: MetricsRegistry,
                 faults: Optional[FaultPlan] = None,
                 hb: Optional[HeartbeatBook] = None):
        self.wid, self.cfg, self.max_seq = wid, cfg, max_seq
        self.paged, self.page_size = paged, page_size
        self.metrics = metrics
        self.faults = faults
        self.hb = hb
        self.devices = list(devices)  # restart recipe: same carve slice
        self._ckw = {} if cache_dtype is None else {"dtype": cache_dtype}
        self.mesh = make_serving_mesh((1, len(devices)), devices=devices)
        self.rules = (rules if rules is not None
                      else default_rules()).for_mesh(self.mesh)
        self.params = _place_params(self.mesh, self.rules, params, cfg)
        self._prefill = make_prefill_select_step(
            cfg, self.rules, mode, temperature=temperature, top_k=top_k,
            paged=paged)

        def extract_row(c, row):
            """One sequence row of a scratch cache (still batched dim 1,
            for the decode side's write_slot at row 0)."""
            def leaf(x):
                if x.ndim == 1:  # per-sequence pos vector
                    return lax.dynamic_slice_in_dim(x, row, 1)
                return lax.dynamic_slice_in_dim(x, row, 1, axis=1)
            return jax.tree.map(leaf, c)
        self._extract_row = jax.jit(extract_row)

    def _fire(self, seam: str) -> None:
        if self.faults is not None:
            self.faults.raise_any(self.faults.fire(seam, worker=self.wid),
                                  wid=self.wid)

    def prefill(self, toks, lens, key):
        """Contiguous prefill on this worker's devices."""
        self._fire("prefill")
        blen = int(toks.shape[0])
        t0 = time.perf_counter()
        with jax.set_mesh(self.mesh), _flight.phase("", window=0,
                                                   worker=self.wid):
            c1, _ = lm.init_cache(self.cfg, blen, self.max_seq)
            tok0, c1 = self._prefill(self.params, toks, lens, c1, key)
            tok0 = np.asarray(tok0)
        self._account(t0)
        return tok0, c1

    def prefill_paged(self, toks, lens, slot_live, n_pages, key):
        """Cold paged prefill into a *scratch* pool on this worker: row i
        of the batch owns scratch pages [i*n_pages, (i+1)*n_pages) via an
        identity block table, so the decode side can adopt exactly the
        pages each admitted request touched. Dead batch rows keep the
        slot sentinel (their pos scatter drops)."""
        self._fire("prefill")
        blen = int(toks.shape[0])
        pool = blen * n_pages
        table = np.arange(pool, dtype=np.int32).reshape(blen, n_pages)
        slot_ids = np.where(slot_live, np.arange(blen, dtype=np.int32),
                            np.int32(blen))
        starts = np.zeros((blen,), np.int32)
        t0 = time.perf_counter()
        with jax.set_mesh(self.mesh), _flight.phase("", window=0,
                                                   worker=self.wid):
            c1, _ = lm.init_cache(self.cfg, blen, self.max_seq,
                                  page_size=self.page_size,
                                  pool_pages=pool, **self._ckw)
            c1 = self._table_write_scratch(c1, table)
            tok0, c1 = self._prefill(self.params, jnp.asarray(toks),
                                     jnp.asarray(lens), jnp.asarray(starts),
                                     jnp.asarray(slot_ids), jnp.asarray(table),
                                     c1, key)
            tok0 = np.asarray(tok0)
        self._account(t0)
        return tok0, c1

    @staticmethod
    def _table_write_scratch(cache, table):
        out = dict(cache)
        out["table"] = jnp.asarray(table)
        return out

    def extract_row(self, cache, row):
        with jax.set_mesh(self.mesh):
            return self._extract_row(cache, jnp.int32(row))

    def _account(self, t0: float):
        m = self.metrics
        m.counter("lm_worker_dispatches", worker=self.wid,
                  role="prefill", kind="prefill").inc()
        m.histogram("lm_prefill_worker_s", worker=self.wid,
                    role="prefill").record(time.perf_counter() - t0)
        if self.hb is not None:  # heartbeat per successful dispatch
            self.hb.beat(self.wid)


class DisaggExecutor(_DecodeSide):
    """Disaggregated executor: prefill worker pool + resident decode mesh
    on disjoint device slices, bridged by a ``jax.device_put`` handoff.

    Device carve: the first ``prefill_devices`` attached devices become
    the prefill pool (split round-robin into ``prefill_workers`` TP
    workers), the next ``decode_devices`` the decode mesh (shape
    ``decode_mesh_shape``, default (D, 1) = slot-parallel DP). When the
    box has too few devices the pools overlap (with a warning) instead
    of raising — the handoff path still runs, it just moves bytes
    between colocated buffers.

    Unsupported combinations raise at construction: prefix-cache reuse
    needs prefill to read the *resident* pools' history, which is
    exactly the coupling disaggregation removes (degraded mode, where
    prefill runs on the decode mesh anyway, lifts the restriction).

    Recovery: a :class:`WorkerCrash` at a prefill/handoff seam routes
    through :meth:`on_worker_crash` — the dead worker is rebuilt on its
    own device slice up to ``max_worker_restarts`` times, then dropped
    from the pool; when the last worker is gone the executor *degrades*
    instead of failing: prefill falls back to the decode mesh
    (``LocalExecutor`` layout, lazily compiled), so the server keeps
    serving at reduced throughput. :meth:`check_stragglers` applies the
    same verdicts to workers whose heartbeats go silent."""

    role = "disagg"

    def __init__(self, cfg: ModelConfig, params, *,
                 prefill_devices: int = 1, decode_devices: int = 1,
                 prefill_workers: int = 0, decode_mesh_shape=None,
                 mode: str = "float", rules=None, temperature: float = 0.0,
                 top_k: int = 0, paged: bool = False, page_size: int = 16,
                 spec_decode: bool = False, draft_k: int = 4,
                 max_seq: int = 128, cache_dtype=None,
                 metrics: Optional[MetricsRegistry] = None,
                 faults: Optional[FaultPlan] = None,
                 max_worker_restarts: int = 1):
        pdevs, ddevs = carve_devices(prefill_devices, decode_devices)
        dshape = tuple(decode_mesh_shape or (len(ddevs), 1))
        mesh = make_serving_mesh(dshape, devices=ddevs)
        super().__init__(cfg, params, mode=mode, rules=rules, mesh=mesh,
                         temperature=temperature, top_k=top_k, paged=paged,
                         spec_decode=spec_decode, draft_k=draft_k,
                         metrics=metrics, worker="d0", faults=faults)
        self.max_seq = max_seq
        self.page_size = page_size
        self.max_worker_restarts = max_worker_restarts
        self.degraded = False
        self.hb = HeartbeatBook()
        self._restarts: dict = {}
        self._fb: dict = {}  # degraded-mode prefill fns, built on demand
        # worker rebuild recipe: the ORIGINAL (pre-placement) params plus
        # the construction kwargs — self.params is already mesh-placed
        self._init_params = params
        self._worker_kw = dict(mode=mode, rules=rules,
                               temperature=temperature, top_k=top_k,
                               paged=paged, page_size=page_size,
                               max_seq=max_seq, cache_dtype=cache_dtype)

        nw = prefill_workers or 1
        if len(pdevs) % nw:
            raise ValueError(f"{len(pdevs)} prefill devices do not split "
                             f"into {nw} workers")
        per = len(pdevs) // nw
        self.pool: List[PrefillWorker] = [
            self._mk_worker(f"p{i}", pdevs[i * per:(i + 1) * per])
            for i in range(nw)]
        self._rr = 0

        if paged:
            def adopt(cache, pools, src_ids, dst_ids, slot_ids,
                      pos_vals):
                """Adopt prefilled pages into the resident pools: gather
                ``src_ids`` from the handed-off scratch pools, scatter at
                ``dst_ids`` (sentinel-padded entries drop), and land each
                admitted slot's position (dead rows carry the slot
                sentinel and drop)."""
                def leaf(full, one):
                    rows = jnp.take(one, src_ids, axis=1)
                    return full.at[:, dst_ids].set(
                        rows.astype(full.dtype), mode="drop")
                out = dict(cache)
                for grp in ("layers", "dense_layers"):
                    if grp in cache:
                        out[grp] = jax.tree.map(leaf, cache[grp],
                                                pools[grp])
                out["pos"] = cache["pos"].at[slot_ids].set(pos_vals,
                                                           mode="drop")
                return out
            self._adopt = jax.jit(adopt, donate_argnums=(0,))

    def _mk_worker(self, wid: str, devices) -> PrefillWorker:
        return PrefillWorker(wid, self.cfg, self._init_params, devices,
                             metrics=self.metrics, faults=self.faults,
                             hb=self.hb, **self._worker_kw)

    def _next_worker(self) -> Optional[PrefillWorker]:
        if not self.pool:  # degraded: prefill falls back to decode mesh
            return None
        w = self.pool[self._rr % len(self.pool)]
        self._rr += 1
        return w

    # -- recovery ------------------------------------------------------------

    def on_worker_crash(self, wid: str) -> str:
        """Recovery verdict for a dead prefill worker: rebuild it on its
        own device slice (``'restarted'``, bounded by
        ``max_worker_restarts``), then drop it (``'dropped'``); losing
        the last worker flips the executor into degraded decode-mesh
        prefill (``'degraded'``). The scheduler re-prefills whatever the
        deceased had in flight either way."""
        self.hb.forget(wid)
        idx = next((i for i, w in enumerate(self.pool) if w.wid == wid),
                   None)
        if idx is None:  # already dropped (or decode-side attribution)
            return "degraded" if self.degraded else "retry"
        n = self._restarts.get(wid, 0)
        if n < self.max_worker_restarts:
            self._restarts[wid] = n + 1
            self.pool[idx] = self._mk_worker(wid, self.pool[idx].devices)
            self.metrics.counter("lm_worker_restarts", worker=wid).inc()
            return "restarted"
        self.pool.pop(idx)
        if self.pool:
            self.metrics.counter("lm_worker_drops", worker=wid).inc()
            return "dropped"
        self.degraded = True
        self.metrics.gauge("lm_degraded").set(1.0)
        return "degraded"

    def check_stragglers(self, timeout: float, now=None) -> List[str]:
        """Heartbeat supervision (``HeartbeatBook``): a worker silent for
        ``timeout`` seconds is treated exactly like a crash. Returns the
        ``wid:verdict`` actions taken (empty = everyone healthy)."""
        return [f"{wid}:{self.on_worker_crash(wid)}"
                for wid in self.hb.stale(timeout, now)]

    def _fallback_prefill(self, *, paged: bool, history: bool = False):
        """Degraded-mode prefill entry point on the decode mesh, compiled
        on first use (the happy path never pays for it)."""
        k = (paged, history)
        fn = self._fb.get(k)
        if fn is None:
            fn = self._fb[k] = make_prefill_select_step(
                self.cfg, self.rules, self.mode,
                temperature=self.temperature, top_k=self.top_k,
                paged=paged, history=history)
        return fn

    # -- contiguous path -----------------------------------------------------

    def prefill(self, toks, lens, key):
        w = self._next_worker()
        if w is None:  # degraded: prefill locally on the decode mesh
            self._fire("prefill")
            blen = int(toks.shape[0])
            t0 = time.perf_counter()
            with self._ctx(), self._tag():
                c1, _ = lm.init_cache(self.cfg, blen, self.max_seq)
                tok0, c1 = self._fallback_prefill(paged=False)(
                    self.params, toks, lens, c1, key)
                tok0 = np.asarray(tok0)
            self._account("prefill", t0)
            return tok0, _PrefillHandle(None, c1)
        tok0, c1 = w.prefill(toks, lens, key)
        return tok0, _PrefillHandle(w, c1)

    def write_slot(self, cache, handle: _PrefillHandle, row, slot):
        """The contiguous handoff: extract one finished sequence row on
        the prefill worker, ``jax.device_put`` it onto the decode mesh,
        scatter it into the donated resident cache. Degraded-mode
        handles (no worker) are already on our mesh — plain local
        write, no handoff."""
        if handle.worker is None:
            with self._ctx():
                return self._write(cache, handle.cache, jnp.int32(row),
                                   jnp.int32(slot))
        self._fire("handoff", wid=handle.worker.wid)
        t0 = time.perf_counter()
        row_cache = handle.worker.extract_row(handle.cache, row)
        moved = _replicate_on(self.mesh, row_cache)
        with self._ctx():
            out = self._write(cache, moved, jnp.int32(0), jnp.int32(slot))
        jax.block_until_ready(out["pos"])
        self._handoff(t0, handle.worker.wid)
        return out

    # -- paged path ----------------------------------------------------------

    def prefill_paged(self, toks, lens, starts, slot_ids, rows, cache, key,
                      *, history: bool):
        """The paged handoff: cold-prefill into an identity-mapped
        scratch pool on a prefill worker, move the touched pages to the
        decode mesh, and adopt them at the scheduler's physical page ids
        through the resident block table."""
        w = self._next_worker()
        if w is None:  # degraded: straight through the resident table
            self._fire("prefill")
            fn = self._fallback_prefill(paged=True, history=history)
            t0 = time.perf_counter()
            with self._ctx(), self._tag():
                tok0, cache = fn(self.params, toks, lens, starts,
                                 slot_ids, rows, cache, key)
                tok0 = np.asarray(tok0)
            self._account("prefill", t0)
            return tok0, cache
        if history:
            raise RuntimeError(
                "prefix-cache suffix prefill reads resident pool history; "
                "it cannot run on a disaggregated prefill worker")
        rows_np = np.asarray(rows)
        slots_np = np.asarray(slot_ids)
        blen, n_pages = rows_np.shape
        sentinel = int(jax.tree.leaves(cache["layers"])[0].shape[1])
        slot_live = slots_np < cache["table"].shape[0]
        tok0, scratch = w.prefill_paged(np.asarray(toks), np.asarray(lens),
                                        slot_live, n_pages, key)
        # the handoff seam fires after the scratch prefill but BEFORE the
        # donating adopt: an injected mid-handoff crash leaves the
        # resident cache valid, and the scheduler re-prefills.
        self._fire("handoff", wid=w.wid)

        t0 = time.perf_counter()
        # fixed-width id vectors (compiled once per batch bucket): row i's
        # j-th mapped page lives at scratch page i*n_pages+j and lands at
        # the physical id the scheduler allocated; unmapped entries pad
        # with the sentinel and drop in the scatter.
        src_ids = np.zeros((blen * n_pages,), np.int32)
        dst_ids = np.full((blen * n_pages,), sentinel, np.int32)
        for i in range(blen):
            if not slot_live[i]:
                continue
            mapped = rows_np[i][rows_np[i] < sentinel]
            k = len(mapped)
            src_ids[i * n_pages:i * n_pages + k] = \
                i * n_pages + np.arange(k, dtype=np.int32)
            dst_ids[i * n_pages:i * n_pages + k] = mapped
        pools = {grp: scratch[grp] for grp in ("layers", "dense_layers")
                 if grp in scratch}
        moved = _replicate_on(self.mesh, pools)
        pos_vals = _replicate_on(self.mesh, scratch["pos"])
        with self._ctx():
            cache = self._adopt(cache, moved, jnp.asarray(src_ids),
                                jnp.asarray(dst_ids), jnp.asarray(slots_np),
                                pos_vals)
        jax.block_until_ready(cache["pos"])
        self._handoff(t0, w.wid)
        return tok0, cache

    def _handoff(self, t0: float, src_worker: str):
        dt = time.perf_counter() - t0
        m = self.metrics
        m.histogram("lm_handoff_latency").record(dt)
        m.histogram("lm_handoff_latency", worker=src_worker,
                    role="prefill").record(dt)
        m.counter("lm_handoffs").inc()
