"""Continuous-batching LM decode server: device-resident ring/linear KV
caches, slot-based admission/eviction, bucketed prefill.

The serving loop the kernel work of PRs 3-4 was building toward — the LM
itself served to many concurrent users:

  * a resident cache pytree sized [slots, max_seq, ...] lives on device
    for the whole server lifetime; every jitted entry point *donates* it
    (``donate_argnums``), so per-token cache updates are in-place
    scatters, never whole-cache copies,
  * decode runs as ONE fused step over all slots with per-sequence
    positions (``cache['pos']: [S]``) — sequences at different depths
    (admitted mid-flight) share the step bit-exactly with solo decoding,
  * new requests prefill into free slots while resident sequences keep
    decoding: waiting prompts are drained in *batch buckets* (the shared
    :func:`repro.launch.bucketed.drain_take` policy) and *right-padded*
    into power-of-two length buckets — right padding + per-sequence
    ``lengths`` keeps causal prefill bit-identical to the unpadded
    prompt, and the number of compiled (batch, length) prefill shapes
    stays bounded,
  * per-slot retirement on EOS or length; the freed slot is refilled
    from the queue on the next admission pass,
  * token selection (greedy / temperature / top-k) is fused into the
    prefill and decode programs — the host only ever sees the [S] int32
    ids it needs for retirement decisions.

Paged mode (``paged=True`` / ``--paged``) virtualizes the cache: KV
leaves become fixed-size page pools ([pool_pages, page_size, ...]) and a
[slots, extent/page_size] block table maps logical to physical pages
(models/attention.py gathers rows through it, same trick as
``_ring_rows``). Admission becomes page allocation off a host free list
with per-page refcounts: memory scales with *live tokens*, a too-small
pool backpressures admission instead of crashing, and — with
``prefix_cache=True`` — each full prompt page hashes into a chained
128-bit key matched against resident pages via one batched CAM launch
(``retrieval/prefix.py``): a hit maps the new slot's table entries onto
existing pages (copy-on-write for a shared tail page) and only the
suffix is prefilled. Prefill writes go straight through the table into
the donated resident pools — no scratch cache, no copy step.

Chaos hardening (PR 10): an optional :class:`FaultPlan` (``--fault-plan``
/ ``--fault-seed``) injects deterministic worker crashes, dispatch
errors, handoff stalls, KV/weight bit-flips, pool squeezes and request
deadlines. The scheduler guarantees every submitted request reaches
exactly ONE terminal outcome — ``completed`` | ``shed`` (deadline) |
``failed`` (with a reason) — via bounded retry with page-refcount-correct
unwinding, deadline load shedding, and (``--kv-crc``) a GF(2)-CRC scrub
(``gf2/ops.crc_tags``) that tags sealed prompt pages after prefill and
quarantines any page whose recomputed tag drifts before decode can read
it. With no plan and no CRC flags the serving path is unchanged.

CLI: PYTHONPATH=src python -m repro.launch.serve_lm --arch smollm_360m \
        [--full] --requests 12 --max-new 16 [--serve-quant --weight-bits 4] \
        [--kv-int8] [--temperature 0.8 --top-k 40] [--eos 0] \
        [--paged --page-size 16 --pool-pages 64 --prefix-cache] \
        [--fault-plan 'crash:prefill:0:worker=p0;flip:step:3' \
         --kv-crc --scrub-every 1 --chaos-gate]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig, load_arch
from ..core.backend import use_compile_cache
from ..models import lm
from ..obs.metrics import MetricsRegistry
from ..obs.trace import TraceBuilder, attached, install, span
from ..retrieval.prefix import PagePrefixIndex
from ..serve.step import convert_params_for_serving, serving_cycle_report
from .bucketed import bucket_for, drain_take
from .faults import FaultPlan, InjectedFault, WorkerCrash
from .mesh import make_serving_mesh, parse_mesh_spec
from .paging import PagePool
from .workers import DisaggExecutor, LocalExecutor


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new: int
    eos: Optional[int] = None
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    finish_reason: Optional[str] = None
    # terminal outcome: every submitted request resolves to exactly one
    # of 'completed' | 'shed' | 'failed' (fail_reason says why)
    outcome: Optional[str] = None
    fail_reason: Optional[str] = None
    deadline_s: Optional[float] = None  # submit-relative; None = none
    retries: int = 0
    # telemetry timestamps (perf_counter readings, set by the server)
    submit_t: Optional[float] = None
    first_token_t: Optional[float] = None
    retire_t: Optional[float] = None

    @property
    def latency_s(self) -> Optional[float]:
        """End-to-end submit -> retire latency (None until retired)."""
        if self.submit_t is None or self.retire_t is None:
            return None
        return self.retire_t - self.submit_t


class LMServer:
    """Slot-based continuous batching over a resident, donated cache.

    The server is the *scheduler* half of a scheduler/executor split
    (``launch/workers.py``): it owns admission, paging, and retirement;
    every jitted dispatch goes through ``self.ex``. Three layouts:

      * default — :class:`LocalExecutor` on one device (the PR<=8 path),
      * ``mesh=`` — the same executor with the resident weights TP-
        sharded and the slot/page cache slot-parallel over the mesh,
      * ``prefill_devices``/``decode_devices`` — :class:`DisaggExecutor`
        with disjoint prefill/decode device pools bridged by a
        ``jax.device_put`` cache handoff.
    """

    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 max_seq: int = 128, mode: str = "float", rules=None,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 admit_buckets: Sequence[int] = (1, 2, 4),
                 metrics: Optional[MetricsRegistry] = None,
                 trace: Optional[TraceBuilder] = None,
                 paged: bool = False, page_size: int = 16,
                 pool_pages: Optional[int] = None,
                 prefix_cache: bool = False, cache_dtype=None,
                 spec_decode: bool = False, draft_k: int = 4,
                 mesh=None, prefill_devices: int = 0,
                 decode_devices: int = 0, prefill_workers: int = 0,
                 decode_mesh_shape=None,
                 faults: Optional[FaultPlan] = None, max_retries: int = 1,
                 max_worker_restarts: int = 1, kv_crc: bool = False,
                 scrub_every: int = 0):
        assert tuple(admit_buckets) == tuple(sorted(admit_buckets))
        if prefill_buckets is None:
            # powers of two up to max_seq (any prompt that leaves room to
            # decode is admissible; a bucket may not exceed the cache)
            prefill_buckets, b = [], 8
            while b < max_seq:
                prefill_buckets.append(b)
                b *= 2
            prefill_buckets.append(max_seq)
        assert tuple(prefill_buckets) == tuple(sorted(prefill_buckets))
        assert prefill_buckets[-1] <= max_seq
        self.cfg, self.mode = cfg, mode
        self.slots, self.max_seq = slots, max_seq
        self.prefill_buckets = tuple(prefill_buckets)
        self.admit_buckets = tuple(admit_buckets)
        # SSM state accumulation has no position mask: padded prefill
        # would fold pad tokens into the recurrent state (wrong tokens,
        # silently). SSM/hybrid prompts prefill at their exact length —
        # batched only with same-length peers.
        self.pad_prompts = cfg.family not in ("ssm", "hybrid")
        self.live: List[Optional[Request]] = [None] * slots
        self.queue: List[Request] = []
        self.terminal: List[Request] = []  # shed + failed (never retired)
        self.decode_steps = 0
        self.admit_batches = 0
        # chaos / integrity state
        self.faults = faults
        self.max_retries = max_retries
        self.kv_crc = kv_crc
        self.scrub_every = scrub_every
        self._ticks = 0
        self._squeezes: List[list] = []    # [ticks_left, held_pages]
        self._pending_flips: List = []     # flips waiting for a sealed page
        if kv_crc and not paged:
            raise ValueError("--kv-crc seals KV pages; it needs --paged")
        if kv_crc and cfg.sliding_window:
            raise ValueError("--kv-crc needs a linear cache: ring pages "
                             "are rewritten in place after sealing")
        # telemetry: always-on registry (negligible cost — a few Python
        # dict/float ops per step), optional Chrome-trace sink of the
        # spans (attached around each tick), process gc/compile hooks
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.trace = trace
        install(self.metrics)
        self._key = jax.random.PRNGKey(seed)
        self.paged, self.page_size = paged, page_size
        self._cache_dtype = cache_dtype
        ckw = {} if cache_dtype is None else {"dtype": cache_dtype}

        # family/layout validation happens here, before any executor (and
        # hence any compile or placement) is built
        self.spec_decode, self.draft_k = spec_decode, draft_k
        if paged and cfg.family in ("ssm", "hybrid"):
            raise ValueError("paged serving needs a token-indexed KV "
                             "cache; SSM/hybrid state stays contiguous")
        if spec_decode:
            if cfg.family in ("ssm", "hybrid"):
                raise ValueError("speculative decoding needs a "
                                 "token-indexed KV cache; SSM/hybrid "
                                 "state cannot rewind")
            if paged and cfg.sliding_window:
                raise ValueError("speculative decoding over a paged ring "
                                 "cache is unsupported: rejected wrapped "
                                 "writes cannot be rolled back through "
                                 "the block table")

        disagg = prefill_devices > 0 or decode_devices > 0
        if disagg and prefix_cache:
            raise ValueError("prefix-cache reuse prefills against resident "
                             "pool history, which disaggregated prefill "
                             "workers cannot read; drop --prefix-cache or "
                             "the worker split")
        if mesh is not None and not hasattr(mesh, "devices"):
            mesh = make_serving_mesh(tuple(mesh))  # shape tuple -> mesh
        if disagg:
            self.ex = DisaggExecutor(
                cfg, params, prefill_devices=max(prefill_devices, 1),
                decode_devices=max(decode_devices, 1),
                prefill_workers=prefill_workers,
                decode_mesh_shape=decode_mesh_shape, mode=mode,
                rules=rules, temperature=temperature, top_k=top_k,
                paged=paged, page_size=page_size, spec_decode=spec_decode,
                draft_k=draft_k, max_seq=max_seq, cache_dtype=cache_dtype,
                metrics=self.metrics, faults=faults,
                max_worker_restarts=max_worker_restarts)
        else:
            self.ex = LocalExecutor(
                cfg, params, mode=mode, rules=rules, mesh=mesh,
                temperature=temperature, top_k=top_k, paged=paged,
                spec_decode=spec_decode, draft_k=draft_k, max_seq=max_seq,
                cache_dtype=cache_dtype, metrics=self.metrics,
                faults=faults)

        if paged:
            self.extent = lm.paged_extent(cfg, max_seq)
            self.n_pages = self.extent // page_size
            self.pool_pages = (pool_pages if pool_pages is not None
                               else slots * self.n_pages)
            self.cache, caxes = lm.init_cache(cfg, slots, max_seq,
                                              page_size=page_size,
                                              pool_pages=self.pool_pages,
                                              **ckw)
            self.pool = PagePool(self.pool_pages)
            # host mirror of the device block table (sentinel = unmapped)
            self.table_np = np.full((slots, self.n_pages), self.pool_pages,
                                    np.int32)
            self.prefix = None
            if prefix_cache:
                if cfg.sliding_window:
                    raise ValueError("prefix reuse needs a linear cache: "
                                     "ring page contents depend on the "
                                     "sequence's own positions")
                self.prefix = PagePrefixIndex(page_size)
        else:
            # the resident cache: allocated once, donated through every step
            self.cache, caxes = lm.init_cache(cfg, slots, max_seq, **ckw)
        # on a mesh the resident cache shards slot-parallel ('data');
        # single-device executors return it unchanged
        self.cache = self.ex.place_cache(self.cache, caxes)

        # integrity baseline: CRC tags of every resident packed container
        # (host-side dict keyed by tree path — NOT in the pytree aux, so
        # jit caches stay unfragmented). Empty for float-mode params.
        self._param_tags: Dict[str, int] = {}
        if scrub_every > 0:
            from ..core.engine import container_tags
            self._param_tags = container_tags(self.ex.params)

    @property
    def params(self):
        """The resident (possibly sharded) weights live on the executor."""
        return self.ex.params

    # -- scheduling ----------------------------------------------------------

    def submit(self, req: Request):
        plen = len(req.prompt)
        assert 0 < plen <= self.prefill_buckets[-1], plen
        # prefill emits the first of the max_new tokens, so the last
        # decode step writes cache row plen + max_new - 2: a request
        # needs exactly plen + max_new - 1 rows, not plen + max_new.
        assert plen + req.max_new - 1 <= self.max_seq, \
            f"prompt {plen} + max_new {req.max_new} needs " \
            f"{plen + req.max_new - 1} cache rows, max_seq {self.max_seq}"
        req.submit_t = time.perf_counter()
        if self.faults is not None:  # request-keyed faults apply at submit
            for f in self.faults.for_request(req.rid):
                if f.kind == "deadline":
                    req.deadline_s = f.deadline_s
        self.metrics.counter("lm_requests_submitted").inc()
        self.queue.append(req)

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def _plen_bucket(self, plen: int) -> int:
        """Padded prompt length for one request: a power-of-two bucket for
        attention families (right-pad is bit-exact under causal masking),
        the exact length for SSM/hybrid (padding would corrupt the state)."""
        if self.pad_prompts:
            return bucket_for(plen, self.prefill_buckets)
        return plen

    # -- terminal outcomes / recovery ----------------------------------------

    def _shed(self, r: Request, where: str):
        """Deadline load shedding: the request leaves the system with the
        terminal outcome 'shed' (never admitted, or aborted in flight)."""
        r.done = True
        r.outcome = "shed"
        r.finish_reason = "deadline"
        r.retire_t = time.perf_counter()
        self.metrics.counter("lm_requests_shed", where=where).inc()
        self.terminal.append(r)

    def _fail(self, r: Request, reason: str):
        """Terminal failure (retry budget exhausted, capacity,
        corruption): the request resolves — never silently dropped."""
        r.done = True
        r.outcome = "failed"
        r.fail_reason = reason
        r.finish_reason = reason
        r.retire_t = time.perf_counter()
        self.metrics.counter("lm_requests_failed", reason=reason).inc()
        self.terminal.append(r)

    def _abort_slot(self, s: int):
        """Free a live slot WITHOUT retiring its request (deadline abort,
        corruption re-prefill): pages decref'd through the normal reclaim
        path (quarantined pages stay dead), table row sentineled."""
        self.live[s] = None
        if self.paged:
            self._reclaim_pages()

    def _requeue(self, reqs: List[Request], exc: Exception):
        """Bounded-retry requeue after an injected/real dispatch failure:
        each request goes back to the queue FRONT in order (FIFO held);
        past ``max_retries`` it fails terminally. A WorkerCrash first
        routes through the executor's recovery (restart/drop/degrade)."""
        m = self.metrics
        if isinstance(exc, WorkerCrash):
            verdict = self.ex.on_worker_crash(exc.wid)
            m.counter("lm_worker_crashes", worker=exc.wid,
                      verdict=verdict).inc()
        keep = []
        for r in reqs:
            r.retries += 1
            m.counter("lm_retries").inc()
            if r.retries > self.max_retries:
                self._fail(r, "prefill")
            else:
                keep.append(r)
        self.queue[:0] = keep

    def _expire_deadlines(self):
        """Shed expired requests: at admission (still queued) and in
        flight (slot aborted, pages reclaimed). FIFO order of the
        surviving queue is untouched."""
        now = time.perf_counter()

        def expired(r):
            return (r.deadline_s is not None and r.submit_t is not None
                    and now - r.submit_t > r.deadline_s)
        if any(expired(r) for r in self.queue):
            keep = []
            for r in self.queue:
                (self._shed(r, "queue") if expired(r) else keep.append(r))
            self.queue = keep
        for s, r in enumerate(self.live):
            if r is not None and expired(r):
                self._abort_slot(s)
                self._shed(r, "inflight")

    def _admit(self):
        """Prefill waiting prompts into free slots, in bucketed batches.

        FIFO groups share one padded-length bucket per batch; the batch
        itself is padded to an admission bucket (``drain_take`` policy),
        so compiled prefill shapes stay bounded at
        len(prefill_buckets) x len(admit_buckets) (for SSM archs: one
        shape per distinct prompt length instead)."""
        free = [s for s in range(self.slots) if self.live[s] is None]
        while free and self.queue:
            plb = self._plen_bucket(len(self.queue[0].prompt))
            cap, _ = drain_take(min(len(free), len(self.queue)),
                                self.admit_buckets)
            grp: List[Request] = []
            while (self.queue and len(grp) < cap
                   and self._plen_bucket(len(self.queue[0].prompt)) == plb):
                grp.append(self.queue.pop(0))
            if self.paged:
                if not self._admit_paged(grp, free, plb):
                    break  # pool backpressure: retry after retirements
                continue
            blen = bucket_for(len(grp), self.admit_buckets)
            toks = np.zeros((blen, plb), np.int32)
            lens = np.ones((blen,), np.int32)
            for i, r in enumerate(grp):
                toks[i, :len(r.prompt)] = r.prompt  # RIGHT-pad: bit-exact
                lens[i] = len(r.prompt)
            t0 = time.perf_counter()
            try:
                with span("prefill_batch", batch=blen, plen=plb,
                          fill=len(grp) / blen, rids=[r.rid for r in grp]):
                    tok0, handle = self.ex.prefill(jnp.asarray(toks),
                                                   jnp.asarray(lens),
                                                   self._next_key())
            except (InjectedFault, WorkerCrash) as e:
                # nothing resident yet: the whole group requeues (or
                # fails past its retry budget); stop admitting this tick
                self._requeue(grp, e)
                break
            t1 = time.perf_counter()
            self.admit_batches += 1
            m = self.metrics
            m.counter("lm_prefill_batches").inc()
            m.histogram("lm_prefill_s").record(t1 - t0)
            m.histogram("lm_admit_fill_ratio").record(len(grp) / blen)
            ok = 0
            for i, r in enumerate(grp):
                s = free[0]
                try:
                    self.cache = self.ex.write_slot(self.cache, handle,
                                                    i, s)
                except (InjectedFault, WorkerCrash) as e:
                    # crash mid-handoff: the resident cache is untouched
                    # (seams fire before the donating write) — this and
                    # every later row of the batch re-prefill
                    self._requeue(grp[i:], e)
                    break
                free.pop(0)
                ok += 1
                r.out.append(int(tok0[i]))
                r.first_token_t = t1  # prefill emits the first token
                if r.submit_t is not None:
                    m.histogram("lm_queue_wait_s").record(t0 - r.submit_t)
                    m.histogram("lm_ttft_s").record(t1 - r.submit_t)
                self.live[s] = r
            m.counter("lm_requests_admitted").inc(ok)
            # prefill emits each request's first token: count it here so
            # lm_tokens_generated matches sum(len(r.out)) — the decode
            # loop only adds the per-step occupancy (decode tokens)
            m.counter("lm_tokens_generated").inc(ok)
            if ok < len(grp):
                break

    def _admit_paged(self, grp: List[Request], free: List[int],
                     plb: int) -> bool:
        """Page-granular admission: map each request's table row onto
        physical pages off the pool (prefix hits first), then prefill
        cold prompts and hit suffixes straight through the table into
        the donated resident pools.

        Returns False when the pool backpressured: un-admitted requests
        went back to the queue FRONT (FIFO order preserved) and the
        caller stops admitting this tick — pages free up as live
        requests retire."""
        m = self.metrics
        psz = self.page_size
        plans = []  # (req, slot, mapping, keys, s0)
        bounced: List[Request] = []
        for r in grp:
            if bounced:  # keep FIFO order behind the first bounce
                bounced.append(r)
                continue
            plen = len(r.prompt)
            if self.cfg.sliding_window:
                # ring prefill writes all `extent` wrapped rows up front,
                # and ring page contents depend on the sequence's own
                # positions — every slot needs the full page complement
                need, keys, matched = self.n_pages, [], []
            else:
                rows = min(plen + r.max_new - 1, self.extent)
                need = -(-rows // psz)
                keys = (self.prefix.keys_for(r.prompt)
                        if self.prefix is not None else [])
                matched = (self.prefix.lookup(keys)
                           if self.prefix is not None and keys else [])
            if need > self.pool.pages:
                raise RuntimeError(
                    f"request {r.rid} needs {need} pages but the pool "
                    f"holds only {self.pool.pages}; raise --pool-pages "
                    f"or lower max_new")
            if need > self.pool.capacity:
                # quarantined pages shrank the pool below this request's
                # need: it can never fit — terminal, not a bounce
                self._fail(r, "capacity")
                continue
            nm = len(matched)
            # the suffix must re-emit from row plen-1 (whose logits pick
            # the first output token), so even a full match of every
            # prompt page still prefills one row — and that row lands in
            # a SHARED page: copy-on-write it into a private page first
            s0 = min(nm * psz, plen - 1)
            cow = nm > 0 and nm * psz > plen - 1
            fresh_needed = need - nm + (1 if cow else 0)
            pages = self.pool.alloc(fresh_needed)
            if pages is None and self.prefix is not None:
                # recycle idle registrations (refcount == 1, LRU) — but
                # never the pages this very request just matched
                protect = set(matched)
                for p in self.prefix.idle_pages(self.pool.refcount):
                    if p in protect:
                        continue
                    self.prefix.evict_page(p)
                    self.pool.decref([p])
                    m.counter("lm_prefix_pages_evicted").inc()
                    if self.pool.free_pages >= fresh_needed:
                        break
                pages = self.pool.alloc(fresh_needed)
            if pages is None:
                # a fault-injected squeeze returns its pages in a known
                # number of ticks: bounce, don't raise
                if (not plans and not self._squeezes
                        and not any(x is not None for x in self.live)):
                    raise RuntimeError(
                        f"pool exhausted with no live requests to "
                        f"retire: request {r.rid} needs {fresh_needed} "
                        f"fresh pages, {self.pool.free_pages} free of "
                        f"{self.pool.pages}")
                bounced.append(r)
                continue
            mapping = list(matched)
            if cow:
                src, dst = mapping[-1], pages.pop(0)
                mapping[-1] = dst
                self.cache = self.ex.copy_page(self.cache, jnp.int32(src),
                                               jnp.int32(dst))
                m.counter("lm_pages_cow").inc()
                self.pool.incref(matched[:-1])  # still-shared pages only
            else:
                self.pool.incref(matched)
            mapping += pages
            s = free.pop(0)
            self.table_np[s] = self.pool_pages  # sentinel-fill the tail
            self.table_np[s, :len(mapping)] = mapping
            m.counter("lm_prefix_pages_hit").inc(nm)
            m.counter("lm_prefix_pages_total").inc(plen // psz)
            m.counter("lm_prefill_rows_skipped").inc(s0)
            plans.append((r, s, mapping, keys, s0))
        if bounced:
            self.queue[:0] = bounced
        done_plans, launch_failed = [], False
        if plans:
            slot_ids = np.array([p[1] for p in plans], np.int32)
            self.cache = self.ex.table_write(
                self.cache, jnp.asarray(slot_ids),
                jnp.asarray(self.table_np[slot_ids]))
            cold = [p for p in plans if p[4] == 0]
            hits = [p for p in plans if p[4] > 0]
            by_slb = {}
            for p in hits:  # suffixes re-bucket by their OWN length
                slb = bucket_for(len(p[0].prompt) - p[4],
                                 self.prefill_buckets)
                by_slb.setdefault(slb, []).append(p)
            groups = ([(cold, plb, False)] if cold else []) + \
                [(by_slb[slb], slb, True) for slb in sorted(by_slb)]
            for gi, (g, lenb, hist) in enumerate(groups):
                try:
                    self._launch_prefill(g, lenb, history=hist)
                    done_plans.extend(g)
                except (InjectedFault, WorkerCrash) as e:
                    # failed group + every unlaunched group unwind
                    # (exactly one decref per mapped page) and requeue in
                    # plan order; already-launched groups stay admitted
                    lost = [p for gg, _, _ in groups[gi:] for p in gg]
                    self._unwind_plans(lost)
                    self._requeue([p[0] for p in lost], e)
                    launch_failed = True
                    break
            if self.prefix is not None:
                # register fresh full-prompt pages; the index holds one
                # reference so hot prefixes outlive their creator.
                # register() refuses duplicates (already-matched pages,
                # COW copies whose key is resident) so no double-count.
                for r, _, mapping, keys, _ in done_plans:
                    for j in range(len(r.prompt) // psz):
                        if self.prefix.register(keys[j], mapping[j]):
                            self.pool.incref([mapping[j]])
            if self.kv_crc:
                self._seal_plans(done_plans)
            # prefill-emitted first tokens (mirrors the contiguous path)
            m.counter("lm_tokens_generated").inc(len(done_plans))
        m.gauge("lm_pool_pages_used").set(self.pool.used_pages)
        m.gauge("lm_pool_pages_free").set(self.pool.free_pages)
        return not bounced and not launch_failed

    def _unwind_plans(self, plans):
        """Roll back planned-but-unlaunched admissions after a prefill
        failure: every page in a plan's mapping carries exactly ONE
        reference from this admission (fresh alloc, prefix incref, or
        COW dst), so one decref per page restores the pool, and the
        table rows go back to the sentinel on host and device."""
        sids = []
        for r, s, mapping, _keys, _s0 in plans:
            self.pool.decref(mapping)
            self.table_np[s] = self.pool_pages
            sids.append(s)
        if sids:
            ss = np.asarray(sorted(sids), np.int32)
            self.cache = self.ex.table_write(
                self.cache, jnp.asarray(ss),
                jnp.asarray(self.table_np[ss]))

    def _seal_plans(self, plans):
        """Tag-and-seal every fully-prefilled prompt page of the freshly
        admitted plans: pages wholly below plen ((j+1)*page_size <= plen)
        are never written again (decode writes rows >= plen), so their
        GF(2) CRC is stable until the slot's pages are reclaimed. One
        batched ``crc_tags`` launch covers all new pages."""
        psz = self.page_size
        to_seal = sorted({p for r, _s, mapping, _k, _s0 in plans
                          for j, p in enumerate(mapping)
                          if (j + 1) * psz <= len(r.prompt)
                          and not self.pool.is_sealed(p)})
        if not to_seal:
            return
        from ..gf2.ops import crc_tags
        bufs = self.ex.read_pages(self.cache, to_seal)
        tags = crc_tags(bufs)
        for p, t in zip(to_seal, tags):
            self.pool.seal(p, int(t))
        self.metrics.counter("lm_pages_sealed").inc(len(to_seal))

    def _launch_prefill(self, plans, lenb: int, *, history: bool):
        """One paged prefill launch: cold prompts (history=False) or the
        unshared suffixes of prefix hits (history=True). Dead batch rows
        carry slot_id == slots and all-sentinel table rows, so their
        pos/table scatters drop on the floor instead of clobbering a
        live slot."""
        blen = bucket_for(len(plans), self.admit_buckets)
        toks = np.zeros((blen, lenb), np.int32)
        lens = np.ones((blen,), np.int32)
        starts = np.zeros((blen,), np.int32)
        slot_ids = np.full((blen,), self.slots, np.int32)
        rows = np.full((blen, self.n_pages), self.pool_pages, np.int32)
        for i, (r, s, mapping, _, s0) in enumerate(plans):
            part = r.prompt[s0:] if history else r.prompt
            toks[i, :len(part)] = part  # RIGHT-pad: bit-exact
            lens[i] = len(part)
            starts[i] = s0
            slot_ids[i] = s
            rows[i] = self.table_np[s]
        t0 = time.perf_counter()
        with span("prefill_batch", batch=blen, plen=lenb,
                  fill=len(plans) / blen, history=history,
                  rids=[p[0].rid for p in plans]):
            tok0, self.cache = self.ex.prefill_paged(
                jnp.asarray(toks), jnp.asarray(lens), jnp.asarray(starts),
                jnp.asarray(slot_ids), jnp.asarray(rows), self.cache,
                self._next_key(), history=history)
        t1 = time.perf_counter()
        self.admit_batches += 1
        m = self.metrics
        m.counter("lm_prefill_batches").inc()
        m.counter("lm_requests_admitted").inc(len(plans))
        m.histogram("lm_prefill_s").record(t1 - t0)
        m.histogram("lm_admit_fill_ratio").record(len(plans) / blen)
        for i, (r, s, *_rest) in enumerate(plans):
            r.out.append(int(tok0[i]))
            r.first_token_t = t1
            if r.submit_t is not None:
                m.histogram("lm_queue_wait_s").record(t0 - r.submit_t)
                m.histogram("lm_ttft_s").record(t1 - r.submit_t)
            self.live[s] = r

    def _retire_slot(self, s: int, r: Request, now: float):
        """Evict a finished request from its slot and record telemetry."""
        m = self.metrics
        r.retire_t = now
        r.outcome = "completed"
        m.counter("lm_requests_retired").inc()
        m.counter("lm_slots_evicted").inc()
        m.counter(f"lm_finish_{r.finish_reason}").inc()
        if r.latency_s is not None:
            m.histogram("lm_request_latency_s").record(r.latency_s)
        if r.first_token_t is not None and len(r.out) > 1:
            m.histogram("lm_tpot_s").record(
                (now - r.first_token_t) / (len(r.out) - 1))
        self.live[s] = None  # evict: slot is free for re-admission

    def _reclaim_pages(self):
        """Return the pages of freshly-freed slots to the pool."""
        m = self.metrics
        reclaim = [s for s, r in enumerate(self.live)
                   if r is None and (self.table_np[s]
                                     < self.pool_pages).any()]
        for s in reclaim:
            held = [int(p) for p in self.table_np[s]
                    if p < self.pool_pages]
            self.pool.decref(held)  # shared pages survive via refcount
            self.table_np[s] = self.pool_pages
        if reclaim:
            sids = np.asarray(reclaim, np.int32)
            self.cache = self.ex.table_write(
                self.cache, jnp.asarray(sids),
                jnp.asarray(self.table_np[sids]))
        m.gauge("lm_pool_pages_used").set(self.pool.used_pages)
        m.gauge("lm_pool_pages_free").set(self.pool.free_pages)

    def step(self) -> List[Request]:
        """One fused decode step over all slots; returns retired requests."""
        if self.spec_decode:
            return self._step_spec()
        occupied = sum(r is not None for r in self.live)
        if occupied == 0:
            # admission backpressured with nothing resident: a decode
            # launch would only burn a step on dead slots
            return []
        tick = self._ticks
        with span("decode.prepare", tick=tick):
            toks = np.zeros((self.slots, 1), np.int32)
            for s, r in enumerate(self.live):
                if r is not None:
                    toks[s, 0] = r.out[-1]
            toks, key = jnp.asarray(toks), self._next_key()
        t0 = time.perf_counter()
        try:
            with span("decode_step", occupied=occupied, tick=tick):
                nxt, self.cache = self.ex.decode(toks, self.cache, key)
                with span("decode.wait", tick=tick):
                    nxt = np.asarray(nxt)  # the only host transfer: [S] ids
        except (InjectedFault, WorkerCrash) as e:
            # the seam fires before the donating dispatch, so the cache
            # is intact: skip this tick and redo the step (the fault is
            # consumed — the retry always makes progress)
            if isinstance(e, WorkerCrash):
                self.ex.on_worker_crash(e.wid)
            self.metrics.counter("lm_retries").inc()
            return []
        t1 = time.perf_counter()
        with span("decode.retire", tick=tick):
            self.decode_steps += 1
            m = self.metrics
            m.histogram("lm_decode_step_s").record(t1 - t0)
            m.gauge("lm_slot_occupancy").set(occupied)
            m.histogram("lm_slot_occupancy_per_step").record(occupied)
            m.counter("lm_tokens_generated").inc(occupied)
            m.gauge("lm_queue_depth").set(len(self.queue))
            retired = []
            for s, r in enumerate(self.live):
                if r is None:
                    continue
                t = int(nxt[s])
                r.out.append(t)
                hit_eos = r.eos is not None and t == r.eos
                if hit_eos or len(r.out) >= r.max_new:
                    r.done = True
                    r.finish_reason = "eos" if hit_eos else "length"
                    self._retire_slot(s, r, t1)
                    retired.append(r)
            if self.paged and retired:
                self._reclaim_pages()
        return retired

    def _step_spec(self) -> List[Request]:
        """One speculative draft->verify->accept round over all slots.

        A single cache-donating dispatch (k packed1-rung drafts + ONE
        batched target-rung verify) retires a *variable* number of
        tokens per slot — ``n_emit[s]`` in [1, draft_k + 1] — so the
        host-side loop appends each slot's accepted prefix and truncates
        at EOS / max_new (tokens past a mid-window stop are discarded;
        the slot is evicted and its cache rows recycled on re-admission).
        """
        occupied = sum(r is not None for r in self.live)
        if occupied == 0:
            return []
        tick = self._ticks
        with span("decode.prepare", tick=tick):
            toks = np.zeros((self.slots,), np.int32)
            for s, r in enumerate(self.live):
                if r is not None:
                    toks[s] = r.out[-1]
            toks, key = jnp.asarray(toks), self._next_key()
        t0 = time.perf_counter()
        try:
            with span("spec_round", occupied=occupied,
                      draft_k=self.draft_k, tick=tick):
                emitted, n_emit, self.cache = self.ex.spec_round(
                    toks, self.cache, key)
                with span("spec_round.wait", tick=tick):
                    emitted = np.asarray(emitted)  # [S, draft_k+1] ids
                    n_emit = np.asarray(n_emit)    # [S] accepted prefix + 1
        except (InjectedFault, WorkerCrash) as e:
            if isinstance(e, WorkerCrash):
                self.ex.on_worker_crash(e.wid)
            self.metrics.counter("lm_retries").inc()
            return []
        t1 = time.perf_counter()
        with span("decode.retire", tick=tick):
            self.decode_steps += 1
            m = self.metrics
            m.histogram("lm_decode_step_s").record(t1 - t0)
            m.gauge("lm_slot_occupancy").set(occupied)
            m.histogram("lm_slot_occupancy_per_step").record(occupied)
            m.gauge("lm_queue_depth").set(len(self.queue))
            retired = []
            for s, r in enumerate(self.live):
                if r is None:
                    continue
                ne = int(n_emit[s])
                if self.draft_k:  # per-slot acceptance telemetry
                    m.counter("lm_spec_rounds").inc()
                    m.counter("lm_spec_tokens_drafted").inc(self.draft_k)
                    m.counter("lm_spec_tokens_accepted").inc(ne - 1)
                    m.histogram("lm_spec_accept_rate").record(
                        (ne - 1) / self.draft_k)
                for j in range(ne):
                    t = int(emitted[s, j])
                    r.out.append(t)
                    m.counter("lm_tokens_generated").inc()
                    hit_eos = r.eos is not None and t == r.eos
                    if hit_eos or len(r.out) >= r.max_new:
                        r.done = True
                        r.finish_reason = "eos" if hit_eos else "length"
                        break  # discard accepted tokens past the stop
                if r.done:
                    self._retire_slot(s, r, t1)
                    retired.append(r)
            if self.paged and retired:
                self._reclaim_pages()
        return retired

    # -- chaos tick: fault application + integrity scrub ---------------------

    def _tick_faults(self):
        """Apply this tick's step-seam faults: bit-flips (KV page or
        resident weight container) and pool squeezes. Runs BEFORE the
        scrub, so with ``scrub_every=1`` every flip is detected before
        any decode step can read the corrupted page."""
        m = self.metrics
        # release expired squeezes first: a hold of 1 spans exactly one
        # admission+step and frees on the next tick
        keep = []
        for sq in self._squeezes:
            sq[0] -= 1
            if sq[0] <= 0:
                self.pool.decref(sq[1])
            else:
                keep.append(sq)
        self._squeezes = keep
        hits = self.faults.fire("step")
        for f in hits:
            if f.kind == "stall":
                time.sleep(f.stall_s)
        flips = self._pending_flips + [f for f in hits if f.kind == "flip"]
        self._pending_flips = []
        for f in flips:
            if f.param:
                from ..core.engine import flip_container_bit
                self.ex.reload_params(flip_container_bit(
                    self.ex.params, index=max(f.page, 0), bit=f.bit))
                m.counter("lm_faults_injected", kind="param_flip").inc()
            elif self.paged:
                page = f.page
                if page < 0:
                    sealed = self.pool.sealed_items()
                    if not sealed:  # nothing sealed yet: fire next tick
                        self._pending_flips.append(f)
                        continue
                    page = min(sealed)
                self.cache = self.ex.corrupt_page(self.cache, page, f.bit)
                m.counter("lm_faults_injected", kind="kv_flip").inc()
        for f in hits:
            if f.kind == "squeeze" and self.paged:
                k = min(f.pages, self.pool.free_pages)
                if k > 0:
                    self._squeezes.append([f.hold, self.pool.alloc(k)])
                    m.counter("lm_faults_injected", kind="squeeze").inc()

    def _scrub(self):
        """Integrity scrub: recompute the GF(2) CRC of every sealed KV
        page (one batched CRC-as-MVP launch) and of every tagged weight
        container; quarantine drifted pages (their requests re-prefill or
        fail with 'corruption'), repair drifted containers from their
        quantization shadow."""
        m = self.metrics
        t0 = time.perf_counter()
        if self.kv_crc:
            sealed = self.pool.sealed_items()
            if sealed:
                from ..gf2.ops import crc_tags
                pages = sorted(sealed)
                bufs = self.ex.read_pages(self.cache, pages)
                tags = crc_tags(bufs)
                m.counter("lm_scrub_pages").inc(len(pages))
                for p, t in zip(pages, tags):
                    if int(t) != sealed[p]:
                        self._quarantine_page(p)
        if self._param_tags:
            from ..core.engine import scrub_params
            params, report = scrub_params(self.ex.params, self._param_tags)
            for path, verdict in report.items():
                if verdict != "clean":
                    m.counter(f"lm_param_scrub_{verdict}").inc()
            if any(v == "repaired" for v in report.values()):
                self.ex.reload_params(params)
        m.histogram("lm_scrub_s").record(time.perf_counter() - t0)

    def _quarantine_page(self, p: int):
        """A sealed page failed its CRC re-check: pull it out of
        circulation permanently (it never re-enters the free list) and
        recompute every request that mapped it — abort the slot, clear
        the partial output, and re-prefill from the prompt (greedy
        re-generation is bit-identical); past the retry budget the
        request fails terminally with reason 'corruption'. The page is
        also evicted from the prefix index so no future prompt can match
        into poisoned history."""
        m = self.metrics
        m.counter("lm_pages_quarantined").inc()
        if self.prefix is not None and self.prefix.evict_page(p):
            self.pool.decref([p])  # the index's registration reference
        self.pool.quarantine(p)
        requeue = []
        for s, r in enumerate(self.live):
            if r is None or p not in self.table_np[s]:
                continue
            self._abort_slot(s)
            r.out.clear()  # restart generation from the prompt
            r.first_token_t = None
            r.retries += 1
            m.counter("lm_retries").inc()
            if r.retries > self.max_retries:
                self._fail(r, "corruption")
            else:
                requeue.append(r)
        self.queue[:0] = requeue

    def tick(self) -> List[Request]:
        """One scheduler tick: faults -> scrub -> deadlines -> admission
        -> decode step. The ordering is the scrub-before-read guarantee:
        a bit flipped at this tick's fault stage is caught by this
        tick's scrub (``scrub_every=1``) before the decode step can read
        it — corrupted tokens are never emitted silently.

        Each phase is a span (``obs.trace``) inside the tick's own, and
        the server's ``trace`` builder, if any, is attached for the
        tick."""
        self._ticks += 1
        with attached(self.trace), span("tick", tick=self._ticks):
            if self.faults is not None:
                with span("sched.faults"):
                    self._tick_faults()
            if self.scrub_every and self._ticks % self.scrub_every == 0:
                with span("sched.scrub"):
                    self._scrub()
            with span("sched.admit"):
                self._expire_deadlines()
                self._admit()
            return self.step()

    def run(self) -> List[Request]:
        done = []
        while self.queue or any(r is not None for r in self.live):
            done.extend(self.tick())
        return done


def fmt_latency(latency_s: Optional[float]) -> str:
    """Render a latency for the per-request summary line. Only ``None``
    (not yet retired) is unknown — 0.0 is a legitimate measurement and
    must NOT fall through a truthiness check to '?'."""
    return "?" if latency_s is None else f"{latency_s * 1e3:.1f}ms"


def run_and_report(server: LMServer, requests: List[Request], *,
                   report=None, show_metrics: bool = False) -> List[Request]:
    """Submit, run to completion, and print the shared serving summary
    (one copy for both the serve and serve_lm CLIs: identically-timed
    tok/s, slot/bucket stats, per-request latency percentiles from the
    telemetry registry, optional PPAC cycle accounting)."""
    for r in requests:
        server.submit(r)
    t0 = time.time()
    completed = server.run()
    # an empty request list (or a sub-resolution run) must not divide
    # the tok/s line by zero
    dt = max(time.time() - t0, 1e-9)
    toks = sum(len(r.out) for r in completed)
    print(f"served {len(completed)} requests, {toks} tokens in {dt:.1f}s "
          f"({toks / dt:.1f} tok/s, slots={server.slots}, "
          f"{server.decode_steps} decode steps, "
          f"{server.admit_batches} prefill batches)")
    if server.spec_decode:
        acc = server.metrics.histogram("lm_spec_accept_rate")
        drafted = server.metrics.counter("lm_spec_tokens_drafted").value
        accepted = server.metrics.counter("lm_spec_tokens_accepted").value
        print(f"speculative: draft_k={server.draft_k}, "
              f"accepted {accepted}/{drafted} drafts "
              f"({accepted / max(drafted, 1):.0%}), "
              f"accept-rate p50={acc.percentile(50):.2f} "
              f"({toks / max(server.decode_steps, 1):.2f} tok/round)")
    if server.paged:
        line = (f"paged pool: {server.pool.used_pages}/{server.pool.pages} "
                f"pages held (page_size={server.page_size})")
        if server.prefix is not None:
            hit, tot = server.prefix.pages_hit, server.prefix.pages_probed
            line += (f", prefix hits {hit}/{tot} pages "
                     f"({hit / max(tot, 1):.0%})")
        print(line)
    shed = server.metrics.total("lm_requests_shed")
    failed = server.metrics.total("lm_requests_failed")
    retries = server.metrics.counter("lm_retries").value
    if shed or failed or retries or server.terminal:
        reasons = sorted({r.fail_reason for r in server.terminal
                          if r.fail_reason})
        print(f"outcomes: {len(completed)} completed, {shed} shed, "
              f"{failed} failed"
              + (f" ({', '.join(reasons)})" if reasons else "")
              + f"; {retries} retries, "
              f"{server.metrics.total('lm_worker_restarts')} worker "
              f"restarts"
              + (", DEGRADED (prefill on decode mesh)"
                 if server.metrics.gauge('lm_degraded').value else ""))
    quar = server.metrics.total("lm_pages_quarantined")
    if quar:
        print(f"integrity: {quar} KV pages quarantined by CRC scrub "
              f"({server.metrics.total('lm_scrub_pages')} page checks)")
    lat = server.metrics.histogram("lm_request_latency_s")
    ttft = server.metrics.histogram("lm_ttft_s")
    if lat.count:
        print(f"latency submit->retire: p50={lat.percentile(50) * 1e3:.1f}ms "
              f"p95={lat.percentile(95) * 1e3:.1f}ms "
              f"max={lat.max * 1e3:.1f}ms; "
              f"ttft p50={ttft.percentile(50) * 1e3:.1f}ms "
              f"p95={ttft.percentile(95) * 1e3:.1f}ms")
    if report is not None:
        print(f"PPAC compute: {toks * report.cycles_per_token} emulated "
              f"cycles for {toks} decoded tokens "
              f"({report.cycles_per_token}/token, "
              f"{toks * report.energy_nj_per_token / 1e3:.2f} uJ modeled)")
    for r in completed[:3]:
        print(f"  req {r.rid} [{r.finish_reason}, {fmt_latency(r.latency_s)}]: "
              f"{r.out[:8]}...")
    if show_metrics:
        print(server.metrics.prometheus_text(), end="")
    return completed


def chaos_check(server: LMServer) -> List[str]:
    """The chaos invariants (shared by ``--chaos-gate`` and the test
    suite). Returns human-readable violations; empty = all held.

      1. no request lost: submitted == completed + shed + failed, and
         nothing is still queued or resident;
      2. page-pool refcount conservation: every remaining reference is a
         live slot mapping, a prefix registration, or an injected
         squeeze hold — nothing leaked, nothing double-freed;
      3. every injected KV bit-flip was caught by the CRC scrub (each
         flip quarantines the page it corrupted — schedule flips on
         distinct scrub intervals).
    """
    m = server.metrics
    problems: List[str] = []
    submitted = m.counter("lm_requests_submitted").value
    retired = m.counter("lm_requests_retired").value
    shed = m.total("lm_requests_shed")
    failed = m.total("lm_requests_failed")
    if submitted != retired + shed + failed:
        problems.append(
            f"request conservation: {submitted} submitted != "
            f"{retired} completed + {shed} shed + {failed} failed")
    if server.queue or any(r is not None for r in server.live):
        problems.append("requests still queued/resident after run")
    for r in server.terminal:
        if r.outcome not in ("shed", "failed"):
            problems.append(
                f"terminal request {r.rid} has outcome {r.outcome!r}")
    if server.paged:
        refs = int(server.pool.refcount.sum())
        mapped = int(sum((server.table_np[s] < server.pool_pages).sum()
                         for s, r in enumerate(server.live)
                         if r is not None))
        registered = (server.prefix.registered_pages
                      if server.prefix is not None else 0)
        held = sum(len(sq[1]) for sq in server._squeezes)
        if refs != mapped + registered + held:
            problems.append(
                f"pool conservation: {refs} refs != {mapped} slot "
                f"mappings + {registered} prefix registrations + "
                f"{held} squeeze holds")
    flips = m.counter("lm_faults_injected", kind="kv_flip").value
    quar = m.counter("lm_pages_quarantined").value
    if server.kv_crc and quar < flips:
        problems.append(f"{flips} KV bit-flips injected but only {quar} "
                        f"pages quarantined by the scrub")
    return problems


def build_lm_server(arch: str, *, full: bool = False,
                    serve_quant: bool = False, weight_bits: int = 4,
                    kv_int8: bool = False, backend: str = "auto",
                    spec_decode: bool = False, **server_kw):
    """Config -> random weights -> resident serving containers -> server.

    ``arch`` names a module of ``repro.configs``; ``full`` picks its
    published widths instead of the smoke preset. Weights are random,
    drawn from seed 0. ``serve_quant`` packs every eligible
    projection at ``weight_bits`` (1..4 bits: packed bitplanes on the PPAC
    kernels, 8: int8 rows) with 8-bit activations, launched on the kernel
    ``backend`` ('auto' = Pallas on TPU). ``server_kw`` goes to
    :class:`LMServer`. Returns (server, PPAC cycle report or None).
    """
    mod = load_arch(arch)
    cfg = mod.full() if full else mod.smoke()
    if kv_int8:
        cfg = dataclasses.replace(cfg, kv_dtype="int8")
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    mode, report = "float", None
    if serve_quant:
        cfg = dataclasses.replace(
            cfg, ppac=dataclasses.replace(cfg.ppac, enabled=True,
                                          weight_bits=weight_bits,
                                          act_bits=8, min_features=32,
                                          backend=backend))
        params = convert_params_for_serving(params, cfg, draft=spec_decode)
        mode = "serve"
        report = serving_cycle_report(params, cfg)
    server = LMServer(cfg, params, mode=mode, spec_decode=spec_decode,
                      **server_kw)
    return server, report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_360m")
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="the architecture's reduced test preset (default)")
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="the architecture's published widths and depth")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed for sampled decoding (temperature > 0); "
                         "runs with the same seed reproduce exactly")
    ap.add_argument("--eos", type=int, default=None)
    ap.add_argument("--spec-decode", action="store_true",
                    help="self-speculative decoding: draft with the "
                         "resident packed1 rung, verify all drafts in one "
                         "batched target-rung launch (outputs identical "
                         "to plain decoding; greedy is bit-exact)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="speculative draft depth per round")
    ap.add_argument("--serve-quant", action="store_true")
    ap.add_argument("--weight-bits", type=int, default=4,
                    choices=(1, 2, 3, 4, 8))
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--paged", action="store_true",
                    help="virtualize the KV cache into fixed-size pages "
                         "over a bounded pool with a block table")
    ap.add_argument("--page-size", type=int, default=16,
                    help="rows per physical page (must divide the cache "
                         "extent)")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="physical pool size; default slots*extent/page_size "
                         "(smaller pools backpressure admission)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="CAM-matched prefix reuse: map shared prompt "
                         "pages instead of re-prefilling them")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="shard the resident server over a device mesh, "
                         "e.g. '2x2' (data x model); raises when fewer "
                         "devices are attached")
    ap.add_argument("--prefill-devices", type=int, default=0,
                    help="disaggregated serving: devices for the prefill "
                         "worker pool (disjoint from decode)")
    ap.add_argument("--decode-devices", type=int, default=0,
                    help="disaggregated serving: devices for the resident "
                         "decode mesh")
    ap.add_argument("--prefill-workers", type=int, default=0,
                    help="split the prefill devices into this many TP "
                         "workers (default: one worker over all of them)")
    ap.add_argument("--metrics", action="store_true",
                    help="print the telemetry registry (Prometheus text) "
                         "after the run")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics snapshot as JSON")
    ap.add_argument("--fault-plan", default=None, metavar="SPEC",
                    help="inject deterministic faults: a JSON file, an "
                         "inline JSON list, or 'kind:seam:at[:k=v,...];...'"
                         " (see launch/faults.py)")
    ap.add_argument("--fault-seed", type=int, default=None,
                    help="seeded random chaos schedule instead of an "
                         "explicit --fault-plan")
    ap.add_argument("--max-retries", type=int, default=1,
                    help="per-request retry budget before terminal "
                         "failure")
    ap.add_argument("--max-worker-restarts", type=int, default=1,
                    help="rebuilds per dead prefill worker before it is "
                         "dropped (empty pool => degraded mode)")
    ap.add_argument("--kv-crc", action="store_true",
                    help="GF(2)-CRC-tag sealed prompt pages (paged only); "
                         "the scrub quarantines drifted pages")
    ap.add_argument("--scrub-every", type=int, default=0,
                    help="scrub sealed pages + weight containers every N "
                         "scheduler ticks (0 = off; 1 guarantees flips "
                         "are caught before any decode reads them)")
    ap.add_argument("--chaos-gate", action="store_true",
                    help="exit nonzero unless every request reached one "
                         "terminal outcome, the page pool conserved "
                         "refcounts, and every injected KV flip was "
                         "caught by the scrub")
    args = ap.parse_args()

    faults = None
    if args.fault_plan:
        faults = FaultPlan.parse(args.fault_plan)
    elif args.fault_seed is not None:
        faults = FaultPlan.seeded(args.fault_seed,
                                  n_requests=args.requests)

    use_compile_cache()
    mesh = (make_serving_mesh(parse_mesh_spec(args.mesh))
            if args.mesh else None)
    server, report = build_lm_server(
        args.arch, full=not args.smoke, serve_quant=args.serve_quant,
        weight_bits=args.weight_bits, kv_int8=args.kv_int8,
        slots=args.slots, max_seq=args.max_seq,
        temperature=args.temperature, top_k=args.top_k, seed=args.seed,
        paged=args.paged, page_size=args.page_size,
        pool_pages=args.pool_pages, prefix_cache=args.prefix_cache,
        spec_decode=args.spec_decode, draft_k=args.draft_k, mesh=mesh,
        prefill_devices=args.prefill_devices,
        decode_devices=args.decode_devices,
        prefill_workers=args.prefill_workers, faults=faults,
        max_retries=args.max_retries,
        max_worker_restarts=args.max_worker_restarts, kv_crc=args.kv_crc,
        scrub_every=args.scrub_every)
    cfg = server.cfg
    rng = np.random.default_rng(0)
    run_and_report(
        server,
        [Request(i, rng.integers(0, cfg.vocab, int(rng.integers(4, 24))),
                 args.max_new, eos=args.eos)
         for i in range(args.requests)],
        report=report, show_metrics=args.metrics)
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(server.metrics.snapshot(), f, indent=1)
        print(f"wrote metrics snapshot to {args.metrics_out}")
    if args.chaos_gate:
        problems = chaos_check(server)
        if problems:
            for p in problems:
                print(f"CHAOS GATE FAILED: {p}")
            sys.exit(1)
        print("chaos gate passed: no request lost, pool conserved, "
              "all injected flips detected")


if __name__ == "__main__":
    main()
