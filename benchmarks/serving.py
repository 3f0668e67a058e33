"""Serving-path benchmark: LM decode-step latency, end-to-end generation
throughput, + emulated PPAC cycles.

One decode step of a small LM is timed per resident weight container
(bf16 float baseline, int8 MXU fallback, packed4 / packed1 fused PPAC
kernels) and priced in the paper's §III-C K·L cycle accounting aggregated
over every projection — the Table II NN-inference story at model scale.

The packed kinds run twice: the zero-repack fast path (grouped wqkv/wig
containers, in-kernel activation bit-slicing, load-time MXU shadow) and
the pre-PR ``*_prepack`` path (per-projection containers, per-call weight
unpacking on the MXU lowering) — the before/after pair the perf
trajectory tracks. ``benchmarks.check_serving`` gates CI on the fast path
beating the prepack path and staying at least level with int8.

On top of the per-step rows, ``gen_*`` rows time *generation* end to end
(prefill + N decoded tokens, reported as us/token with tokens/sec in the
derived column) across a batch sweep (b1/b2/b8/b16) per weight kind:
``gen_scan`` is the device-resident ``lax.scan`` program with donated
ring caches and fused sampling (one dispatch for the whole tail),
``gen_loop`` the per-step python loop it replaced (one dispatch per
token). ``benchmarks.check_serving`` gates scan >= 2x loop at smoke
scale — the dispatch/donation overhead the scan path deletes.

Timing is a warmed, fixed-iteration, ``lax``-free python loop; the
reported figure is the p50 over >= 5 repetitions (single-rep means on a
shared CI box are noisy enough to hide a 20% regression).

Run directly (``python -m benchmarks.serving --trace-out trace.json``)
for the *traced serving smoke*: the continuous-batching LM server runs
with the flight recorder open and exports a Perfetto-loadable Chrome
trace (server prefill/decode spans interleaved with per-launch PPAC
kernel events carrying cycles / energy / tile-plan args) plus a
telemetry-registry snapshot; an in-run gate asserts the ledger cycles of
one eager decode step equal the cost-model report exactly.
"""
import argparse
import dataclasses
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import load_arch
from repro.models import lm
from repro.serve.step import (
    convert_params_for_serving,
    generate_scan,
    greedy_generate,
    make_decode_select_step,
    make_prefill_step,
    make_speculative_decode_step,
    make_speculative_scan,
    serving_cycle_report,
)

# (weight_bits, label, fast path?) — fast = grouped + resident shadow,
# prepack = the pre-PR per-projection / per-call-unpack layout.
_CONTAINERS = [
    (0, "float_bf16", True),
    (8, "int8", True),
    (4, "packed4", True),
    (1, "packed1", True),
    (4, "packed4_prepack", False),
    (1, "packed1_prepack", False),
]

# generation sweep: every fast-path kind x decode batch; the python-loop
# baseline rides once per kind (at _GEN_LOOP_BATCH) for the CI gate.
_GEN_KINDS = [(0, "float_bf16"), (8, "int8"), (4, "packed4"), (1, "packed1")]
_GEN_BATCHES = (1, 2, 8, 16)
_GEN_LOOP_BATCH = 1
_GEN_STEPS = 16
_GEN_PROMPT = 8


def _t(fn, *, iters: int = 10, reps: int = 7):
    """p50 per-call µs: compile + warm, then ``reps`` timed runs of a
    fixed ``iters``-iteration python loop (block once per run)."""
    jax.block_until_ready(fn())  # compile
    jax.block_until_ready(fn())  # warm
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        r = None
        for _ in range(iters):
            r = fn()
        jax.block_until_ready(r)
        samples.append((time.perf_counter() - t0) / iters * 1e6)
    return statistics.median(samples)


def _serving_cfg_params(base, params0, wb, *, fast=True):
    if wb == 0:
        return base, params0, "float", None
    cfg = dataclasses.replace(
        base, ppac=dataclasses.replace(
            base.ppac, enabled=True, weight_bits=wb, act_bits=8,
            min_features=32))
    # fast: grouped containers + platform-default shadow policy;
    # prepack: per-projection, no shadow (per-call unpack — pre-PR)
    params = convert_params_for_serving(
        params0, cfg, group=fast, store_shadow=None if fast else False)
    return cfg, params, "serve", serving_cycle_report(params, cfg)


def run():
    rows = []
    base = load_arch("stablelm_12b").smoke()
    params0, _ = lm.init(base, jax.random.PRNGKey(0))
    slots, max_seq = 2, 32
    for wb, label, fast in _CONTAINERS:
        cfg, params, mode, rep = _serving_cfg_params(base, params0, wb,
                                                     fast=fast)
        cache, _ = lm.init_cache(cfg, slots, max_seq)
        _, cache = jax.jit(
            lambda p, b, c, cfg=cfg, mode=mode: lm.prefill(p, cfg, b, c,
                                                           mode=mode)
        )(params, {"tokens": jnp.ones((slots, 8), jnp.int32)}, cache)
        decode = jax.jit(
            lambda p, t, c, cfg=cfg, mode=mode: lm.decode_step(p, cfg, t, c,
                                                               mode=mode))
        tok = jnp.ones((slots, 1), jnp.int32)
        us = _t(lambda: decode(params, tok, cache)[0])
        kind = label.removesuffix("_prepack")
        extras = (dict(kind=kind, path="fast" if fast else "prepack",
                       cycles_per_tok=rep.cycles_per_token,
                       fused=rep.fused_cycles_per_token,
                       energy_nj_per_tok=round(rep.energy_nj_per_token, 3))
                  if rep else dict(kind=kind, path="fast"))
        rows.append((f"serve_decode_{label}_b{slots}", us, extras))
    rows.extend(_generation_rows(base, params0))
    rows.extend(_spec_rows(base, params0))
    rows.extend(_paged_prefix_rows())
    rows.extend(_chaos_rows())
    rows.extend(_mesh_rows())
    return rows


# chaos/integrity sweep: CRC-scrub overhead + degraded-mode throughput
_CHAOS_ARCH = "smollm_360m"
_CHAOS_REQUESTS = 6
_CHAOS_MAX_NEW = 8
_CHAOS_SLOTS = 2


def _chaos_rows():
    """Integrity/recovery costs on the serving path.

    ``serve_crc_off`` / ``serve_crc_on``: the same paged workload served
    with and without the per-page GF(2) CRC seal + every-tick scrub
    (``kv_crc=True, scrub_every=1`` — the paranoid setting; production
    would scrub every N). The delta is the full integrity bill: sealing
    freshly-prefilled prompt pages, re-reading + re-tagging every sealed
    page per tick. ``benchmarks.check_serving --crc-overhead`` gates the
    tok/s cost.

    ``serve_degraded``: throughput of a disaggregated server AFTER its
    prefill-worker pool is lost (an injected crash with a zero restart
    budget) — every admission goes through the decode-mesh fallback
    prefill. Informational: the CI chaos smoke gates the *behavior*
    (no request lost, bit-identity); this row prices the mode.
    """
    from repro.launch.faults import FaultPlan
    from repro.launch.serve_lm import LMServer, Request
    from repro.obs import MetricsRegistry

    cfg = load_arch(_CHAOS_ARCH).smoke()
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab, int(rng.integers(9, 20)))
               for _ in range(_CHAOS_REQUESTS)]

    def serve_batch(server, rid0):
        for i, p in enumerate(prompts):
            server.submit(Request(rid0 + i, np.asarray(p, np.int32),
                                  _CHAOS_MAX_NEW))
        return server.run()

    def timed(server, metrics):
        serve_batch(server, 0)  # compile + warm
        pre = metrics.total("lm_scrub_pages")
        t0 = time.perf_counter()
        done = serve_batch(server, 100)
        dt = time.perf_counter() - t0
        ntok = sum(len(r.out) for r in done)
        assert len(done) == len(prompts)
        return dt / ntok * 1e6, ntok / dt, \
            metrics.total("lm_scrub_pages") - pre

    rows, baseline = [], None
    for tag, kw in (("off", {}),
                    ("on", dict(kv_crc=True, scrub_every=1))):
        metrics = MetricsRegistry()
        server = LMServer(cfg, params, slots=_CHAOS_SLOTS, max_seq=64,
                          paged=True, page_size=8, metrics=metrics, **kw)
        us, tok_s, scrubbed = timed(server, metrics)
        if tag == "off":
            baseline = tok_s
        rows.append((f"serve_crc_{tag}_b{_CHAOS_SLOTS}", us,
                     dict(crc=tag, batch=_CHAOS_SLOTS,
                          tok_s=round(tok_s, 1),
                          pages_scrubbed=int(scrubbed),
                          overhead=round(1.0 - tok_s / baseline, 3))))

    # degraded mode: crash the only prefill worker (restart budget 0)
    # during the warm pass; the timed pass runs fully degraded
    metrics = MetricsRegistry()
    server = LMServer(cfg, params, slots=_CHAOS_SLOTS, max_seq=64,
                      paged=True, page_size=8, metrics=metrics,
                      prefill_devices=1, decode_devices=1,
                      prefill_workers=1, max_worker_restarts=0,
                      max_retries=3,
                      faults=FaultPlan.parse("crash:prefill:0:worker=p0"))
    us, tok_s, _ = timed(server, metrics)
    assert server.ex.degraded, "worker pool survived the injected crash"
    rows.append((f"serve_degraded_b{_CHAOS_SLOTS}", us,
                 dict(crc="off", batch=_CHAOS_SLOTS, degraded=1,
                      tok_s=round(tok_s, 1),
                      vs_local=round(tok_s / baseline, 3))))
    return rows


# multi-device serving sweep: every mesh layout that fits the runtime
# device count, plus a disaggregated prefill/decode split at >= 4
# devices. Each layout serves the same prompt set twice through one
# server (the first pass compiles, the second is timed) and must retire
# bit-identical greedy tokens to the 1x1 baseline — parity rides in the
# row and is gated by ``check_serving --mesh-parity``.
_MESH_BATCHES = (1, 4)
_MESH_MAX_NEW = 8
_MESH_PROMPT = 8


def _mesh_layouts(ndev):
    layouts = [("1x1", {})]
    if ndev >= 2:
        layouts += [("1x2", dict(mesh=(1, 2))),   # pure TP
                    ("2x1", dict(mesh=(2, 1)))]   # pure slot-DP
    if ndev >= 4:
        layouts += [("2x2", dict(mesh=(2, 2))),
                    ("disagg_2p2d", dict(prefill_devices=2,
                                         decode_devices=2))]
    return layouts


def _hist_delta(pre, post, name):
    """(count, mean-seconds) a histogram gained between two snapshots."""
    a, b = pre.get(name, {}), post.get(name, {})
    n = b.get("count", 0) - a.get("count", 0)
    if n <= 0:
        return 0, 0.0
    return n, (b.get("sum", 0.0) - a.get("sum", 0.0)) / n


def _mesh_rows():
    ndev = jax.device_count()
    if ndev < 2:
        return []  # single-device runtime: nothing to shard against
    from repro.launch.serve_lm import LMServer, Request
    from repro.obs import MetricsRegistry

    base = dataclasses.replace(load_arch("smollm_360m").smoke(),
                               dtype="float32")
    params0, _ = lm.init(base, jax.random.PRNGKey(0))
    cfg, params, mode, _ = _serving_cfg_params(base, params0, 4)

    rows, baseline = [], {}
    for tag, kw in _mesh_layouts(ndev):
        for b in _MESH_BATCHES:
            rng = np.random.default_rng(100 + b)  # same prompts per batch
            prompts = [rng.integers(0, cfg.vocab, _MESH_PROMPT)
                       for _ in range(2 * b)]
            metrics = MetricsRegistry()
            server = LMServer(cfg, params, slots=b, max_seq=64, mode=mode,
                              metrics=metrics, **kw)

            def serve_batch(rid0):
                for i, p in enumerate(prompts):
                    server.submit(Request(rid0 + i,
                                          np.asarray(p, np.int32),
                                          _MESH_MAX_NEW))
                return server.run()

            serve_batch(0)  # compile + warm
            pre = metrics.snapshot()
            t0 = time.perf_counter()
            done = serve_batch(100)
            dt = time.perf_counter() - t0
            toks = {r.rid - 100: tuple(r.out) for r in done}
            ntok = sum(len(v) for v in toks.values())
            post = metrics.snapshot()

            if tag == "1x1":
                baseline[b] = toks
            extras = dict(mesh=tag, batch=b, devices=ndev,
                          tok_s=round(ntok / dt, 1),
                          parity=int(toks == baseline[b]))
            n, mean_s = _hist_delta(pre, post, "lm_ttft_s")
            if n:
                extras["ttft_ms"] = round(mean_s * 1e3, 3)
            n, mean_s = _hist_delta(pre, post, "lm_handoff_latency")
            if n:
                extras["handoff_ms"] = round(mean_s * 1e3, 3)
            rows.append((f"serve_mesh_{tag}_b{b}", dt / ntok * 1e6,
                         extras))
    return rows


# speculative-decoding sweep: packed4 target rung, draft_k drafts/round
_SPEC_BATCH = 2
_SPEC_STEPS = 24
_SPEC_K = 4
_SPEC_PROMPT = 8


def _spec_rows(base, params0):
    """Self-speculative decoding rows (temperature 0, packed4 target).

    Three serving paths over the same ``_SPEC_STEPS``-token tail:

      * ``serve_spec_plain``: the per-token decode-select loop — one host
        dispatch per emitted token, the continuous-batching server's
        non-speculative unit of work;
      * ``serve_spec_round``: the fused draft->verify->accept round —
        ONE dispatch retires up to draft_k + 1 tokens. Benchmarked with
        the drafter on the *target* rung (accept rate exactly 1.0, so
        the row isolates the round-dispatch amortization and is
        deterministic enough to CI-gate: ``check_serving
        --spec-speedup`` requires >= 1.3x the plain loop) and with the
        resident *packed1* rung (``draft=packed1``) — the precision-
        ladder configuration, reporting the honest measured accept rate
        (low on random smoke weights; the cycle columns carry the §III-C
        story: draft launches price 1 bit-plane pass against the
        target's K*L);
      * ``serve_spec_scan``: the whole tail as one on-device
        ``lax.while_loop`` program.

    Every spec row is output-bit-identical to the plain loop (asserted
    here, not just claimed).
    """
    rows = []
    cfg, params, mode, _ = _serving_cfg_params(base, params0, 4)
    params_lad = convert_params_for_serving(
        params0, cfg, draft=True)  # + resident packed1 rung of same weights
    b, steps, k = _SPEC_BATCH, _SPEC_STEPS, _SPEC_K
    max_seq = _SPEC_PROMPT + steps + k + 2
    batch = {"tokens": jnp.ones((b, _SPEC_PROMPT), jnp.int32)}
    prefill = make_prefill_step(cfg, None, mode)
    dec = make_decode_select_step(cfg, None, mode)
    spec = make_speculative_decode_step(cfg, None, mode, draft_k=k)
    sscan = make_speculative_scan(cfg, steps=steps, draft_k=k, mode=mode)
    key = jax.random.PRNGKey(0)

    def start(p):
        cache, _ = lm.init_cache(cfg, b, max_seq)
        logits, cache = prefill(p, batch, cache)
        return jnp.argmax(logits[:, -1], -1).astype(jnp.int32), cache

    def plain_call():
        tok, cache = start(params)
        out = [np.asarray(tok)]
        for _ in range(steps - 1):
            tok, cache = dec(params, tok[:, None], cache, key)
            out.append(np.asarray(tok))
        return np.stack(out, axis=1)

    def spec_call(p, stats=None):
        tok, cache = start(p)
        out = np.full((b, steps + k + 1), -1, np.int64)
        out[:, 0] = np.asarray(tok)
        off = np.ones((b,), np.int64)
        while off.min() < steps:
            em, ne, cache = spec(p, tok, cache, key)
            em, ne = np.asarray(em), np.asarray(ne)
            if stats is not None:
                stats.append(ne)
            for s in range(b):
                out[s, off[s]:off[s] + ne[s]] = em[s, :ne[s]]
            tok = jnp.asarray(em[np.arange(b), ne - 1])
            off += ne
        return out[:, :steps]

    def scan_call():
        cache, _ = lm.init_cache(cfg, b, max_seq)
        logits, cache = prefill(params, batch, cache)
        toks, _ = sscan(params, logits, cache, key)
        return toks

    ref = plain_call()
    us = _t(plain_call, iters=2, reps=5) / (steps * b)
    rows.append((f"serve_spec_plain_packed4_b{b}", us,
                 dict(impl="plain_loop", kind="packed4", batch=b,
                      tok_s=round(1e6 / us), steps=steps)))

    for tag, p in (("target", params), ("packed1", params_lad)):
        stats = []
        got = spec_call(p, stats)
        assert np.array_equal(got, ref), \
            f"spec ({tag} drafter) diverged from the plain decode loop"
        ne = np.concatenate(stats)
        accept = float((ne - 1).sum() / (k * len(ne)))
        us = _t(lambda p=p: spec_call(p), iters=2, reps=5) / (steps * b)
        extras = dict(impl="spec_round", kind="packed4", draft=tag,
                      draft_k=k, batch=b, tok_s=round(1e6 / us),
                      accept_rate=round(accept, 3),
                      rounds=len(ne) // b, steps=steps)
        if tag == "packed1":
            # ladder cycle accounting: one eager round under the flight
            # recorder, split by phase tag (deterministic: launch
            # geometry, not wall clock)
            from repro.obs import Ledger
            from repro.serve.step import _spec_round
            tok, cache = start(p)
            with Ledger() as led, jax.disable_jit():
                _spec_round(p, cfg, tok, cache, key, draft_k=k, mode=mode,
                            rules=None, temperature=0.0, top_k=0)
            ph = led.by_phase()
            extras.update(
                draft_cycles_per_round=ph.get("draft", {}).get("cycles", 0),
                verify_cycles_per_round=ph.get("verify", {}).get("cycles",
                                                                 0))
        rows.append((f"serve_spec_round_packed4_{tag}_k{k}_b{b}", us,
                     extras))

    got = np.asarray(scan_call())
    assert np.array_equal(got, ref), \
        "spec scan diverged from the plain decode loop"
    us = _t(scan_call, iters=2, reps=5) / (steps * b)
    rows.append((f"serve_spec_scan_packed4_k{k}_b{b}", us,
                 dict(impl="spec_scan", kind="packed4", draft="target",
                      draft_k=k, batch=b, tok_s=round(1e6 / us),
                      steps=steps)))
    return rows


# paged prefix-reuse sweep: a repeated-system-prompt workload
_PAGED_ARCH = "smollm_360m"
_PAGED_REQUESTS = 4
_PAGED_SHARED = 24   # shared system prompt: 3 full pages at page_size 8
_PAGED_TAIL = 4      # per-request user suffix (partial tail page)
_PAGED_MAX_NEW = 5


def _paged_prefix_rows():
    """Prefix-reuse rows: the same repeated-system-prompt workload served
    three ways — ``cold`` (paged, no prefix cache: the baseline every
    admission pays full prefill), ``register`` (prefix cache on, first
    sight of the prompts: CAM registration + intra-run hits), ``warm``
    (the 100%-shared-prefix rerun on the now-resident pages: admission
    maps matched pages and prefills only suffixes).

    Cycles are ledger-measured per phase and the server runs EAGERLY
    (``jax.disable_jit``): the ledger prices launches at trace time, so
    a cached jit executable would replay nothing. Cycle totals are
    deterministic (launch geometry comes from the padded bucket shapes,
    not wall clock); ``benchmarks.check_serving`` gates warm <= cold/2.
    """
    from repro.launch.serve_lm import LMServer, Request
    from repro.obs import Ledger

    page_size, slots, max_seq = 8, 2, 64
    cfg = load_arch(_PAGED_ARCH).smoke()
    cfg = dataclasses.replace(cfg, ppac=dataclasses.replace(
        cfg.ppac, enabled=True, weight_bits=4, act_bits=8,
        min_features=32, backend="auto"))
    params0, _ = lm.init(cfg, jax.random.PRNGKey(0))
    params = convert_params_for_serving(params0, cfg)
    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab, _PAGED_SHARED)
    prompts = [np.concatenate(
        [shared, rng.integers(0, cfg.vocab, _PAGED_TAIL)]).astype(np.int32)
        for _ in range(_PAGED_REQUESTS)]

    def serve_round(server, phase, rows):
        pre_led, dec_led = Ledger(), Ledger()
        hit0 = server.prefix.pages_hit if server.prefix else 0
        probe0 = server.prefix.pages_probed if server.prefix else 0
        skip0 = server.metrics.counter("lm_prefill_rows_skipped").value
        for i, p in enumerate(prompts):
            server.submit(Request(i, p, _PAGED_MAX_NEW))
        t0 = time.perf_counter()
        done = 0
        while server.queue or any(r is not None for r in server.live):
            with pre_led:
                server._admit()
            with dec_led:
                done += len(server.step())
        dt = time.perf_counter() - t0
        assert done == len(prompts)
        probed = (server.prefix.pages_probed - probe0) if server.prefix \
            else 0
        hits = (server.prefix.pages_hit - hit0) if server.prefix else 0
        skipped = server.metrics.counter("lm_prefill_rows_skipped").value \
            - skip0
        rows.append((
            f"serve_paged_prefill_{phase}", dt / len(prompts) * 1e6,
            dict(workload="shared_prefix", phase=phase,
                 prefill_cycles=pre_led.total_cycles,
                 prefill_launches=pre_led.num_launches,
                 decode_cycles=dec_led.total_cycles,
                 prefix_hit_rate=round(hits / probed, 3) if probed else 0.0,
                 rows_skipped=skipped,
                 requests=len(prompts), page_size=page_size)))

    rows = []
    with jax.disable_jit():
        cold = LMServer(cfg, params, slots=slots, max_seq=max_seq,
                        mode="serve", paged=True, page_size=page_size)
        serve_round(cold, "cold", rows)
        warm = LMServer(cfg, params, slots=slots, max_seq=max_seq,
                        mode="serve", paged=True, page_size=page_size,
                        prefix_cache=True)
        serve_round(warm, "register", rows)  # first sight: registration
        serve_round(warm, "warm", rows)      # 100%-shared rerun
    cyc = {e["phase"]: e["prefill_cycles"] for _, _, e in rows}
    rows[-1][2]["cycles_saved_ratio"] = round(cyc["cold"] / cyc["warm"], 2)
    return rows


def _generation_rows(base, params0):
    """End-to-end generation throughput: scan-fused vs per-step loop.

    Each call is the full serving unit — cache init + prefill(b x 8) + 16
    decoded tokens — so the row is honest end-to-end tokens/sec, and the
    donated cache is freshly allocated per call (donation consumes it)."""
    rows = []
    gen_max_seq = _GEN_PROMPT + _GEN_STEPS + 1
    for wb, label in _GEN_KINDS:
        cfg, params, mode, _ = _serving_cfg_params(base, params0, wb)
        for b in _GEN_BATCHES:
            batch = {"tokens": jnp.ones((b, _GEN_PROMPT), jnp.int32)}

            def scan_call(cfg=cfg, params=params, mode=mode, batch=batch):
                return generate_scan(params, cfg, batch, steps=_GEN_STEPS,
                                     max_seq=gen_max_seq, mode=mode)

            us = _t(scan_call, iters=2, reps=5) / (_GEN_STEPS * b)
            rows.append((f"gen_scan_{label}_b{b}", us,
                         dict(impl="scan", kind=label, batch=b,
                              tok_s=round(1e6 / us), steps=_GEN_STEPS)))
            if b == _GEN_LOOP_BATCH:
                def loop_call(cfg=cfg, params=params, mode=mode,
                              batch=batch):
                    return greedy_generate(params, cfg, batch,
                                           steps=_GEN_STEPS,
                                           max_seq=gen_max_seq, mode=mode)

                us = _t(loop_call, iters=2, reps=5) / (_GEN_STEPS * b)
                rows.append((f"gen_loop_{label}_b{b}", us,
                             dict(impl="loop", kind=label, batch=b,
                                  tok_s=round(1e6 / us), steps=_GEN_STEPS)))
    return rows


def traced_smoke(*, arch: str = "smollm_360m", requests: int = 6,
                 weight_bits: int = 4, slots: int = 3, max_new: int = 8,
                 trace_out=None, metrics_out=None):
    """Traced serving smoke: the LM server under the flight recorder.

    Serves ``requests`` random prompts through the continuous-batching
    server with a :class:`~repro.obs.Ledger` open, a telemetry registry
    attached, and Chrome-trace span capture on — then (optionally)
    writes the interleaved trace and the metrics snapshot. Before the
    serving run, one eager decode step gates the recorder against the
    static cost model. ``lax.scan`` over the stacked blocks traces its
    body exactly once (the records carry ``traced=True``), so the step
    emits each stacked projection once and the gate compares against the
    report's per-layer-unique cycles — the ``count`` column is pure
    layer multiplicity:

        ledger.total_cycles == slots * sum(p.cycles / p.count)

    Because both sides price launches through
    ``obs.ledger.record_for``, any drift between the instrumented
    dispatch path and the §III-C accounting fails CI here (full
    count-weighted equality is asserted per container kind in
    tests/test_obs.py, where no layer stacking is involved).
    """
    from repro.launch.serve_lm import LMServer, Request, run_and_report
    from repro.obs import Ledger, MetricsRegistry, TraceBuilder, span
    from repro.obs.trace import attached

    max_seq = 64
    cfg = load_arch(arch).smoke()
    cfg = dataclasses.replace(cfg, ppac=dataclasses.replace(
        cfg.ppac, enabled=True, weight_bits=weight_bits, act_bits=8,
        min_features=32, backend="auto"))
    params0, _ = lm.init(cfg, jax.random.PRNGKey(0))
    params = convert_params_for_serving(params0, cfg)
    report = serving_cycle_report(params, cfg)

    trace = TraceBuilder()
    metrics = MetricsRegistry()
    with Ledger() as flight:  # outer: every launch -> the trace
        # -- golden gate: eager decode step vs the static cycle report
        cache, _ = lm.init_cache(cfg, slots, max_seq)
        toks = jnp.ones((slots, 1), jnp.int32)
        with Ledger() as led, jax.disable_jit(), attached(trace), \
                span("eager_decode_golden", slots=slots):
            lm.decode_step(params, cfg, toks, cache, mode="serve")
        per_layer = sum(p.cycles // p.count for p in report.projections)
        expect = slots * per_layer
        assert led.total_cycles == expect, (
            f"flight-recorder drift: eager decode step recorded "
            f"{led.total_cycles} cycles, cost model prices it at "
            f"{expect} ({slots} slots x {per_layer} per-layer-unique "
            f"cycles/token; full report: {report.cycles_per_token})")
        print(f"golden gate OK: {led.total_cycles} recorded cycles == "
              f"{slots} slots x {per_layer} per-layer-unique cycles/token "
              f"({len(led.records)} launches, "
              f"{led.total_energy_nj:.1f} nJ modeled, report "
              f"{report.cycles_per_token} cycles/token over "
              f"{len(report.projections)} projections)")

        # -- the served run, spans + telemetry on
        server = LMServer(cfg, params, slots=slots, max_seq=max_seq,
                          mode="serve", metrics=metrics, trace=trace)
        rng = np.random.default_rng(0)
        run_and_report(
            server,
            [Request(i, rng.integers(0, cfg.vocab, int(rng.integers(4, 16))),
                     max_new) for i in range(requests)],
            report=report)
    trace.add_ledger(flight)

    lat = metrics.histogram("lm_ttft_s")
    assert lat.count == requests, "telemetry lost requests"
    if trace_out:
        trace.write(trace_out)
        print(f"wrote {trace.num_events} trace events to {trace_out} "
              f"(load in https://ui.perfetto.dev)")
    if metrics_out:
        payload = dict(metrics=metrics.snapshot(),
                       serving_cycle_report=report.as_dict(),
                       ledger=flight.summary())
        with open(metrics_out, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"wrote metrics snapshot to {metrics_out}")
    return trace, metrics, report


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="traced serving smoke (see module docstring)")
    ap.add_argument("--arch", default="smollm_360m")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--weight-bits", type=int, default=4,
                    choices=(1, 2, 3, 4, 8))
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the Chrome-trace JSON (Perfetto-loadable)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the telemetry + cycle-report snapshot JSON")
    ap.add_argument("--mesh-bench", default=None, metavar="PATH",
                    help="run only the multi-device serve_mesh sweep and "
                         "write its rows as benchmarks.run-schema JSON "
                         "(CI runs this under forced-host devices)")
    args = ap.parse_args(argv)
    if args.mesh_bench:
        from .run import derived_string
        rows = _mesh_rows()
        if not rows:
            print("mesh bench: single-device runtime — set XLA_FLAGS="
                  "--xla_force_host_platform_device_count=N", file=sys.stderr)
            return 1
        payload = [dict(module="serving", name=name, us_per_call=us,
                        derived=derived_string(extras), **extras)
                   for name, us, extras in rows]
        with open(args.mesh_bench, "w") as f:
            json.dump(payload, f, indent=2)
        for name, us, extras in rows:
            print(f"{name},{us:.1f},{derived_string(extras)}")
        print(f"wrote {len(payload)} mesh rows to {args.mesh_bench}",
              file=sys.stderr)
        return 0
    traced_smoke(arch=args.arch, requests=args.requests,
                 weight_bits=args.weight_bits, slots=args.slots,
                 max_new=args.max_new, trace_out=args.trace_out,
                 metrics_out=args.metrics_out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
