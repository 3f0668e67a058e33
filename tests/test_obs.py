"""Flight recorder: instruction-ledger/cost-model agreement, zero
overhead when disabled, metrics registry invariants, Chrome-trace export.

The load-bearing property is *structural*: the live ledger (records
captured at the dispatch chokepoint) and the static
``serving_cycle_report`` both price launches through
``obs.ledger.record_for``, so their totals must agree bit-exactly for
every container kind — packed1, packed4, oddint (mask plane), the int8
MXU fallback, and grouped (fused wqkv-style) projections.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, PPACModeConfig
from repro.core.engine import (
    pack_weight_for_serving,
    serve_dense,
    serve_dense_grouped,
)
from repro.kernels.engine import ppac_matmul
from repro.obs import Ledger, MetricsRegistry, TraceBuilder, span
from repro.obs.trace import attached
from repro.obs import ledger as obs_ledger


def _cfg(weight_bits, weight_format="int", act_bits=4):
    ppac = PPACModeConfig(enabled=True, weight_bits=weight_bits,
                          act_bits=act_bits, weight_format=weight_format)
    return ModelConfig(name="t", family="t", n_layers=1, d_model=64,
                       n_heads=2, n_kv_heads=2, d_ff=128, vocab=32,
                       ppac=ppac)


@pytest.mark.parametrize("weight_bits,weight_format,kind", [
    (1, "int", "packed1"),
    (4, "int", "packed4"),
    (4, "oddint", "packed4"),   # extra resident mask plane
    (8, "int", "int8"),         # MXU fallback, bypasses ppac_matmul
])
def test_ledger_matches_cycle_report(weight_bits, weight_format, kind):
    """One token through serve_dense records exactly the cycles/energy
    the static report replays for that projection — bit-exact."""
    from repro.serve.step import serving_cycle_report

    cfg = _cfg(weight_bits, weight_format)
    rng = np.random.default_rng(0)
    w = rng.standard_normal((64, 128)).astype(np.float32) * 0.1
    c = pack_weight_for_serving(jnp.asarray(w), weight_bits=weight_bits,
                                weight_format=weight_format)
    assert c.kind == kind
    report = serving_cycle_report({"blk": {"w": c}}, cfg)

    x = jnp.asarray(rng.standard_normal((1, 64)).astype(np.float32))
    with Ledger() as led:
        serve_dense(x, c, act_bits=cfg.ppac.act_bits, backend="mxu")

    assert led.total_cycles == report.cycles_per_token
    assert led.total_energy_nj == pytest.approx(report.energy_nj_per_token)
    (rec,) = led.records
    assert not rec.traced          # eager call: per-execution record
    assert rec.m_rows == 128 and rec.n_bits == 64
    if kind == "int8":
        assert rec.mode == "mvp_int8_mxu"
    else:
        assert rec.mode == "mvp_multibit_resident"


def test_ledger_matches_cycle_report_grouped():
    """A grouped (fused wqkv-style) container: one fat launch, priced at
    the fused [sum(out), in] shape on both sides."""
    from repro.serve.step import serving_cycle_report

    cfg = _cfg(4)
    rng = np.random.default_rng(1)
    splits = (48, 48, 32)
    w = rng.standard_normal((64, sum(splits))).astype(np.float32) * 0.1
    c = pack_weight_for_serving(jnp.asarray(w), weight_bits=4,
                                splits=splits)
    report = serving_cycle_report({"wqkv": {"w": c}}, cfg)
    assert report.projections[0].d_out == sum(splits)

    x = jnp.asarray(rng.standard_normal((1, 64)).astype(np.float32))
    with Ledger() as led:
        outs = serve_dense_grouped(x, c, act_bits=cfg.ppac.act_bits,
                                   backend="mxu")
    assert tuple(o.shape[-1] for o in outs) == splits
    assert len(led.records) == 1  # ONE fused launch for the group
    assert led.total_cycles == report.cycles_per_token
    assert led.total_energy_nj == pytest.approx(report.energy_nj_per_token)


def test_ledger_batch_scaling_and_plan_capture():
    """Cycles scale linearly in the streamed batch; pallas launches
    capture the resolved tile plan on the record."""
    rng = np.random.default_rng(2)
    c = pack_weight_for_serving(
        jnp.asarray(rng.standard_normal((64, 128)).astype(np.float32)),
        weight_bits=4)
    xs = [jnp.asarray(rng.standard_normal((b, 64)).astype(np.float32))
          for b in (1, 3)]
    with Ledger() as led:
        for x in xs:
            serve_dense(x, c, act_bits=4, backend="mxu")
    r1, r3 = led.records
    assert r3.cycles == 3 * r1.cycles
    assert r3.energy_nj == pytest.approx(3 * r1.energy_nj)
    assert led.by_mode()["mvp_multibit_resident"]["launches"] == 2


def test_ledger_nesting_is_independent():
    """Nested ledgers each see the launches issued while they are open."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(
        rng.integers(0, 2**32, (2, 2), dtype=np.uint64).astype(np.uint32))
    a = jnp.asarray(
        rng.integers(0, 2**32, (4, 2), dtype=np.uint64).astype(np.uint32))
    with Ledger() as outer:
        ppac_matmul(x, a, mode="hamming", n=64, backend="mxu")
        with Ledger() as inner:
            ppac_matmul(x, a, mode="hamming", n=64, backend="mxu")
    assert len(inner.records) == 1
    assert len(outer.records) == 2
    assert outer.total_cycles == 2 * inner.total_cycles


def test_zero_overhead_when_disabled(monkeypatch):
    """With no ledger open, the instrumented paths never touch the
    recorder beyond the single ``active()`` check — the README's
    zero-overhead-when-disabled guarantee."""
    def boom(*a, **k):  # pragma: no cover - must never run
        raise AssertionError("recorder invoked with no ledger open")

    monkeypatch.setattr(obs_ledger, "recorded_launch", boom)
    monkeypatch.setattr(obs_ledger, "record_launch", boom)
    assert not obs_ledger.active()

    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((1, 64)).astype(np.float32))
    for wb in (4, 8):  # fused path and the int8 MXU fallback
        c = pack_weight_for_serving(
            jnp.asarray(rng.standard_normal((64, 96)).astype(np.float32)),
            weight_bits=wb)
        serve_dense(x, c, act_bits=4, backend="mxu")


def test_metrics_registry_snapshot_and_percentiles():
    m = MetricsRegistry()
    m.counter("reqs").inc()
    m.counter("reqs").inc(4)
    m.gauge("occ").set(3)
    m.gauge("occ").set(1)
    h = m.histogram("lat_s")
    for v in np.linspace(0.001, 0.1, 100):
        h.record(float(v))
    snap = m.snapshot()
    assert snap["reqs"] == 5
    assert snap["occ"] == {"value": 1, "max": 3}
    assert snap["lat_s"]["count"] == 100
    assert snap["lat_s"]["min"] == pytest.approx(0.001)
    assert snap["lat_s"]["max"] == pytest.approx(0.1)
    # percentiles are bucket-interpolated estimates: ordered + in-range
    p50, p90 = h.percentile(50), h.percentile(90)
    assert 0.001 <= p50 <= p90 <= 0.1
    assert abs(p50 - 0.05) < 0.02
    json.dumps(snap)  # the CI artifact format must be JSON-serializable

    text = m.prometheus_text()
    assert "# TYPE reqs counter" in text and "reqs 5" in text
    assert "# TYPE occ gauge" in text
    assert '# TYPE lat_s summary' in text and 'quantile="0.5"' in text

    with pytest.raises(AssertionError):  # name/type collisions are bugs
        m.gauge("reqs")


def test_metrics_labels_and_escaping():
    """Per-worker labels: distinct (name, labels) pairs are distinct
    metrics, unlabeled names keep their bare snapshot keys, and
    exposition output escapes hostile label values (a worker id with a
    quote must not corrupt the whole scrape)."""
    m = MetricsRegistry()
    m.counter("lm_worker_dispatches", worker="p0", role="prefill").inc(2)
    m.counter("lm_worker_dispatches", worker="d0", role="disagg").inc()
    m.counter("lm_worker_dispatches", worker="p0", role="prefill").inc()
    m.histogram("lm_handoff_latency").record(0.002)
    m.histogram("lm_handoff_latency", worker="p0").record(0.002)

    snap = m.snapshot()
    # canonical sorted-label keys; same labels -> same instance
    assert snap['lm_worker_dispatches{role="prefill",worker="p0"}'] == 3
    assert snap['lm_worker_dispatches{role="disagg",worker="d0"}'] == 1
    # the unlabeled histogram keeps its bare-name key (back-compat)
    assert snap["lm_handoff_latency"]["count"] == 1
    assert snap['lm_handoff_latency{worker="p0"}']["count"] == 1

    text = m.prometheus_text()
    assert 'lm_worker_dispatches{role="prefill",worker="p0"} 3' in text
    # one TYPE line per metric family, not per labeled instance
    assert text.count("# TYPE lm_worker_dispatches counter") == 1

    hostile = MetricsRegistry()
    hostile.counter("c", worker='p"0\\x\n').inc()
    line = next(l for l in hostile.prometheus_text().splitlines()
                if l.startswith("c{"))
    assert line == 'c{worker="p\\"0\\\\x\\n"} 1'


def test_trace_export_valid_and_monotonic():
    """Trace output: valid JSON, named tracks, per-track monotonic ts,
    ledger launch events carrying cycles/energy args."""
    rng = np.random.default_rng(5)
    c = pack_weight_for_serving(
        jnp.asarray(rng.standard_normal((64, 128)).astype(np.float32)),
        weight_bits=4)
    x = jnp.asarray(rng.standard_normal((2, 64)).astype(np.float32))
    trace = TraceBuilder()
    with Ledger() as led, attached(trace):
        with span("step", i=0):
            serve_dense(x, c, act_bits=4, backend="mxu")
        with span("step", i=1):
            serve_dense(x, c, act_bits=4, backend="mxu")
    trace.add_ledger(led)

    payload = json.loads(json.dumps(trace.to_dict()))
    events = payload["traceEvents"]
    tracks = {e["args"]["name"]: e["tid"] for e in events
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert set(tracks) == {"server", "ppac"}
    xs = [e for e in events if e["ph"] == "X"]
    for tid in tracks.values():
        ts = [e["ts"] for e in xs if e["tid"] == tid]
        assert ts == sorted(ts) and ts[0] >= 0
    launches = [e for e in xs if e["tid"] == tracks["ppac"]]
    assert len(launches) == 2
    for e in launches:
        assert e["args"]["cycles"] > 0 and e["args"]["energy_nj"] > 0
        assert e["dur"] > 0
