"""CPU tests of the reduction from a profiler trace to per-layer metrics.

``testdata/trace-smollm-360m.decode-backlog.json`` is the first part of
the window of a traced chip run of that cell (TPU v5 lite), in the
normalized form ``trace_reduce.trim`` writes: the harness's host spans
and every device op event.
"""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import trace_reduce as tr  # noqa: E402
import work  # noqa: E402

RECORDED = os.path.join(HERE, "testdata",
                        "trace-smollm-360m.decode-backlog.json")


def smollm():
    conf = harness.load_json(HERE, "configs", "smollm-360m.json")
    return harness.model_numbers(harness.model_config(conf))


def recorded():
    with open(RECORDED) as f:
        return tr.Trace.from_json(json.load(f))


def test_union_and_containers():
    n, merged = tr.union_length([(0, 10), (5, 20), (30, 40), (35, 36)],
                                0, 38)
    assert n == 28 and merged == [[0, 20], [30, 38]]
    ops = [("while", 0, 100), ("a", 10, 5), ("b", 20, 5), ("c", 200, 5)]
    assert tr.containers(ops) == {0}


def synthetic():
    host = [("bench_window", 0, 1000), ("bench_decode", 100, 300),
            ("bench_prefill", 500, 300)]
    key = "k.1 = s32[32,1024] planes u32[4,1024,30]"
    ops = [("while", 110, 280), (key, 120, 100), ("fusion.3", 230, 50),
           (key, 520, 200), ("fusion.4", 760, 30)]
    modules = [("jit_step(1)", 105, 290), ("jit_step(2)", 505, 290)]
    return tr.Trace(host, ops, modules)


def test_busy_and_idle_gaps_are_labelled_by_host_span():
    red = tr.Reduction(synthetic(), smollm())
    assert red.window_s == pytest.approx(1e-6)
    assert red.busy_s == pytest.approx((280 + 200 + 30) * 1e-9)
    gaps = {}
    for name, s in red.idle_gaps():
        gaps[name] = gaps.get(name, 0) + s
    # each gap goes to the span holding its midpoint
    assert gaps == {"between ticks": pytest.approx((110 + 130 + 210) * 1e-9),
                    "bench_prefill": pytest.approx(40e-9)}
    ops = dict(red.device_ops())
    assert "while" not in ops and ops["k"] == pytest.approx(300e-9)


def test_launch_matches_the_projection_it_fits():
    red = tr.Reduction(synthetic(), smollm())
    ls = red.launches()
    assert [(x.kind, x.proj, x.rows, x.n, x.m) for x in ls] == \
        [("decode", "wo", 32, 960, 960), ("prefill", "wo", 32, 960, 960)]


def test_recorded_trace():
    m = smollm()
    red = tr.Reduction(recorded(), m)
    assert 0 < red.busy_s <= red.window_s
    decode = [c for c in red.calls if c[0] == "bench_decode"]
    ls = [x for x in red.launches() if x.kind == "decode"]
    assert decode and len(ls) == work.launches_per_step(m) * len(decode)
    # each decode step launches every projection once per layer
    for name, n, m_out in work.projections(m):
        got = [x for x in ls if x.proj == name]
        assert len(got) == m["n_layers"] * len(decode)
        assert all((x.n, x.m, x.rows) == (n, m_out, 32) for x in got)
    share = red.roofline("decode", work.peaks("TPU v5 lite"))
    assert 0 < share < 100
    out = red.breakdown()
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    assert out["device_ops"][0][1] > 0


def test_missing_launches_fail_loudly():
    trace = recorded()
    kept, dropped = [], False
    for o in trace.ops:
        if " planes " in o[0] and not dropped:
            dropped = True
            continue
        kept.append(o)
    red = tr.Reduction(tr.Trace(trace.host, kept, trace.modules), smollm())
    with pytest.raises(tr.TraceError, match="projection launches"):
        red.roofline("decode", work.peaks("TPU v5 lite"))


def test_no_call_of_a_kind_reads_nothing():
    trace = recorded()
    host = [h for h in trace.host if h[0] != "bench_prefill"]
    red = tr.Reduction(tr.Trace(host, trace.ops, trace.modules), smollm())
    assert red.roofline("prefill", work.peaks("TPU v5 lite")) is None
