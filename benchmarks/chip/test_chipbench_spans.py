"""CPU tests of the program-span metrics (``spans.py`` and its three
readers).

``testdata/trace-smollm-360m.decode-backlog.spans.json`` is the first
part of the window of a traced chip run of that cell (TPU v5 lite) with
the program's spans and op scopes, as ``spans.trim`` writes it;
``trace-smollm-360m.decode-backlog.json`` is an older run's, of a
program without them.
"""
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import spans  # noqa: E402
import trace_reduce as tr  # noqa: E402

DATA = os.path.join(HERE, "testdata")
READERS = ("host_self_ms.scheduler", "device_idle_share.scheduler",
           "kv_write_share.decode")


def recorded(name: str) -> spans.Spans:
    with open(os.path.join(DATA, name)) as f:
        return spans.Spans.from_json(json.load(f))


def synthetic() -> spans.Spans:
    """Two ticks on one clock (offset 0): a decode tick whose device
    program runs inside ``decode_step`` and leaves gaps in ``decode.prepare``
    and ``decode.retire``, and an admission tick with a prefill."""
    host = [("bench_window", 0, 1000)]
    sp = [("tick", 100, 400), ("sched.admit", 100, 20),
          ("decode.prepare", 120, 30), ("decode_step", 150, 300),
          ("decode.dispatch", 150, 20), ("decode.wait", 440, 10),
          ("decode.retire", 450, 50),
          ("tick", 600, 300), ("sched.admit", 600, 250),
          ("prefill_batch", 610, 200), ("prefill.dispatch", 610, 10),
          ("prefill.wait", 620, 190), ("host.gc", 830, 10)]
    ops = [("while", 160, 200), ("fusion.1", 160, 100),
           ("scatter.2", 260, 50), ("copy.3", 310, 50),
           ("custom-call.4", 620, 180)]
    scopes = ["jit(step)/while", "jit(step)/while/body/proj/wqkv/dot",
              "jit(step)/while/body/kv_write/scatter",
              "jit(step)/while/body/attn/copy", "jit(step)/proj/wo/cc"]
    modules = [("jit_step(1)", 155, 210), ("jit_step(2)", 615, 190)]
    return spans.Spans(tr.Trace(host, ops, modules), sorted(
        sp, key=lambda x: x[1]), scopes)


def test_synthetic_window():
    w = spans.Window(synthetic())
    assert w.offset == 0
    # tick self time: 400 - 300 and 300 - 200 ns
    assert w.host_self_ms() == pytest.approx(1e-6 * (100 + 100) / 2)
    gaps = w.idle()
    assert gaps == [(0, 160), (360, 620), (800, 1000)]
    # each part of a gap goes to the innermost span open over it
    by = w.idle_by_span()
    assert by == pytest.approx({
        "outside_tick": 300e-9, "decode_step": 80e-9, "sched.admit": 60e-9,
        "tick": 50e-9, "decode.retire": 50e-9, "decode.prepare": 30e-9,
        "decode.dispatch": 10e-9, "decode.wait": 10e-9,
        "prefill.dispatch": 10e-9, "prefill.wait": 10e-9, "host.gc": 10e-9})
    assert sum(by.values()) == pytest.approx(620e-9)
    # idle inside a tick, outside its calls: [100, 150), [450, 500),
    # [600, 610) and [810, 900)
    assert w.scheduler_idle_share() == pytest.approx(
        100.0 * (50 + 50 + 10 + 90) / 1000)
    assert w.programs("decode_step") == [(155, 365)]
    # kv_write ops over the decode program's op time (the while left out)
    assert w.scope_share("kv_write", "decode_step") == pytest.approx(
        100.0 * 50 / 200)
    # the prefill program carries named scopes, none of them kv_write
    assert w.scope_share("kv_write", "prefill_batch") == 0.0
    assert w.scope_share("kv_write", "spec_round") is None
    assert w.dispatches("decode_step", "decode.dispatch") == [1]


def test_idle_is_labelled_by_the_innermost_span():
    w = spans.Window(synthetic())
    assert w.innermost(50) == "outside_tick"
    assert w.innermost(125) == "decode.prepare"
    assert w.innermost(445) == "decode.wait"
    assert w.innermost(835) == "host.gc"
    assert w.innermost(870) == "tick"
    assert w.innermost(950) == "outside_tick"
    # decode.dispatch starts with decode_step: the shorter is inside
    assert w.innermost(155) == "decode.dispatch"


def test_scopes_without_kv_write_read_zero():
    sp = synthetic()
    sp.scopes[2] = "jit(step)/while/body/attn/scatter"
    assert spans.Window(sp).scope_share("kv_write", "decode_step") == 0.0
    sp.scopes = ["jit(step)/while/body/x"] * len(sp.scopes)
    assert spans.Window(sp).scope_share("kv_write", "decode_step") is None


def test_json_round_trip_and_plain_trace_view(tmp_path):
    sp = synthetic()
    path = tmp_path / "t.json"
    path.write_text(json.dumps(sp.to_json()))
    d = json.loads(path.read_text())
    back = spans.Spans.from_json(d)
    assert back.spans == sp.spans and back.scopes == sp.scopes
    assert tr.Trace.from_json(d).ops == sp.trace.ops


def test_a_program_without_spans_reads_none():
    old = recorded("trace-smollm-360m.decode-backlog.json")
    assert not old.spans and set(old.scopes) == {""}
    w = spans.Window(old)
    assert w.host_self_ms() is None
    assert w.scheduler_idle_share() is None
    assert w.scope_share("kv_write", "decode_step") is None


def fake_context(sp: spans.Spans):
    win = [h for h in sp.trace.host if h[0] == "bench_window"][0]
    return types.SimpleNamespace(red=types.SimpleNamespace(
        lo=win[1], hi=win[1] + win[2]))


@pytest.mark.parametrize("name", ["trace-smollm-360m.decode-backlog.json",
                                  "trace-smollm-360m.decode-backlog"
                                  ".spans.json"])
def test_readers_find_the_window_trace(name, tmp_path, monkeypatch):
    """The readers find the harness's trace directory under the temporary
    directory, keep only the one whose window is the context's, and read
    None from a program without spans."""
    sp = recorded(name)
    for d in ("chipbench-trace-other", "chipbench-trace-this"):
        (tmp_path / d).mkdir()
    monkeypatch.setattr(spans.tempfile, "tempdir", str(tmp_path))
    other = spans.Spans(tr.Trace([("bench_window", 1, 2)], [], []), [], [])
    monkeypatch.setattr(spans, "load", lambda d: sp if d.endswith("this")
                        else other)
    monkeypatch.setattr(spans, "_WINDOWS", {})
    ctx = fake_context(sp)
    vals = {r: harness.load_reader(r)(ctx) for r in READERS}
    if not sp.spans:
        assert vals == {r: None for r in READERS}
    else:
        assert all(isinstance(v, float) and v > 0 for v in vals.values())


def test_recorded_window():
    sp = recorded("trace-smollm-360m.decode-backlog.spans.json")
    w = spans.Window(sp)
    red = tr.Reduction(sp.trace)
    # the program's decode spans hold the harness's, so the offsets agree
    assert abs(w.offset - red.offset) < 2_000_000
    steps = w.dispatches("decode_step", "decode.dispatch")
    assert steps and all(n == 1 for n in steps)
    by = w.idle_by_span()
    idle_s = (w.hi - w.lo) * 1e-9 - red.busy_s
    assert sum(by.values()) == pytest.approx(idle_s, rel=1e-6)
    assert 0 < w.scheduler_idle_share() < 100 * red.idle_share
    assert 0 < w.host_self_ms() < 50
    share = w.scope_share("kv_write", "decode_step")
    assert 0 < share < 100
    scoped = {s for sc in sp.scopes for s in spans.SCOPES
              if f"/{s}/" in f"/{sc}/"}
    assert scoped >= {"kv_write", "attn", "proj/wqkv", "proj/wo",
                      "proj/wig", "proj/down", "lm_head"}


def test_readers_read_a_capture_of_the_program(tmp_path, monkeypatch):
    """A CPU capture of the server inside a ``bench_window``, in a
    ``chipbench-trace-*`` directory as the harness makes it: the program's
    spans are read (the CPU trace has no device ops, so no scope)."""
    import jax
    import numpy as np
    from repro.configs.base import load_arch
    from repro.launch.serve_lm import LMServer, Request
    from repro.models import lm
    cfg = load_arch("smollm_360m").smoke()
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    server = LMServer(cfg, params, slots=2, max_seq=32)
    rng = np.random.default_rng(0)
    for i in range(3):
        server.submit(Request(i, rng.integers(0, cfg.vocab, 5), 4))
    server.tick()
    tdir = tmp_path / "chipbench-trace-x"
    with jax.profiler.trace(str(tdir)):
        with jax.profiler.TraceAnnotation("bench_window"):
            server.run()
    sp = spans.load(str(tdir))
    names = {x[0] for x in sp.spans}
    assert {"tick", "sched.admit", "decode.prepare", "decode_step",
            "decode.dispatch", "decode.wait", "decode.retire",
            "prefill_batch", "prefill.dispatch", "prefill.wait"} <= names
    monkeypatch.setattr(spans.tempfile, "tempdir", str(tmp_path))
    monkeypatch.setattr(spans, "_WINDOWS", {})
    ctx = fake_context(sp)
    assert harness.load_reader("host_self_ms.scheduler")(ctx) > 0
    assert harness.load_reader("kv_write_share.decode")(ctx) is None
    w = spans.for_context(ctx)
    assert all(n == 1 for n in w.dispatches("decode_step", "decode.dispatch"))
