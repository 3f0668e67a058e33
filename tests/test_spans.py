"""The span API (``obs.trace``): the span stack, the Chrome-trace sink,
compile and gc attribution, the spans a served tick writes into a
profiler capture, and the named scopes in the compiled serving steps."""
import dataclasses
import gc
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import load_arch
from repro.models import lm
from repro.obs import TraceBuilder, span
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import attached, current, install
from repro.serve.step import (
    convert_params_for_serving,
    make_decode_select_step,
    make_prefill_select_step,
)

SCOPES = ("proj/wqkv", "proj/wo", "proj/wig", "proj/down", "attn",
          "kv_write", "lm_head", "sample")


def serve_cfg():
    cfg = load_arch("smollm_360m").smoke()
    return dataclasses.replace(cfg, ppac=dataclasses.replace(
        cfg.ppac, enabled=True, weight_bits=4, act_bits=8, min_features=32,
        backend="auto"))


@pytest.fixture(scope="module")
def served():
    cfg = serve_cfg()
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    return cfg, convert_params_for_serving(params, cfg)


def test_span_stack_and_trace_builder_sink():
    tb = TraceBuilder()
    assert current() is None
    with span("unsunk"):
        assert current() == "unsunk"
    with attached(tb), span("outer", tick=1):
        assert current() == "outer"
        with span("inner", rids=[3, 4]):
            assert current() == "inner"
        assert current() == "outer"
    assert current() is None
    with span("after"):  # the builder is detached again
        pass
    xs = [e for e in tb.to_dict()["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in xs] == ["outer", "inner"]
    outer, inner = xs
    assert outer["args"] == {"tick": 1} and inner["args"] == {"rids": [3, 4]}
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3


def test_span_stack_unwinds_on_error():
    with pytest.raises(ValueError):
        with span("a"), span("b"):
            raise ValueError
    assert current() is None


def test_compile_counted_under_the_innermost_span():
    reg = MetricsRegistry()
    install(reg)
    # a constant of this process's own, so no compile cache can hold it
    k = float(os.getpid() * 1000 + time.time_ns() % 1000)
    f = jax.jit(lambda x: x * k + 1.0)
    x = jnp.arange(7.0).block_until_ready()
    n0 = reg.counter("lm_compiles", span="x").value
    s0 = reg.counter("lm_compile_s", span="x").value
    outer = reg.counter("lm_compiles", span="outer").value
    with span("outer"), span("x"):
        f(x).block_until_ready()
    assert reg.counter("lm_compiles", span="x").value == n0 + 1
    assert reg.counter("lm_compile_s", span="x").value > s0
    assert reg.counter("lm_compiles", span="outer").value == outer
    with span("x"):  # cached: no compile
        f(x).block_until_ready()
    assert reg.counter("lm_compiles", span="x").value == n0 + 1


def test_gc_pause_counted():
    reg = MetricsRegistry()
    install(reg)
    gc.collect()
    assert reg.counter("lm_gc_pause_s").value > 0


def test_server_metrics_carry_the_process_counters():
    """A server's own registry, the one ``--metrics-out`` and the
    Prometheus dump write, counts compiles by span and collections."""
    from repro.launch.serve_lm import LMServer
    cfg = load_arch("smollm_360m").smoke()
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    server = LMServer(cfg, params, slots=2, max_seq=32)
    k = float(os.getpid() * 1000 + time.time_ns() % 1000 + 0.5)
    x = jnp.arange(3.0).block_until_ready()
    with span("x"):
        jax.jit(lambda x: x * k)(x).block_until_ready()
    gc.collect()
    snap = server.metrics.snapshot()
    assert snap['lm_compiles{span="x"}'] == 1
    assert snap['lm_compile_s{span="x"}'] > 0
    assert snap["lm_gc_pause_s"] > 0
    assert 'lm_compiles{span="x"} 1' in server.metrics.prometheus_text()


def _program_spans(path):
    """The program's spans in a capture (the profiler's own Python
    events, on the same line, left out)."""
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if re.fullmatch(r"tick|sched\.\w+|decode[._]\w+|"
                                    r"prefill[._]\w+|write_slot\.\w+",
                                    ev.name):
                        out.append((ev.name, int(ev.start_ns),
                                    int(ev.start_ns + ev.duration_ns),
                                    dict(ev.stats)))
    return out


def test_profiled_ticks_nest_the_executor_spans(tmp_path):
    from repro.launch.serve_lm import LMServer, Request
    cfg = load_arch("smollm_360m").smoke()
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    server = LMServer(cfg, params, slots=2, max_seq=32)
    rng = np.random.default_rng(0)
    for i in range(3):
        server.submit(Request(i, rng.integers(0, cfg.vocab, 5), 4))
    server.tick()  # compiles outside the capture
    with jax.profiler.trace(str(tmp_path)):
        server.run()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    evs = _program_spans(path)

    def named(n):
        return [e for e in evs if e[0] == n]

    def inside(e, outer):
        return [o for o in outer if o[1] <= e[1] and e[2] <= o[2]]
    ticks, steps = named("tick"), named("decode_step")
    assert ticks and steps
    for st in steps:
        (tick,) = inside(st, ticks)
        assert st[3]["tick"] == tick[3]["tick"]
        kids = [e for e in evs if inside(e, [st]) and e is not st]
        assert sorted(k[0] for k in kids) == ["decode.dispatch",
                                              "decode.wait"]
    for name in ("decode.prepare", "decode.retire", "sched.admit"):
        assert all(len(inside(e, ticks)) == 1 for e in named(name))
    assert len(named("decode.prepare")) == len(steps)
    # the request admitted inside the capture: its prefill and the
    # executor's dispatch and wait under it, ids in the args
    (pb,) = named("prefill_batch")
    assert str(pb[3]["rids"]) == "2"
    assert sorted(e[0] for e in evs if inside(e, [pb]) and e is not pb
                  and e[0].startswith("prefill.")) == ["prefill.dispatch",
                                                       "prefill.wait"]


def _op_names(compiled) -> set:
    return set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))


def _scoped(names, scope):
    return any(f"/{scope}/" in f"/{n}/" for n in names)


def test_decode_step_carries_the_named_scopes(served):
    cfg, params = served
    cache, _ = lm.init_cache(cfg, 2, 32)
    step = make_decode_select_step(cfg, None, "serve")
    names = _op_names(step.lower(params, jnp.zeros((2, 1), jnp.int32),
                                 cache, jax.random.PRNGKey(0)).compile())
    missing = [s for s in SCOPES if not _scoped(names, s)]
    assert not missing, missing


def test_prefill_step_carries_the_named_scopes(served):
    cfg, params = served
    cache, _ = lm.init_cache(cfg, 2, 32)
    step = make_prefill_select_step(cfg, None, "serve")
    names = _op_names(step.lower(
        params, jnp.ones((2, 16), jnp.int32), jnp.full((2,), 16, jnp.int32),
        cache, jax.random.PRNGKey(0)).compile())
    missing = [s for s in SCOPES if not _scoped(names, s)]
    assert not missing, missing
