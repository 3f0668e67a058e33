"""CPU tests of the output comparison that decides ``correct``.

At a size a test run can hold (the smollm-360m block at width 64, 2
layers, 4 slots; ``testdata/``) the same harness path as a chip run:
the program serves a window, then the plain reference compares the
served tokens of sampled finished requests and of requests admitted
inside the window, and ``control.py`` puts the reference at 4-bit
activations in the program's place and sends its tokens through the
same comparison.

Readings at this size (CPU, 2 s windows, 8 requests compared, seeds 1,
2, 3, 2**31 + 5): the program's widest gap 0.0070-0.0218, the
control's (the reference at 4-bit activations) 0.219-0.419. ``LIMIT``
sits between them: 3.7 times the highest program reading, 2.7 times
below the lowest control reading.
"""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import control  # noqa: E402
import harness  # noqa: E402

LIMIT = 0.08
SEEDS = (1, 2, 2**31 + 5)


def smoke_cell():
    return {"name": "smoke", "config": "smoke",
            "traffic": "testdata/smoke-backlog", "chips": 1,
            "config_entry": {"file": "benchmarks/chip/testdata/"
                                     "smoke-config.json"},
            "bench": harness.benchmark(),
            "limits": {"max_logit_gap": LIMIT}}


def run(hook=None, seed=7, keep=None):
    return harness.execute(smoke_cell(), seed, 1.5, False,
                           t_start=time.perf_counter(), server_hook=hook,
                           keep=keep)


@pytest.mark.parametrize("seed", SEEDS)
def test_program_passes_and_control_fails(seed, tmp_path):
    keep = tmp_path / "kept.json"
    out = run(seed=seed, keep=str(keep))
    assert out["correct"] is True
    with open(keep) as f:
        kept = json.load(f)
    r = control.control(smoke_cell(), kept, ctrl_bits=4)
    assert r["tokens"] >= 100
    # the control goes through the run's own comparison and fails it
    assert r["program_correct"] is True and r["program"] == out["checks"]
    assert r["control_correct"] is False
    assert r["control"]["max_logit_gap"]["value"] > LIMIT


def test_sound_run_is_correct():
    out = run()
    assert out["correct"] is True
    assert list(out)[-1] == "checks"
    for k in ("max_logit_gap", "window_logit_gap"):
        assert out["checks"][k]["limit"] == LIMIT
        assert 0 <= out["checks"][k]["value"] <= LIMIT


def test_nothing_compared_is_not_correct():
    rec = {"groups": ["finished"], "gap": 0.0, "short": False}
    checks, ok = harness.judge([rec], {"max_logit_gap": LIMIT})
    assert checks["window_logit_gap"]["value"] is None and ok is False


def state_unchanged(server):
    """The decode step computes its tokens but hands back the cache it
    was given: no new KV row, no position advanced."""
    ex = server.ex
    inner = ex.decode

    def decode(toks, cache, key):
        keep = jax.tree.map(jnp.copy, cache)
        nxt, _ = inner(toks, cache, key)
        return nxt, keep
    ex.decode = decode


def half_batch(server):
    """Only the first half of the slots is decoded; the rest repeat
    their input token."""
    ex = server.ex
    inner = ex.decode

    def decode(toks, cache, key):
        nxt, cache = inner(toks, cache, key)
        nxt = np.array(nxt)
        half = len(nxt) // 2
        nxt[half:] = np.asarray(toks)[half:, 0]
        return nxt, cache
    ex.decode = decode


def altered_token(server):
    """One token per step is changed where the decode step produces it."""
    ex = server.ex
    inner = ex.decode
    step = [0]

    def decode(toks, cache, key):
        nxt, cache = inner(toks, cache, key)
        nxt = np.array(nxt)
        s = step[0] % len(nxt)
        nxt[s] = (nxt[s] + 1) % server.cfg.vocab
        step[0] += 1
        return nxt, cache
    ex.decode = decode


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   altered_token])
def test_fault_under_the_timed_path_is_not_correct(fault):
    out = run(fault)
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap"]["value"] > LIMIT


def test_fault_in_window_admissions_is_not_correct(monkeypatch):
    """A prefill that alters its first token, only for the requests
    admitted once the window has opened: the comparison of the window's
    admissions sees it."""
    window = [False]
    measure = harness.Run.measure

    def opened(self, *a, **k):
        window[0] = True
        return measure(self, *a, **k)
    monkeypatch.setattr(harness.Run, "measure", opened)

    def hook(server):
        ex = server.ex
        inner = ex.prefill

        def prefill(toks, lens, key):
            tok0, handle = inner(toks, lens, key)
            if window[0]:
                tok0 = (np.array(tok0) + 1) % server.cfg.vocab
            return tok0, handle
        ex.prefill = prefill
    out = run(hook)
    assert out["correct"] is False
    assert out["checks"]["window_logit_gap"]["value"] > LIMIT


def test_open_loop_run_serves_every_due_request():
    cell = dict(smoke_cell(), traffic="testdata/smoke-chat")
    run = harness.Run(cell, 11, t_start=time.perf_counter())
    run.build()
    run.warm_up()
    run.fill(1.0)
    win = run.measure(1.0)
    m = run.end_to_end(win)
    assert m["n_due"] > 10 and m["n_missing"] == 0
    assert 0 < m["ttft_p95_s"] < win.drain_end - win.t0
    assert win.lateness["n"] == len(run._sent) > m["n_due"]
    # the window is the schedule's: every arrival due in it was submitted
    due = [it for it in run.traffic.schedule(1.0) if 0 <= it.due_s < 1.0]
    assert win.t0 == run._t_ref and m["n_due"] == len(due)
    assert run.backlog_at(win.drain_end) == 0


def test_backlog_counts_due_requests_without_a_first_token():
    item = harness.loadgen.Item
    tr = harness.tails.Track
    fake = type("R", (), {})()
    fake._t_ref = 100.0
    fake._pending = [item(5, None, 1, due_s=3.0), item(6, None, 1, 9.0)]
    fake.tracks = {0: tr(0, due=100.5, tokens=[(101.0, 0)]),
                   1: tr(1, due=101.5, tokens=[(103.5, 0)]),
                   2: tr(2, due=102.0),
                   3: tr(3)}                  # closed loop: never due
    at = harness.Run.backlog_at
    # at 102.5: 1 and 2 wait; at 103.2 the pending item 5 is due too
    assert at(fake, 102.5) == 2 and at(fake, 103.2) == 3
    # at 104: 1 has its first token; 2 and 5 still wait
    assert at(fake, 104.0) == 2


def test_sequence_past_the_models_context_is_refused(tmp_path):
    conf = harness.load_json(HERE, "testdata", "smoke-config.json")
    conf["max_position_embeddings"] = 64          # the traffic's max_seq: 96
    path = tmp_path / "config.json"
    path.write_text(json.dumps(conf))
    cell = dict(smoke_cell(), config_entry={"file": str(path)})
    with pytest.raises(harness.BenchError, match="past the model's context"):
        harness.cell_model(cell)
