"""CPU tests of the benchmark's traffic generator and metric arithmetic."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import loadgen  # noqa: E402
import tails  # noqa: E402


def spec(name):
    return loadgen.load_traffic(name)


@pytest.mark.parametrize("mix", ["decode-backlog", "chat-poisson"])
def test_every_seed_serves_the_same_lengths(mix):
    s = spec(mix)
    a = loadgen.Traffic(s, 1, 1000)
    b = loadgen.Traffic(s, 2**31 + 9, 1000)
    # the same lengths in the same order; only the tokens differ
    assert list(a.prompt_lens) == list(b.prompt_lens)
    assert list(a.output_lens) == list(b.output_lens)
    ia, ib = next(a.backlog(0)), next(b.backlog(0))
    assert len(ia.prompt) == len(ib.prompt)
    assert ia.prompt.tolist() != ib.prompt.tolist()
    for key in ("prompt", "output"):
        lens = a.prompt_lens if key == "prompt" else a.output_lens
        assert lens.min() >= s[key]["min"] and lens.max() <= s[key]["max"]
        # the median of the stratified quantiles is the stated median
        assert abs(np.median(lens) - s[key]["median"]) <= 1


def test_same_seed_same_inputs():
    s = spec("decode-backlog")
    a = loadgen.Traffic(s, 5, 1000).starting_occupants()
    b = loadgen.Traffic(s, 5, 1000).starting_occupants()
    assert [(i.max_new, i.prompt.tolist()) for i in a] == \
        [(i.max_new, i.prompt.tolist()) for i in b]


def test_starting_residuals_are_seed_free():
    s = spec("decode-backlog")
    r1 = sorted(i.max_new for i in loadgen.Traffic(s, 3, 100)
                .starting_occupants())
    r2 = sorted(i.max_new for i in loadgen.Traffic(s, 4, 100)
                .starting_occupants())
    assert r1 == r2 and len(r1) == s["slots"]
    # remaining lengths are fractions of drawn lengths
    assert min(r1) >= 1 and max(r1) <= s["output"]["max"]


@pytest.mark.parametrize("mix", ["decode-backlog", "chat-poisson"])
def test_prompt_and_output_fit_the_cache_rows(mix):
    s = spec(mix)
    t = loadgen.Traffic(s, 2**31 + 3, 1000)
    items = t.starting_occupants() if s["loop"] == "closed_backlog" else []
    gen = t.backlog(0)
    items += [next(gen) for _ in range(s["pool"])]
    assert all(len(i.prompt) + i.max_new <= s["max_seq"] for i in items)
    assert max(len(i.prompt) + i.max_new for i in items) == s["max_seq"] \
        or s["prompt"]["max"] + s["output"]["max"] <= s["max_seq"]


def test_poisson_schedule_spans_lead_in_and_window():
    s = dict(spec("chat-poisson"), rate_rps=2.0, lead_in_s=5.0)
    items = loadgen.Traffic(s, 7, 100).schedule(20.0)
    due = np.array([i.due_s for i in items])
    assert len(items) == round(2.0 * 25.0)
    assert due[0] == pytest.approx(-5.0) and np.all(np.diff(due) > 0)
    assert due[-1] < 20.0
    # another seed: the same arrivals
    other = loadgen.Traffic(s, 8, 100).schedule(20.0)
    assert [i.due_s for i in other] == [i.due_s for i in items]
    # a Poisson process: gaps spread like an exponential's
    gaps = np.diff(due)
    assert gaps.std() == pytest.approx(gaps.mean(), rel=0.2)


def test_lateness_report():
    rep = loadgen.lateness_report([0.0, 1.0, 2.0], [0.0, 1.5, 2.1])
    assert rep["n"] == 3 and rep["max_s"] == pytest.approx(0.5)


def track(rid, times, prefills=None, due=None, outcome="completed"):
    tr = tails.Track(rid, due=due, outcome=outcome)
    for i, t in enumerate(times):
        tr.add(1, t, (prefills or [0] * len(times))[i])
    return tr


def test_percentile_is_nearest_rank_over_raw_values():
    vals = list(range(1, 101))
    assert tails.percentile(vals, 95) == 95
    assert tails.percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        tails.percentile([], 95)


def test_rate_is_all_tokens_over_the_whole_window():
    trs = [track(0, [0.5, 1.0, 1.5, 2.5]), track(1, [1.2, 3.9, 4.5])]
    m = tails.window_metrics(trs, 1.0, 4.0)
    # tokens at 1.0, 1.5, 2.5, 1.2, 3.9 lie in [1, 4]: 5 over 3 s
    assert m["output_tok_s"] == pytest.approx(5 / 3.0)


def test_gap_straddling_the_window_start_is_left_out():
    trs = [track(0, [0.5, 1.5, 2.0, 3.5])]
    gaps = tails.gaps_in(trs, 1.0, 3.0)
    assert [g for g, _ in gaps] == [pytest.approx(0.5)]
    # a gap whose later token falls after the close is out too
    m = tails.window_metrics(trs, 1.0, 3.0)
    assert m["itl_p95_ms"] == pytest.approx(500.0)


def test_stall_share_counts_gaps_with_a_prefill():
    trs = [track(0, [1.0, 2.0, 3.0, 4.0], prefills=[0, 0, 1, 1]),
           track(1, [1.0, 2.0, 3.0, 4.0], prefills=[0, 0, 1, 1])]
    m = tails.window_metrics(trs, 0.0, 5.0)
    assert m["prefill_stall_share"] == pytest.approx(100.0 / 3)


def test_tail_is_over_all_requests_and_missing_ones_count():
    # 19 requests served 1 s after they were due, one never served
    trs = [track(i, [i + 1.0], due=float(i)) for i in range(19)]
    trs.append(track(19, [], due=5.0, outcome="failed"))
    vals, missing = tails.ttfts(trs, 0.0, 30.0, drain_end=65.0)
    assert missing == 1 and len(vals) == 20
    m = tails.window_metrics(trs, 0.0, 30.0, drain_end=65.0)
    assert m["n_missing"] == 1
    # nearest rank 19 of 20 is a served request; the missing one is the max
    assert m["ttft_p95_s"] == pytest.approx(1.0)
    trs.append(track(20, [], due=6.0, outcome=None))  # unserved by the drain
    m = tails.window_metrics(trs, 0.0, 30.0, drain_end=65.0)
    assert m["n_missing"] == 2 and m["ttft_p95_s"] == pytest.approx(59.0)


def test_requests_due_outside_the_window_are_not_in_the_tail():
    trs = [track(0, [2.0], due=-1.0), track(1, [12.0], due=11.0),
           track(2, [3.0], due=2.0)]
    vals, _ = tails.ttfts(trs, 0.0, 10.0, drain_end=20.0)
    assert vals == [pytest.approx(1.0)]


def _declared(kind):
    import json
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return [m["name"] for m in json.load(f)[kind]]


@pytest.mark.parametrize("name", _declared("end_to_end"))
def test_every_end_to_end_metric_is_taken_from_token_times(name):
    # a backlog window (no drain): set-up is timed by the harness itself
    trs = [track(0, [1.0, 1.4, 1.8, 3.0], prefills=[0, 0, 0, 1]),
           track(1, [1.1, 1.5, 1.9, 3.1], prefills=[0, 0, 0, 1])]
    m = tails.window_metrics(trs, 0.5, 3.5)
    assert name == "setup_s" or m[name] > 0


@pytest.mark.parametrize("name", _declared("per_layer"))
def test_every_per_layer_metric_has_a_reader(name):
    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(here, "metrics", f"{name}.py")
    with open(path) as f:
        assert "def read(ctx)" in f.read()
