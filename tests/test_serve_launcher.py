"""Continuous-batching server: slot management, bucketed admission,
mid-flight result parity, EOS retirement."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import load_arch
from repro.launch.bucketed import bucket_for, drain_take
from repro.launch.serve import BatchServer, Request
from repro.launch.serve_lm import LMServer, build_lm_server
from repro.models import lm
from repro.serve.step import greedy_generate


def test_server_completes_all_requests():
    cfg = load_arch("smollm_360m").smoke()
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    server = BatchServer(cfg, params, slots=2, max_seq=64)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab, int(rng.integers(4, 12))),
                    max_new=5) for i in range(5)]
    for r in reqs:
        server.submit(r)
    done = server.run()
    assert len(done) == 5
    assert all(len(r.out) >= 5 for r in done)
    assert all(0 <= t < cfg.vocab for r in done for t in r.out)


def test_build_lm_server_smoke_preset_packed4():
    """The CLI's and the chip smoke's one construction path: the smoke
    preset by default, every packed projection resident as packed4."""
    server, report = build_lm_server("smollm_360m", serve_quant=True,
                                     weight_bits=4, slots=2, max_seq=64)
    assert server.cfg.d_model == load_arch("smollm_360m").smoke().d_model
    kinds = {c.kind for c in jax.tree.leaves(
        server.params, is_leaf=lambda x: hasattr(x, "kind"))
        if hasattr(c, "kind")}
    assert kinds == {"packed4"}, kinds
    assert report is not None and report.projections
    server.submit(Request(0, np.arange(1, 9, dtype=np.int32), max_new=3))
    done = server.run()
    assert len(done) == 1 and done[0].outcome == "completed"
    assert len(done[0].out) == 3


def test_server_single_request_matches_greedy():
    """One request through the batched server == greedy_generate."""
    cfg = dataclasses.replace(load_arch("smollm_360m").smoke(),
                              dtype="float32")
    params, _ = lm.init(cfg, jax.random.PRNGKey(1))
    prompt = np.arange(1, 9, dtype=np.int32)  # len 8 == bucket -> no padding

    server = BatchServer(cfg, params, slots=1, max_seq=64)
    server.submit(Request(0, prompt, max_new=6))
    done = server.run()

    ref = greedy_generate(params, cfg, {"tokens": prompt[None, :]},
                          steps=6, max_seq=64)
    assert done[0].out[:6] == list(np.asarray(ref)[0][:6])


@pytest.mark.parametrize("arch,kv,paged", [
    ("smollm_360m", "bfloat16", False),
    ("h2o_danube3_4b", "bfloat16", False),
    ("stablelm_12b", "int8", False),
    ("smollm_360m", "bfloat16", True),
    ("h2o_danube3_4b", "bfloat16", True),
    ("stablelm_12b", "int8", True),
])
def test_midflight_admission_bit_identical_to_solo(arch, kv, paged):
    """The acceptance property of per-sequence positions: requests
    admitted into free slots while other sequences keep decoding produce
    tokens bit-identical to generating each prompt alone — across linear,
    rolling (sliding-window) and int8-quantized caches, with ragged
    prompt lengths (right-padded bucketed prefill). The paged variants
    route every cache read/write through the block table and must stay
    bit-identical to the contiguous layout."""
    cfg = dataclasses.replace(load_arch(arch).smoke(), dtype="float32",
                              kv_dtype=kv)
    params, _ = lm.init(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (8, 5, 11, 8, 3)]

    solo = [list(np.asarray(greedy_generate(
        params, cfg, {"tokens": np.asarray(p)[None, :]}, steps=6,
        max_seq=64))[0]) for p in prompts]

    # 2 slots, 5 requests: requests 2..4 are necessarily admitted
    # mid-flight, into slots whose neighbors are mid-generation.
    kw = {"paged": True, "page_size": 8} if paged else {}
    server = LMServer(cfg, params, slots=2, max_seq=64, **kw)
    for i, p in enumerate(prompts):
        server.submit(Request(i, p, max_new=6))
    done = server.run()
    assert len(done) == len(prompts)
    assert server.admit_batches >= 2  # someone was admitted mid-flight
    for r in done:
        assert r.out[:6] == solo[r.rid], (r.rid, r.out[:6], solo[r.rid])


def test_eos_retirement_frees_slot_early():
    """A sequence hitting EOS retires immediately (finish_reason='eos');
    the freed slot is refilled from the queue."""
    cfg = load_arch("smollm_360m").smoke()
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    server = LMServer(cfg, params, slots=1, max_seq=64)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, 8)
    # probe the greedy continuation of THIS prompt; its second token is a
    # token the real run is guaranteed to emit -> usable as EOS.
    probe = LMServer(cfg, params, slots=1, max_seq=64)
    probe.submit(Request(0, prompt, max_new=4))
    eos = probe.run()[0].out[1]

    server.submit(Request(0, prompt, max_new=50, eos=int(eos)))
    server.submit(Request(1, rng.integers(0, cfg.vocab, 8), max_new=3))
    done = server.run()
    by_rid = {r.rid: r for r in done}
    assert by_rid[0].finish_reason == "eos"
    assert len(by_rid[0].out) < 50 and by_rid[0].out[-1] == eos
    assert by_rid[1].finish_reason == "length" and len(by_rid[1].out) == 3


def test_admission_uses_batch_buckets():
    """Admission drains waiting prompts in bucketed batches (shared
    drain policy), not one prefill per request."""
    cfg = load_arch("smollm_360m").smoke()
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    server = LMServer(cfg, params, slots=4, max_seq=64)
    rng = np.random.default_rng(0)
    for i in range(4):  # same length bucket -> one batched prefill
        server.submit(Request(i, rng.integers(0, cfg.vocab, 6), max_new=4))
    done = server.run()
    assert len(done) == 4
    assert server.admit_batches == 1


def test_bucket_policy_helpers():
    assert bucket_for(3, (1, 2, 4)) == 4
    # overflow is a caller bug (a batch that can't fit its bucket): the
    # old clamp silently truncated payload rows
    with pytest.raises(ValueError):
        bucket_for(9, (1, 2, 4))
    assert drain_take(7, (1, 4, 16)) == (4, 4)  # whole bucket, unpadded
    assert drain_take(3, (1, 4, 16)) == (3, 4)  # remainder, padded
    assert drain_take(1, (1, 4, 16)) == (1, 1)
    assert drain_take(9, (1, 2, 4)) == (4, 4)   # drain_take caps, no raise


def test_ssm_server_matches_solo_generation():
    """SSM archs must serve unpadded (state accumulation has no position
    mask): ragged prompts still come out bit-identical to solo runs."""
    cfg = dataclasses.replace(load_arch("mamba2_370m").smoke(),
                              dtype="float32")
    params, _ = lm.init(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (5, 7, 5)]
    solo = [list(np.asarray(greedy_generate(
        params, cfg, {"tokens": np.asarray(p)[None, :]}, steps=6,
        max_seq=64))[0]) for p in prompts]
    server = LMServer(cfg, params, slots=2, max_seq=64)
    assert not server.pad_prompts
    for i, p in enumerate(prompts):
        server.submit(Request(i, p, max_new=6))
    done = server.run()
    for r in done:
        assert r.out[:6] == solo[r.rid], (r.rid, r.out[:6], solo[r.rid])


def test_long_prompts_admissible_up_to_max_seq():
    """Prefill buckets derive from max_seq: prompts longer than the old
    fixed 64-token top bucket are servable."""
    cfg = load_arch("smollm_360m").smoke()
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    server = LMServer(cfg, params, slots=1, max_seq=160)
    assert server.prefill_buckets[-1] == 160
    rng = np.random.default_rng(0)
    server.submit(Request(0, rng.integers(0, cfg.vocab, 100), max_new=4))
    done = server.run()
    assert len(done) == 1 and len(done[0].out) == 4


def test_metrics_invariants_under_midflight_admission():
    """Telemetry conservation laws hold when requests are admitted into
    slots whose neighbors are mid-generation: every submitted request is
    admitted, timed, and retired exactly once; occupancy never exceeds
    the slot count; the token counter matches the decoded output."""
    cfg = load_arch("smollm_360m").smoke()
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    server = LMServer(cfg, params, slots=2, max_seq=64)
    rng = np.random.default_rng(3)
    n = 5  # 5 requests / 2 slots: 3 are necessarily admitted mid-flight
    for i in range(n):
        server.submit(Request(i, rng.integers(0, cfg.vocab,
                                              int(rng.integers(4, 12))),
                              max_new=5))
    done = server.run()
    assert len(done) == n and server.admit_batches >= 2

    snap = server.metrics.snapshot()
    assert snap["lm_requests_submitted"] == n
    assert snap["lm_requests_admitted"] == n
    assert snap["lm_requests_retired"] == n
    assert snap["lm_slots_evicted"] == n
    assert snap["lm_finish_length"] == n
    # every request timed exactly once, end to end
    for hist in ("lm_ttft_s", "lm_queue_wait_s", "lm_request_latency_s",
                 "lm_tpot_s"):
        assert snap[hist]["count"] == n, hist
        assert snap[hist]["min"] >= 0
    assert all(r.latency_s is not None and r.latency_s >= 0 for r in done)
    # TTFT (prefill included) can never beat pure queue wait
    assert snap["lm_ttft_s"]["sum"] >= snap["lm_queue_wait_s"]["sum"]
    # occupancy bounded by slots; its integral is the decoded tokens
    assert snap["lm_slot_occupancy"]["max"] <= server.slots
    decoded = sum(len(r.out) - 1 for r in done)  # first token <- prefill
    # the counter includes the prefill-emitted first tokens, so it
    # matches the tok/s numerator sum(len(r.out)); the occupancy
    # integral stays decode-only
    assert snap["lm_tokens_generated"] == decoded + n
    assert snap["lm_tokens_generated"] == sum(len(r.out) for r in done)
    assert snap["lm_slot_occupancy_per_step"]["sum"] == decoded
    assert snap["lm_decode_step_s"]["count"] == server.decode_steps
    assert snap["lm_prefill_batches"] == server.admit_batches


def test_sampling_server_stays_in_vocab():
    cfg = load_arch("smollm_360m").smoke()
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    server = LMServer(cfg, params, slots=2, max_seq=64, temperature=0.9,
                      top_k=12, seed=3)
    rng = np.random.default_rng(1)
    for i in range(3):
        server.submit(Request(i, rng.integers(0, cfg.vocab, 7), max_new=6))
    done = server.run()
    assert len(done) == 3
    assert all(0 <= t < cfg.vocab for r in done for t in r.out)
