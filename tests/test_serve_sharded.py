"""Sharded + disaggregated LMServer parity (subprocess, forced-host
devices): every multi-device layout must retire bit-identical greedy
tokens to the single-device server — across weight kinds (packed1 /
packed4 / int8), cache layouts (linear / ring / paged), mid-flight
admission, and a prefill->decode handoff mid-stream — and the sharded
entry points must keep the donation contract."""
import os
import subprocess
import sys
import textwrap

from conftest import cpu_subproc_env

_TESTS = os.path.dirname(os.path.abspath(__file__))


def _run(script: str) -> str:
    res = subprocess.run([sys.executable, "-c", script, _TESTS],
                         capture_output=True, text=True, timeout=600,
                         env=cpu_subproc_env())
    assert res.returncode == 0, res.stdout + res.stderr
    return res.stdout


_PRELUDE = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d"
    import dataclasses
    import jax
    import numpy as np
    from repro.configs import load_arch
    from repro.launch.mesh import make_serving_mesh
    from repro.launch.serve_lm import LMServer, Request
    from repro.models import lm
    from repro.serve.step import convert_params_for_serving

    def serve(cfg, params, prompts, max_new=6, slots=2, **kw):
        server = LMServer(cfg, params, slots=slots, max_seq=64, **kw)
        for i, p in enumerate(prompts):
            server.submit(Request(i, np.asarray(p, np.int32), max_new))
        done = server.run()
        assert len(done) == len(prompts)
        return {r.rid: r.out for r in done}, server
""")


SUBPROC_KINDS = _PRELUDE % 2 + textwrap.dedent("""
    # 2-dev pure TP ('model') across the PPAC weight kinds: packed1
    # (wb=1), packed4 bitplanes (wb=4), int8 (wb=8) — grouped wqkv/wig
    # containers and all. Greedy tokens must match bit-for-bit.
    rng = np.random.default_rng(3)
    for wb in (1, 4, 8):
        cfg = load_arch("smollm_360m").smoke()
        cfg = dataclasses.replace(
            cfg, dtype="float32",
            ppac=dataclasses.replace(cfg.ppac, enabled=True, weight_bits=wb,
                                     act_bits=8, min_features=32))
        params0, _ = lm.init(cfg, jax.random.PRNGKey(1))
        params = convert_params_for_serving(params0, cfg)
        prompts = [rng.integers(0, cfg.vocab, n) for n in (8, 5, 11)]
        ref, _ = serve(cfg, params, prompts, mode="serve")
        got, sv = serve(cfg, params, prompts, mode="serve",
                        mesh=make_serving_mesh((1, 2)))
        assert got == ref, (wb, got, ref)
        # the resident weights must actually be sharded, not replicated
        assert any(not l.sharding.is_fully_replicated
                   for l in jax.tree.leaves(sv.params)), wb
        print("KIND_OK", wb)
    print("KINDS_SHARDED_OK")
""")


def test_sharded_server_kinds_parity_2dev():
    out = _run(SUBPROC_KINDS)
    assert "KINDS_SHARDED_OK" in out, out


SUBPROC_LAYOUTS = _PRELUDE % 4 + textwrap.dedent("""
    # 2x2 mesh (slot-DP x TP) across cache layouts, with 5 requests into
    # 2 slots so admission necessarily happens mid-flight next to
    # decoding neighbors.
    rng = np.random.default_rng(5)
    for name, arch, kw in (("linear", "smollm_360m", {}),
                           ("ring", "h2o_danube3_4b", {}),
                           ("paged", "smollm_360m",
                            dict(paged=True, page_size=8))):
        cfg = dataclasses.replace(load_arch(arch).smoke(), dtype="float32")
        if name == "ring":
            assert cfg.sliding_window
        params, _ = lm.init(cfg, jax.random.PRNGKey(1))
        prompts = [rng.integers(0, cfg.vocab, n) for n in (8, 5, 11, 8, 3)]
        ref, rs = serve(cfg, params, prompts, **kw)
        got, sv = serve(cfg, params, prompts,
                        mesh=make_serving_mesh((2, 2)), **kw)
        assert got == ref, (name, got, ref)
        assert sv.admit_batches >= 2  # someone was admitted mid-flight
        print("LAYOUT_OK", name)
    print("LAYOUTS_SHARDED_OK")
""")


def test_sharded_server_cache_layouts_parity_4dev():
    out = _run(SUBPROC_LAYOUTS)
    assert "LAYOUTS_SHARDED_OK" in out, out


SUBPROC_DISAGG = _PRELUDE % 4 + textwrap.dedent("""
    # Disaggregated pools (2 prefill devices -> 2 decode devices): the
    # third request is submitted only after the first two are mid-decode,
    # so its prefill->decode handoff lands mid-stream into a live server.
    rng = np.random.default_rng(7)
    cfg = dataclasses.replace(load_arch("smollm_360m").smoke(),
                              dtype="float32")
    params, _ = lm.init(cfg, jax.random.PRNGKey(1))
    prompts = [rng.integers(0, cfg.vocab, n) for n in (8, 5, 11)]

    def staggered(**kw):
        server = LMServer(cfg, params, slots=2, max_seq=64, **kw)
        for i in (0, 1):
            server.submit(Request(i, np.asarray(prompts[i], np.int32), 8))
        server._admit()
        done = []
        for _ in range(3):
            done.extend(server.step())
        server.submit(Request(2, np.asarray(prompts[2], np.int32), 8))
        done.extend(server.run())
        assert len(done) == 3
        return {r.rid: r.out for r in done}, server

    for name, kw in (("contig", {}),
                     ("paged", dict(paged=True, page_size=8))):
        ref, _ = staggered(**kw)
        got, sv = staggered(prefill_devices=2, decode_devices=2, **kw)
        assert got == ref, (name, got, ref)
        snap = sv.metrics.snapshot()
        assert snap["lm_handoffs"] >= 2, snap.get("lm_handoffs")
        assert snap["lm_handoff_latency"]["count"] >= 2
        # per-worker attribution rode along with the handoff
        assert any("worker=" in k for k in snap), list(snap)
        print("DISAGG_OK", name, snap["lm_handoffs"])
    print("DISAGG_HANDOFF_OK")
""")


def test_disagg_handoff_midstream_4dev():
    out = _run(SUBPROC_DISAGG)
    assert "DISAGG_HANDOFF_OK" in out, out


SUBPROC_DONATE = _PRELUDE % 4 + textwrap.dedent("""
    # The PR 4-7 donation invariant must survive sharding. Sharded
    # lowerings drop tf.aliasing_output from the StableHLO text, so
    # assert on the compiled module header instead: every cache leaf
    # must STRICTLY alias its output (a may-alias pair). A leaf demoted
    # to buffer_donor means XLA inserted a device-local cache-sized copy
    # each step because the traced output sharding diverged from the
    # donated input's fitted placement.
    import re
    import jax.numpy as jnp

    for kw in ({}, dict(paged=True, page_size=8)):
        cfg = dataclasses.replace(load_arch("smollm_360m").smoke(),
                                  dtype="float32")
        params, _ = lm.init(cfg, jax.random.PRNGKey(0))
        server = LMServer(cfg, params, slots=2, max_seq=64,
                          mesh=make_serving_mesh((2, 2)), **kw)
        ex = server.ex
        toks = jnp.ones((2, 1), jnp.int32)
        with ex._ctx():
            low = ex._decode.lower(ex.params, toks, server.cache,
                                   jax.random.PRNGKey(0))
            txt = low.as_text()
            hdr = low.compile().as_text().splitlines()[0]
        n_leaves = len(jax.tree.leaves(server.cache))
        n_alias = len(re.findall(r"may-alias", hdr))
        assert n_alias >= n_leaves, (n_alias, n_leaves, hdr)
        assert "buffer_donor" not in hdr, hdr
        assert txt.count("sdy.sharding_constraint") >= 1, \
            "no sharding constraints?"
        print("DONATE_OK", bool(kw))
    print("SHARDED_DONATION_OK")
""")


def test_sharded_decode_hlo_donates_cache_4dev():
    out = _run(SUBPROC_DONATE)
    assert "SHARDED_DONATION_OK" in out, out
