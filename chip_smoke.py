#!/usr/bin/env python3
"""Smoke run of the PPAC serving system on a TPU.

    python chip_smoke.py                 # one chip: serve, parity, cam, coding
    python chip_smoke.py --four-chips    # four chips: 2x2 mesh and 2+2
                                         # disaggregated serving vs 1x1

One chip, four phases, all in this one process (a chip belongs to one
process at a time):

  serve   smollm-360m at its published widths (random weights from
          ``--seed``), every projection resident as packed 4-bit planes,
          serves 16 requests with prompts of up to 256 tokens through
          ``LMServer`` on the Pallas bit-serial kernels; an ``obs`` ledger
          must show every packed launch on 'pallas' and no int8 fallback.
          The same requests run twice: the warm pass must repeat the
          cold pass's tokens.
  parity  the same server on the 'ref' (plain jnp) kernel backend: every
          greedy token stream must equal the Pallas one.
  cam     the retrieval server at 65536 x 256-bit codes, top-4 lookups of
          planted rows (recall@1 >= 0.99) on the Hamming top-k kernel.
  coding  the LDPC decode server (32x32 array code), one error per word,
          every message recovered, on the GF(2) kernels.

``--four-chips`` runs only the multi-chip path: the same full-width
server on a 2x2 (data x model) mesh and disaggregated over 2 prefill + 2
decode chips, each compared with 1x1 token streams made in this process
on one of the four chips. It needs four attached chips.

Each phase prints its backend compile seconds (the ``obs`` compile
counter), requests completed and token parity; tokens/s lines are smoke
readings on the host clock, not a benchmark. The last line of standard
output is one JSON object, ``{"ok": true, "device": {...}}``, printed only
when every phase passed. Without a TPU, or outside a checkout of this
repository, the script exits nonzero and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "smollm_360m"
REQUESTS = 16
SLOTS = 8
PROMPT_BUCKET = 256      # one prefill length bucket: prompts 16..256 tokens
MAX_NEW = (8, 24)        # per-request new tokens, drawn in this range
ADMIT_BATCH = 4          # one prefill batch shape: 4 prompts
CAM_M, CAM_BITS, CAM_K = 65536, 256, 4   # the retrieval server's README run


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def compile_clock():
    """A function returning the backend compile seconds since its last
    call, read from the ``obs`` compile counter (``lm_compile_s``, summed
    over the spans it is labelled by)."""
    from repro.obs.metrics import MetricsRegistry
    from repro.obs.trace import install
    reg = MetricsRegistry()
    install(reg)
    last = [0.0]

    def lap() -> float:
        now = reg.total("lm_compile_s")
        s, last[0] = now - last[0], now
        return s
    return lap


def make_requests(vocab: int, seed: int):
    import numpy as np
    from repro.launch.serve_lm import Request
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, vocab,
                                    int(rng.integers(16, PROMPT_BUCKET + 1))),
                    int(rng.integers(*MAX_NEW)))
            for i in range(REQUESTS)]


def serve(requests, *, backend: str, passes: int = 1, **server_kw):
    """Serve fresh copies of ``requests`` ``passes`` times on one server.

    Returns ({rid: tokens} of the last pass, [(seconds, tokens) per pass],
    ledger of every PPAC launch). Fails on any request that does not
    complete."""
    from repro.launch.serve_lm import Request, build_lm_server
    from repro.obs.ledger import Ledger

    server, _ = build_lm_server(
        ARCH, full=True, serve_quant=True, weight_bits=4, backend=backend,
        slots=SLOTS, max_seq=PROMPT_BUCKET + MAX_NEW[1],
        prefill_buckets=(PROMPT_BUCKET,), admit_buckets=(ADMIT_BATCH,),
        **server_kw)
    runs, outs = [], {}
    with Ledger() as ledger:
        for _ in range(passes):
            batch = [Request(r.rid, r.prompt, r.max_new) for r in requests]
            for r in batch:
                server.submit(r)
            t0 = time.perf_counter()
            done = server.run()
            dt = time.perf_counter() - t0
            check(not server.terminal and len(done) == len(batch)
                  and all(r.outcome == "completed" for r in done),
                  f"{backend}: {len(done)}/{len(batch)} completed, "
                  f"{len(server.terminal)} shed or failed")
            pass_outs = {r.rid: list(r.out) for r in done}
            check(not outs or pass_outs == outs,
                  f"{backend}: a rerun of the same requests changed tokens")
            outs = pass_outs
            runs.append((dt, sum(len(t) for t in outs.values())))
    del server
    gc.collect()
    return outs, runs, ledger


def diff(a: dict, b: dict) -> dict:
    """{rid: index of the first differing token} where streams differ."""
    out = {}
    for rid, toks in sorted(a.items()):
        other = b.get(rid, [])
        if toks != other:
            out[rid] = next((i for i, (x, y) in enumerate(zip(toks, other))
                             if x != y), min(len(toks), len(other)))
    return out


def phase_serve(clock, requests):
    from repro.core.backend import auto_interpret, resolve_backend
    check(resolve_backend("auto") == "pallas" and not auto_interpret(),
          "kernels do not resolve to compiled Pallas on this device")
    outs, runs, ledger = serve(requests, backend="auto", passes=2)
    backends = sorted({r.backend for r in ledger.records})
    fallback = sum(r.mode == "mvp_int8_mxu" for r in ledger.records)
    check(ledger.records and backends == ["pallas"],
          f"packed projection launches on {backends}, want only pallas")
    check(fallback == 0, f"{fallback} int8 MXU fallback launches")
    (cold_s, toks), (warm_s, _) = runs
    print(f"[serve] {ARCH} full widths, packed4 "
          f"on pallas: "
          f"{len(outs)}/{len(requests)} requests completed, {toks} tokens; "
          f"compile {clock():.1f}s; {len(ledger.records)} packed launches "
          f"traced, all on pallas, 0 int8 fallback; warm rerun tokens "
          f"identical; smoke reading (host clock, not a benchmark): "
          f"cold {toks / cold_s:.1f} tok/s, warm {toks / warm_s:.1f} tok/s",
          flush=True)
    return outs


def phase_parity(clock, requests, pallas_outs):
    outs, runs, ledger = serve(requests, backend="ref")
    check({r.backend for r in ledger.records} == {"ref"},
          "the parity server did not run on the ref backend")
    bad = diff(pallas_outs, outs)
    check(not bad, f"greedy tokens differ between pallas and ref "
                   f"({{request: first differing token}}): {bad}")
    print(f"[parity] ref backend: {len(outs)}/{len(requests)} requests "
          f"completed; greedy tokens identical to pallas for all "
          f"{len(outs)} requests; compile {clock():.1f}s", flush=True)


def phase_cam(clock):
    from repro.launch.retrieval import serve_planted_lookups
    from repro.obs.ledger import Ledger
    with Ledger() as ledger:
        res = serve_planted_lookups(CAM_M, CAM_BITS, requests=256, k=CAM_K)
    check({r.backend for r in ledger.records} == {"pallas"},
          "CAM lookups did not run on the pallas kernels")
    print(f"[cam] {CAM_M} x {CAM_BITS}-bit index, top-{CAM_K}: "
          f"{res['served']} lookups, "
          f"recall@1 {res['recall_at_1']:.3f} on pallas; compile "
          f"{clock():.1f}s", flush=True)


def phase_coding(clock):
    from repro.launch.coding import serve_noisy_words
    res = serve_noisy_words(32, 32, requests=256, errors=1)
    check(res["backend"] == "pallas",
          f"LDPC decodes ran on {res['backend']!r}, not the pallas kernels")
    print(f"[coding] 32x32 array code, 1 error/word: {res['served']} words, "
          f"{res['recovered']} recovered on pallas; compile "
          f"{clock():.1f}s", flush=True)


def phase_four_chips(clock, requests):
    import jax
    from repro.launch.mesh import make_serving_mesh
    check(len(jax.devices()) >= 4,
          f"--four-chips needs 4 chips, {len(jax.devices())} attached")
    base, _, _ = serve(requests, backend="auto")
    print(f"[1x1] one of four chips: {len(base)}/{len(requests)} requests "
          f"completed; compile {clock():.1f}s", flush=True)
    layouts = {"2x2 mesh": dict(mesh=make_serving_mesh((2, 2))),
               "2+2 disaggregated": dict(prefill_devices=2,
                                         decode_devices=2)}
    differ = []
    for name, kw in layouts.items():
        outs, runs, _ = serve(requests, backend="auto", **kw)
        bad = diff(base, outs)
        dt, toks = runs[0]
        parity = (f"greedy tokens identical to 1x1 for all {len(outs)}"
                  if not bad else f"tokens differ from 1x1 ({{request: "
                                  f"first differing token}}): {bad}")
        print(f"[{name}] {len(outs)}/{len(requests)} requests completed; "
              f"{parity}; compile {clock():.1f}s; smoke reading (host "
              f"clock, cold): {toks / dt:.1f} tok/s", flush=True)
        if bad:
            differ.append(name)
    check(not differ, f"tokens differ from 1x1 on: {', '.join(differ)}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-mesh and disaggregated serving "
                         "phase against 1x1 (needs four chips)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the prompts (weights use init seed 0)")
    args = ap.parse_args()

    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend "
              f"{jax.default_backend()!r})", file=sys.stderr)
        return 2
    from repro.configs.base import load_arch
    from repro.core.backend import use_compile_cache

    # tile plans come from the shape defaults, never from a tuning file
    os.environ.pop("PPAC_TILE_CACHE", None)
    print(f"compile cache: {use_compile_cache()}", flush=True)
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}",
          flush=True)
    clock = compile_clock()
    requests = make_requests(load_arch(ARCH).full().vocab, args.seed)
    try:
        if args.four_chips:
            phase_four_chips(clock, requests)
        else:
            outs = phase_serve(clock, requests)
            phase_parity(clock, requests, outs)
            phase_cam(clock)
            phase_coding(clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
