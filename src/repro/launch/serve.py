"""Batched serving launcher (back-compat CLI over launch/serve_lm.py).

The server itself — slot-based continuous batching over a device-resident
donated cache, bucketed right-padded prefill admission, per-sequence
decode positions, fused on-device token selection — lives in
:mod:`repro.launch.serve_lm`; this module keeps the original CLI (with
the PPAC quantization / cycle-accounting / autotune flags) and the
``BatchServer`` name for existing callers.

CLI: PYTHONPATH=src python -m repro.launch.serve --arch smollm_360m \
        [--full] --requests 12 --max-new 16 [--serve-quant] [--weight-bits 4] \
        [--kv-int8] [--autotune]
"""
from __future__ import annotations

import argparse

import numpy as np

from ..core.backend import use_compile_cache
from ..serve.step import autotune_serving_plans
from .serve_lm import LMServer, Request, build_lm_server, run_and_report

# Back-compat: the slot-based server moved to serve_lm and grew bucketed
# admission + donated-cache residency; the old name stays importable.
BatchServer = LMServer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_360m")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--serve-quant", action="store_true")
    ap.add_argument("--weight-bits", type=int, default=4,
                    choices=(1, 2, 3, 4, 8),
                    help="resident weight precision K for --serve-quant: "
                         "1/2..4 run the fused PPAC kernels, 8 the int8 "
                         "MXU fallback")
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--autotune", action="store_true",
                    help="measure + persist tile plans for every packed "
                         "projection shape before serving (refreshes the "
                         "PPAC_TILE_CACHE json, which must be set; "
                         "meaningful on TPU)")
    args = ap.parse_args()

    use_compile_cache()
    server, report = build_lm_server(
        args.arch, full=not args.smoke, serve_quant=args.serve_quant,
        weight_bits=args.weight_bits, kv_int8=args.kv_int8,
        slots=args.slots)
    cfg = server.cfg
    if report is not None:
        if args.autotune:
            from ..kernels.tiling import plan_cache
            tuned = autotune_serving_plans(server.params, cfg,
                                           batch=args.slots, verbose=True)
            print(f"autotuned {len(tuned)} tile plans -> "
                  f"{plan_cache().path}")
        est = report.est_us_per_token()
        # K/L from the accounting itself: packed1 binarizes activations, so
        # its bit-serial schedule is 1x1 regardless of act_bits.
        kl = sorted({(p.k_bits, p.l_bits) for p in report.projections})
        kl_str = ", ".join(f"K={k}, L={l}" for k, l in kl)
        print(f"PPAC serving: {report.num_projections} quantized projections "
              f"({kl_str}), "
              f"{report.cycles_per_token} emulated cycles/token "
              f"({report.fused_cycles_per_token} on fused kernels); "
              f"per decode step of {args.slots} slots: "
              f"{report.cycles_per_token * args.slots} cycles"
              + (f", est {est:.1f} us/token at the paper's "
                 f"{report.config.m}x{report.config.n} clock"
                 if est is not None else ""))

    rng = np.random.default_rng(0)
    run_and_report(
        server,
        [Request(i, rng.integers(0, cfg.vocab, int(rng.integers(4, 24))),
                 args.max_new)
         for i in range(args.requests)],
        report=report)


if __name__ == "__main__":
    main()
