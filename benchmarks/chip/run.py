#!/usr/bin/env python3
"""Chip benchmark of the PPAC LM server, one cell per run.

    python benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (a configuration under a traffic mix) is named in
``BENCHMARK.json`` at the checkout's root. The run builds the server from
``--seed`` (weights and traffic), warms up every shape the cell's traffic
uses, fills the starting state, drives ``LMServer.submit``/``tick`` for
``--seconds`` and compares the served tokens with the plain reference.
With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics read from a profiler trace of the
window. The last line of standard output is one JSON object; the numbers
compared, each beside its limit, are the last lines of standard error.

It runs only on a TPU with as many chips as the cell asks for; anywhere
else, or without ``BENCHMARK.json``, it exits nonzero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep", default=None,
                    help="write the compared requests here (control.py)")
    args = ap.parse_args(argv)
    if not os.path.exists(os.path.join(ROOT, "BENCHMARK.json")):
        print("run.py: no BENCHMARK.json at the checkout's root",
              file=sys.stderr)
        return 2
    sys.path.insert(0, HERE)
    import harness
    try:
        cell = harness.cell(args.workload)
        harness.prepare(int(cell["chips"]))
    except harness.BenchError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    try:
        out = harness.execute(cell, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START,
                              keep=args.keep)
    except harness.BenchError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
