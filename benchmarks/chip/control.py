#!/usr/bin/env python3
"""The control of a cell's output comparison, on the chip.

    python benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace 0 --keep kept-<n>.json
    python benchmarks/chip/control.py --workload <name> \
        --kept kept-11.json,kept-12.json,kept-13.json [--ctrl-bits 4]

``run.py --keep`` writes the requests a run compared: their prompts and
the tokens the timed path served, at the cell's own sizes. For each such
file this runs the plain reference over the same prompts and tokens, and
at each position reads the gap of the token that the reference at
``--ctrl-bits``-bit activations puts first: the control, put in the
program's place. The control's tokens then go through the very
comparison that decides a run's ``correct`` (``harness.judge``, with the
cell's limits from ``checks/<cell>.json``), beside the program's own.

One JSON line per file; the last line gives the lower reading (the
largest program gap of each number) and the upper one (the smallest
control gap). Exits 1 when the control comes out correct in any file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402


def control(cell: dict, kept: dict, ctrl_bits: int = 4) -> dict:
    """The program's and the control's checks on one kept run."""
    _, spec, cfg, m = harness.cell_model(cell)
    recs = harness.reference_gaps(kept["records"], cfg, m, kept["seed"],
                                  spec["max_seq"], ctrl_bits=ctrl_bits)
    limits = harness.check_limit(cell)
    prog, prog_ok = harness.judge(recs, limits, key="gap")
    ctrl, ctrl_ok = harness.judge(recs, limits, key="ctrl")
    return {"seed": kept["seed"], "tokens": sum(r["tokens"] for r in recs),
            "program": prog, "program_correct": prog_ok,
            "control": ctrl, "control_correct": ctrl_ok}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--kept", required=True,
                    help="comma-separated files written by run.py --keep")
    ap.add_argument("--ctrl-bits", type=int, default=4)
    args = ap.parse_args(argv)
    try:
        cell = harness.cell(args.workload)
        harness.prepare(int(cell["chips"]))
    except harness.BenchError as e:
        print(f"control.py: {e}", file=sys.stderr)
        return 2
    rows = []
    for path in args.kept.split(","):
        with open(path) as f:
            kept = json.load(f)
        if kept["workload"] != args.workload:
            print(f"control.py: {path} holds {kept['workload']}",
                  file=sys.stderr)
            return 2
        row = control(cell, kept, args.ctrl_bits)
        rows.append(row)
        print(json.dumps(row), flush=True)
    names = list(rows[0]["program"])
    lower = {k: max(r["program"][k]["value"] for r in rows) for k in names}
    upper = {k: min(r["control"][k]["value"] for r in rows) for k in names}
    passed = [r["seed"] for r in rows if r["control_correct"]]
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "lower": lower, "upper": upper,
                      "control_correct_on": passed}))
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
