#!/usr/bin/env python3
"""Knee sweep of an open-loop cell, on the chip, in one process.

    python benchmarks/chip/sweep.py --config <config> --traffic <mix> \
        --rates 0.1,0.2,0.3 --seconds 30 [--seed 1] [--out FILE]

The configuration and the open-loop traffic file are named as in
``BENCHMARK.json``; the cell need not be there yet, since the sweep is
what sets its rate. Builds the server once, then for each rate in ascending order:
serves the cell's lead-in and a window of ``--seconds`` of Poisson
arrivals at that rate (the traffic file's lengths), reads the queue
depth at the window's start and close, and drains the server before the
next rate. The depth at a time counts every request due by then that
has no first token yet, whether it waits in the server's queue or, with
the harness busy in a long tick, has not been submitted: the server
admits every waiting request into free slots at each tick, so its own
queue reads empty after almost every tick, also past the knee. The knee
is the highest rate at which the depth at the close is no greater than
at the start. One JSON line per rate; the cell's rate is then written by
hand into its traffic file at about four fifths of the knee.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    import harness
    import loadgen
    try:
        harness.prepare(1)
    except harness.BenchError as e:
        print(f"sweep.py: {e}", file=sys.stderr)
        return 2
    name = f"{args.config}.{args.traffic}"
    cell = {"name": name, "config": args.config, "traffic": args.traffic,
            "chips": 1, "config_entry": {
                "file": f"benchmarks/chip/configs/{args.config}.json"}}
    run = harness.Run(cell, args.seed, t_start=time.perf_counter())
    if run.spec["loop"] != "open_poisson":
        print("sweep.py: the cell is not an open loop", file=sys.stderr)
        return 2
    run.build()
    run.warm_up()
    knee = None
    for rate in sorted(float(r) for r in args.rates.split(",")):
        spec = dict(run.spec, rate_rps=rate)
        run.traffic = loadgen.Traffic(spec, args.seed, run.cfg.vocab)
        run.tracks.clear()
        run.requests.clear()
        run.fill(args.seconds)
        win = run.measure(args.seconds)
        depth0, depth1 = run.backlog_at(win.t0), run.backlog_at(win.t1)
        m = run.end_to_end(win)
        row = {"rate_rps": rate, "depth_start": depth0, "depth_end": depth1,
               "window_s": win.t1 - win.t0,
               **{k: m[k] for k in ("output_tok_s", "ttft_p95_s", "n_due",
                                    "n_missing", "itl_p95_ms") if k in m}}
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps({"cell": name, **row}) + "\n")
        if depth1 <= depth0:
            knee = rate
        while run.server.queue or any(r is not None
                                      for r in run.server.live):
            run.tick()
    print(json.dumps({"cell": name, "knee_rps": knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
