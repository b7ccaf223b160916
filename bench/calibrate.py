#!/usr/bin/env python3
"""Calibration runs of one cell, many in one process (never a benchmark
run): the knee sweep, and the readings the correctness limits are set
from.

    python3 bench/calibrate.py sweep qwen3-1.7b.chat-prefix \\
        --rates 0.6 0.8 --seeds 5 6 --seconds 30
    python3 bench/calibrate.py correct qwen3-1.7b.chat-prefix \\
        --seeds 1 2 3 --seconds 20 [--control]

``sweep`` runs the cell at each arrival rate on each seed and prints the
backlog and the requests waiting for a slot at the window's opening and
close, the output tokens per second beside the offered ones, and the
latency tails: the knee is the highest rate whose backlog does not grow
over the window.  Its drain stops 20 s after the window: the backlog at
the close is the reading.  ``correct`` runs the cell at its own rate on
each seed and prints the logit gaps of the served tokens and, with
``--control``, the int8 control's readings judged against the cell's
limits.  One JSON line per run on standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=("sweep", "correct"))
    ap.add_argument("workload")
    ap.add_argument("--rates", type=float, nargs="*", default=())
    ap.add_argument("--seeds", type=int, nargs="*", default=(1,))
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()
    import harness
    import run
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = harness.resolve(bench, args.workload)
    run.require_chips(cell.chips)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    cell.end_to_end = [m for m in cell.end_to_end if m != "setup_s"]
    sweep = args.mode == "sweep"
    rates = args.rates if sweep else [cell.spec["rate_per_s"]]
    for rate in rates:
        for seed in args.seeds:
            cell.spec = dict(cell.spec, rate_per_s=rate)
            t = time.perf_counter()
            out = harness.run_cell(
                cell, seed, args.seconds, False, t_start=t,
                control=args.control,
                drain_limit_s=20.0 if sweep else harness.DRAIN_LIMIT_S)
            row = {"workload": cell.name, "rate_per_s": rate, "seed": seed,
                   "correct": out["correct"], "checks": out["checks"],
                   "control": out.get("control"), "load": out["load"],
                   "metrics": {k: v["value"]
                               for k, v in out["metrics"].items()},
                   "run_s": time.perf_counter() - t}
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
