#!/usr/bin/env python3
"""Readings that the limits of ``correct`` and the cell's load are set
from, in one process.  Not part of a benchmark run.

For each seed, one window of the cell at its own load through the
program as the configuration states it (the lower readings); for each
control seed the same with the program's tier of a lower precision
switched on (``--control w8a8`` or ``w4a16``), whose readings must fail
a limit.  With ``--rates`` the program runs once per offered rate on
the first seed instead: the knee sweep.  Prints one JSON line per run
with the check's numbers and the served requests' latencies.

    python bench/calibrate.py --workload bloom-3b.paper-mix --seconds 51 \\
        --seeds 11,12,13 --control-seeds 21,22,23 --control w8a8
    python bench/calibrate.py --workload bloom-3b.paper-mix --seconds 51 \\
        --seeds 11 --rates 0.05,0.1,0.2
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def _latencies(result) -> dict:
    served = result.served()
    due = result.due_in_window()
    ttft = [1e3 * (r.first - r.due) for r in served]
    wait = [1e3 * (r.admitted - r.due) for r in due
            if r.admitted is not None]
    tokens = sum(p[1] for p in result.rec.polls if p[0] <= result.seconds)
    return {"due": len(due), "released": sum(r.released is not None
                                            for r in due),
            "tokens_per_s": tokens / result.seconds,
            "admitted": len(wait), "served": len(served),
            "ttft_ms": sorted(ttft), "admit_wait_ms": sorted(wait)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control", default="w8a8", choices=harness.CONTROLS)
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    if args.rates:
        runs = [(seeds[0], None, float(r)) for r in args.rates.split(",")]
    else:
        runs = [(s, None, None) for s in seeds] \
            + [(int(s), args.control, None)
               for s in args.control_seeds.split(",") if s]
    for seed, control, rate in runs:
        t = time.perf_counter()
        seen = {}
        line = harness.run_cell(
            args.workload, seed, args.seconds, False, control=control,
            rate=rate, inspect=lambda res, *_: seen.update(_latencies(res)))
        print(json.dumps({"seed": seed, "run": control or "program",
                          "rate": rate, "correct": line["correct"],
                          "checks": line["checks"],
                          "check_info": line["check_info"],
                          "attempted": line["attempted"],
                          "failed": line["failed"],
                          "metrics": line["metrics"], **seen,
                          "mem": line["device"]["memory_peak_bytes"],
                          "wall_s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
