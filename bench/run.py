#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The cells, their configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout.  With ``--trace 0`` the
line holds the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window.  Without a
TPU, with fewer chips than the cell asks for, on a device kind missing
from ``bench/peaks.json``, or without the serving program beside it
(``src/repro``), it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the TPU runtime logs under /tmp unless told otherwise: keep it inside
# the checkout
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_tpu_logs"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no serving program under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT))
    from bench import harness
    try:
        line = harness.run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace), clock_start=T_START)
    except (harness.NoChip, harness.UnknownDevice) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
