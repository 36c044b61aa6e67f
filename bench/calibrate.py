"""Readings that set a configuration's limits, run once on the chip and
not by the benchmark's own runs.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --seconds 5 [--control 1]

One run per seed in one process; one JSON line per seed with the compared
numbers and the end-to-end metrics.  With --control 1 the reference's
lower-precision twin answers in the program's place.  The limits in a
configuration's ``limits`` are set from these.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))
sys.path.insert(0, os.path.join(ROOT, "src"))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True,
                    help="a cell of BENCHMARK.json")
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", type=int, default=0)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    c = harness.load_cell(args.workload)
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = harness.run(c, seed, args.seconds, False, time.perf_counter(),
                          control=bool(args.control))
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "compared": out["compared"],
                          "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
