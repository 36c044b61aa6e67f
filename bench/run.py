"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Loads the cell named in ``BENCHMARK.json``, makes its corpus and traffic
from ``--seed``, builds the index and warms every shape the traffic uses
(``setup_s``), measures for ``--seconds`` (``--trace 1``: under the
profiler, and reports the per-layer metrics instead of the end-to-end
ones), then compares a sample of the window's answers with the plain
reference.  The last line of standard output is one JSON object; the last
lines of standard error are the compared numbers beside their limits.

There is no CPU fallback: without a TPU, or with fewer chips than the cell
asks for, it exits with code 3 and prints no result.

``--control 1`` puts the reference's lower-precision twin in the program's
place (its runs have to come out not correct; a configuration whose
reference defines no ``Control`` refuses it); the benchmark's own runs
never pass it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--save-trace", default=None,
                    help="also write the trace's extracted events here")
    args = ap.parse_args(argv)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "bench"))
    import harness
    c = harness.load_cell(args.workload, root)
    t_import = time.perf_counter()

    import jax
    devs = jax.devices()
    harness.log("startup (s): " + json.dumps(
        {"imports": round(t_import - T_START, 3),
         "devices": round(time.perf_counter() - t_import, 3)}))
    if devs[0].platform != "tpu" or len(devs) < c.cell["chips"]:
        print(f"needs {c.cell['chips']} TPU chip(s); JAX sees "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    sys.path.insert(0, os.path.join(root, "src"))
    result = harness.run(c, args.seed, args.seconds, bool(args.trace),
                         T_START, control=bool(args.control),
                         save_trace=args.save_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
