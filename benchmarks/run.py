# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows (plus each benchmark's own detailed CSV above them).
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _section(title):
    print(f"\n### {title}")


def smoke(json_path: str | None = None) -> None:
    """Fast CI path: import every benchmark module (catches bit-rot) and run
    a miniature serving sweep plus the fused-scan benchmark end to end."""
    from benchmarks import (fig2_collision, fig34_active_learning,  # noqa: F401
                            roofline_table, serving_async, serving_chaos,
                            serving_mixed, serving_refresh, serving_scan,
                            tables_efficiency)

    _section("smoke — serving sweep (tiny)")
    t0 = time.perf_counter()
    rows = tables_efficiency.run_serving(n=2000, d=32, batch=8,
                                         tables_sweep=(1, 2), repeat=1)
    print(f"# smoke ok: {len(rows)} metrics in "
          f"{time.perf_counter() - t0:.1f}s")

    _section("smoke — fused vs unfused Hamming scan")
    t0 = time.perf_counter()
    serving_scan.run(json_path=json_path, smoke=True)
    print(f"# scan smoke ok in {time.perf_counter() - t0:.1f}s")

    _section("smoke — async deadline-flush serving (Poisson sweep, tiny)")
    t0 = time.perf_counter()
    serving_async.run(json_path=json_path, smoke=True)
    print(f"# async smoke ok in {time.perf_counter() - t0:.1f}s")

    _section("smoke — mixed read/write serving over LSM delta index (tiny)")
    t0 = time.perf_counter()
    serving_mixed.run(json_path=json_path, smoke=True)
    print(f"# mixed smoke ok in {time.perf_counter() - t0:.1f}s")

    _section("smoke — online re-learn + zero-downtime generation swap")
    t0 = time.perf_counter()
    serving_refresh.run(json_path=json_path, smoke=True)
    print(f"# refresh smoke ok in {time.perf_counter() - t0:.1f}s")

    _section("smoke — replicated-shard router under fault injection (tiny)")
    t0 = time.perf_counter()
    serving_chaos.run(json_path=json_path, smoke=True)
    print(f"# chaos smoke ok in {time.perf_counter() - t0:.1f}s")


def main(json_path: str | None = None) -> None:
    from benchmarks import (fig2_collision, fig34_active_learning,
                            roofline_table, serving_async, serving_chaos,
                            serving_mixed, serving_refresh, serving_scan,
                            tables_efficiency)

    summary: list[tuple[str, float, str]] = []

    _section("Fig. 2 — collision probability & query exponent")
    t0 = time.perf_counter()
    fig2_collision.run()
    summary.append(("fig2_collision", (time.perf_counter() - t0) * 1e6,
                    "theory_vs_montecarlo"))

    _section("Fig. 3 — 20NG-like SVM active learning")
    t0 = time.perf_counter()
    os.makedirs("experiments", exist_ok=True)
    fig34_active_learning.run_fig3(out_json="experiments/fig3.json")
    summary.append(("fig3_al_newsgroups", (time.perf_counter() - t0) * 1e6,
                    "map/margin/nonempty per method"))

    _section("Fig. 4 — Tiny1M-like SVM active learning")
    t0 = time.perf_counter()
    fig34_active_learning.run_fig4(out_json="experiments/fig4.json")
    summary.append(("fig4_al_tiny1m", (time.perf_counter() - t0) * 1e6,
                    "map/margin/nonempty per method"))

    _section("Tables 1-3 — efficiency (fit / lookup / scan)")
    t0 = time.perf_counter()
    tables_efficiency.run()
    tables_efficiency.run_kernels()
    summary.append(("tables_efficiency", (time.perf_counter() - t0) * 1e6,
                    "per-method timings"))

    _section("Serving — QPS/latency/recall vs tables L")
    t0 = time.perf_counter()
    tables_efficiency.run_serving()
    summary.append(("serving_sweep", (time.perf_counter() - t0) * 1e6,
                    "qps/latency/recall per L + batch speedup"))

    _section("Serving — fused vs unfused Hamming scan")
    t0 = time.perf_counter()
    serving_scan.run(json_path=json_path)
    summary.append(("serving_scan_fused", (time.perf_counter() - t0) * 1e6,
                    "qps/p50/recall + modeled-vs-measured HBM bytes"))

    _section("Serving — async deadline-flush front end (open-loop Poisson)")
    t0 = time.perf_counter()
    serving_async.run(json_path=json_path)
    summary.append(("serving_async_poisson", (time.perf_counter() - t0) * 1e6,
                    "qps/latency/shed vs arrival-rate x deadline"))

    _section("Serving — mixed read/write traffic over LSM delta index")
    t0 = time.perf_counter()
    serving_mixed.run(json_path=json_path)
    summary.append(("serving_mixed_lsm", (time.perf_counter() - t0) * 1e6,
                    "qps/insert-rate/pause across live compactions"))

    _section("Serving — online re-learn + zero-downtime generation swap")
    t0 = time.perf_counter()
    serving_refresh.run(json_path=json_path)
    summary.append(("serving_refresh", (time.perf_counter() - t0) * 1e6,
                    "recall drift/repair + swap pause + retrace count"))

    _section("Serving — replicated-shard router: kill-a-replica recovery")
    t0 = time.perf_counter()
    serving_chaos.run(json_path=json_path)
    summary.append(("serving_chaos", (time.perf_counter() - t0) * 1e6,
                    "coverage/recall under shard loss + recovery curve"))

    _section("Roofline table (from dry-run artifacts)")
    t0 = time.perf_counter()
    roofline_table.run()
    summary.append(("roofline_table", (time.perf_counter() - t0) * 1e6,
                    "see experiments/dryrun/*.json"))

    _section("summary CSV")
    print("name,us_per_call,derived")
    for name, us, derived in summary:
        print(f"{name},{us:.0f},{derived}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    json_path = None
    if "--json" in sys.argv:
        i = sys.argv.index("--json")
        if i + 1 >= len(sys.argv) or sys.argv[i + 1].startswith("--"):
            sys.exit("--json requires a file path argument")
        json_path = sys.argv[i + 1]
    if "--smoke" in sys.argv:
        smoke(json_path)
    else:
        main(json_path)
