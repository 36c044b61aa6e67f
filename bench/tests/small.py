"""Cells of BENCHMARK.json cut to a size the CPU runs in seconds, for the
tests.  The CPU multiplies float32 at full precision, so the stated hash
rounding becomes float32 here; everything else is the cell's own."""
import time

import harness

SIZES = {
    "tiny1m": {"n_labeled": 600, "n_unlabeled": 4000, "d": 32},
    "newsgroups": {"n": 3000, "d": 500},
}


def small_cell(workload: str, root: str = harness.ROOT):
    """``workload``: a cell of BENCHMARK.json, or "<config>/<traffic>"."""
    if "/" in workload:
        c = harness.cell_of(*workload.split("/"), root)
    else:
        c = harness.load_cell(workload, root)
    corpus = c.cfg["corpus"]
    corpus.update(SIZES[corpus["generator"]])
    c.cfg["rows"] = corpus.get("n") or corpus["n_labeled"] + \
        corpus["n_unlabeled"]
    c.cfg["features"] = corpus["d"] + 1
    c.cfg["precision"]["hash_operands"] = "float32"
    c.mix["check_answers"] = 40
    return c


def run_small(c, seed=12345678901, seconds=1.0, **kw):
    return harness.run(c, seed, seconds, kw.pop("trace", False),
                       time.perf_counter(), **kw)
