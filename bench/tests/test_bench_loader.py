"""A new cell is added with files and BENCHMARK.json entries alone: a
configuration, a traffic mix and a per-layer metric dropped beside the
others are found by name and run, with no existing file edited."""
import json
import os
import shutil

import harness
from small import run_small

CONFIG = {
    "name": "dummy-bh12",
    "rows": 3000, "features": 49,
    "corpus": {"generator": "tiny1m", "n_labeled": 1000,
               "n_unlabeled": 2000, "d": 48, "classes": 4},
    "index": {"method": "bh", "bits": 12, "tables": 1, "radius": 2,
              "scan_l": 64},
    "precision": {"hash_operands": "float32", "hash_accumulate": "float32",
                  "rerank": "float32"},
    "limits": {"topl_bad": 0, "pick_bad": 0, "gap_units": 64,
               "margin_units": 400},
    "reduced": [],
}
MIX = {"name": "al-wide", "loop": "closed_al", "backend": "scan",
       "max_batch": 8, "labeled_per_class": 3, "rounds_per_learner": 4,
       "hyperplanes": {"perturbation": 0.3, "pool_bytes": 65536},
       "check_answers": 24}
METRIC = '''
def read(ctx):
    return float(ctx["counters"]["rounds"])
'''


def _copy_benchmark(dst):
    src = harness.ROOT
    shutil.copy(os.path.join(src, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(src, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))


def test_new_cell_from_files_alone(tmp_path):
    root = str(tmp_path)
    _copy_benchmark(root)
    before = {p: open(p, "rb").read() for p in
              [os.path.join(d, f) for d, _, fs in os.walk(root) for f in fs]
              if not p.endswith("BENCHMARK.json")}
    with open(os.path.join(root, "bench", "configs", "dummy-bh12.json"),
              "w") as f:
        json.dump(CONFIG, f)
    with open(os.path.join(root, "bench", "traffic", "al-wide.json"),
              "w") as f:
        json.dump(MIX, f)
    with open(os.path.join(root, "bench", "metrics", "rounds.dummy.py"),
              "w") as f:
        f.write(METRIC)
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["configs"].append({"name": "dummy-bh12", "source": "test",
                             "file": "bench/configs/dummy-bh12.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "dummy.al-wide",
                               "config": "dummy-bh12", "traffic": "al-wide",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "rounds.dummy", "unit": "rounds",
                               "better": "higher",
                               "source": "program_counter", "layer": "test",
                               "moves": "al_round_ms",
                               "workloads": ["dummy.al-wide"]})
    for m in bench["end_to_end"]:
        if "workloads" in m and m["name"].startswith("al_round"):
            m["workloads"].append("dummy.al-wide")
    json.dump(bench, open(path, "w"))

    c = harness.load_cell("dummy.al-wide", root)
    assert c.cfg["index"]["bits"] == 12 and c.mix["max_batch"] == 8
    assert [m["name"] for m in c.per_layer] == ["rounds.dummy"]
    assert {m["name"] for m in c.end_to_end} == {
        "al_round_ms", "al_round_p95_ms", "setup_s"}
    out = run_small(c, seed=5, trace=False)
    assert out["correct"] is True, out["compared"]
    assert set(out["metrics"]) == {"al_round_ms", "al_round_p95_ms",
                                   "setup_s"}
    out = run_small(c, seed=6, trace=True)
    assert out["correct"] is True
    assert out["metrics"]["rounds.dummy"]["value"] >= 1
    for p, data in before.items():
        assert open(p, "rb").read() == data, p
