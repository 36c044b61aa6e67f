"""A new cell is added with files and BENCHMARK.json entries alone: a
configuration, a traffic mix, a per-layer metric, a corpus generator and a
reference dropped beside the others are found by name and run, with no
existing file edited; the corpus is laid out over the cell's chips."""
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

import corpora
import harness
from small import run_small, small_cell

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


def _write(root, rel, text):
    path = os.path.join(root, *rel.split("/"))
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


def _files(root):
    return {p: open(p, "rb").read() for p in
            [os.path.join(d, f) for d, _, fs in os.walk(root) for f in fs]
            if not p.endswith("BENCHMARK.json")}


def _add_cell(root, config, chips, per_layer=()):
    """Configuration, mix, cell and per-layer entries for ``config`` in the
    copy's BENCHMARK.json; returns the cell's name."""
    _write(root, f"bench/configs/{config['name']}.json", json.dumps(config))
    _write(root, "bench/traffic/al-wide.json", json.dumps(MIX))
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    name = config["name"] + ".al-wide"
    bench["configs"].append({"name": config["name"], "source": "test",
                             "file": f"bench/configs/{config['name']}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": name, "config": config["name"],
                               "traffic": "al-wide", "chips": chips,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append(name)
    bench["per_layer"] += [dict(m, workloads=[name]) for m in per_layer]
    json.dump(bench, open(path, "w"))
    return name


def test_new_cell_from_files_alone(tmp_path):
    root = str(tmp_path)
    _copy_benchmark(root)
    before = _files(root)
    _write(root, "bench/metrics/rounds.dummy.py", METRIC)
    name = _add_cell(root, CONFIG, chips=1, per_layer=[
        {"name": "rounds.dummy", "unit": "rounds", "better": "higher",
         "source": "program_counter", "layer": "test",
         "moves": "al_round_ms"}])

    c = harness.load_cell(name, root)
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


# A configuration of a shape the harness has no code for brings its corpus
# generator and its reference as files of its own.  Here they delegate to
# the built-ins, and record what they were handed.
GENERATOR = '''
import corpora

SEEN = []


def make(key, mesh, **kw):
    SEEN.append(mesh)
    return corpora.tiny1m(key, **kw)
'''
REFERENCE = '''
import reference
from reference import Control  # noqa: F401

ROWS = []


class Reference(reference.Reference):
    def margins(self, w, rows):
        ROWS.append(len(rows))
        return super().margins(w, rows)
'''
# a four-chip cell lays its corpus out by rows over the mesh it is given
SHARDED_GENERATOR = '''
import jax
from jax.sharding import NamedSharding, PartitionSpec

import corpora


def make(key, mesh, **kw):
    assert mesh.axis_names == ("rows",) and mesh.devices.size == 4, mesh
    rows = NamedSharding(mesh, PartitionSpec("rows"))
    x, y = corpora.tiny1m(key, **kw)
    return jax.device_put(x, rows), jax.device_put(y, rows)
'''


def test_generator_and_reference_from_files_alone(tmp_path, monkeypatch):
    root = str(tmp_path)
    _copy_benchmark(root)
    before = _files(root)
    _write(root, "bench/generators/dense_file.py", GENERATOR)
    _write(root, "bench/references/plain_file.py", REFERENCE)
    config = dict(CONFIG, name="dense-file", reference="plain_file",
                  corpus=dict(CONFIG["corpus"], generator="dense_file"))
    name = _add_cell(root, config, chips=1)
    loaded = {}
    load = harness.load_module

    def spy(kind, mod_name, where=harness.ROOT):
        loaded[kind] = load(kind, mod_name, where)
        return loaded[kind]

    monkeypatch.setattr(harness, "load_module", spy)
    c = harness.load_cell(name, root)
    out = run_small(c, seed=2 ** 31 + 11)
    assert out["correct"] is True, out["compared"]
    assert out["compared"]["topl_bad"]["value"] == 0
    mesh, = loaded["generators"].SEEN
    assert mesh.axis_names == ("rows",) and mesh.devices.size == 1
    assert sum(loaded["references"].ROWS) > 0
    assert run_small(c, seed=7, control=True)["correct"] is False
    for p, data in before.items():
        assert open(p, "rb").read() == data, p


def test_control_needs_a_control_in_the_reference(tmp_path):
    root = str(tmp_path)
    _copy_benchmark(root)
    _write(root, "bench/references/no_control.py",
           "from reference import Reference  # noqa: F401\n")
    _write(root, "bench/configs/no-control.json",
           json.dumps(dict(CONFIG, name="no-control",
                           reference="no_control")))
    c = harness.cell_of("no-control", "al-scan", root)
    with pytest.raises(ValueError, match="defines no Control"):
        harness.setup(c, 1, control=True)


FOUR_DEVICES = '''
import json, sys
sys.path[:0] = sys.argv[1:3]
import harness
from small import run_small
from repro.serving.multi_table import MultiTableIndex

seen = []
fit = MultiTableIndex.fit


def spy(self, x, *a, **kw):
    seen.append(x)
    return fit(self, x, *a, **kw)


MultiTableIndex.fit = spy
c = harness.load_cell(sys.argv[4], sys.argv[3])
r = harness.setup(c, 2 ** 31 + 5)
x = seen[0]
shards = sorted((s.device.id, s.index[0].start, s.index[0].stop)
                for s in x.addressable_shards)
out = run_small(c, seed=2 ** 31 + 6)
print(json.dumps({"same": x is r.x, "spec": str(x.sharding.spec),
                  "shards": shards, "rows": x.shape[0],
                  "correct": out["correct"], "compared": out["compared"]}))
'''


def test_four_chip_cell_gets_its_corpus_row_sharded(tmp_path):
    """On four CPU devices, in a process of its own: the generator gets a
    four-device mesh, and the program gets ``x`` as the generator laid it
    out, row-sharded, not gathered by the harness.  The program takes such
    an ``x`` (it copies it to the host itself), so the whole run is made and
    comes out correct."""
    root = str(tmp_path)
    _copy_benchmark(root)
    before = _files(root)
    _write(root, "bench/generators/dense_rows.py", SHARDED_GENERATOR)
    config = dict(CONFIG, name="dense-rows",
                  corpus=dict(CONFIG["corpus"], generator="dense_rows"))
    name = _add_cell(root, config, chips=4)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    tests = os.path.dirname(os.path.abspath(__file__))
    p = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICES, tests, harness.BENCH, root,
         name],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["same"] is True
    assert "rows" in got["spec"]
    n = got["rows"]
    assert got["shards"] == [[d, d * n // 4, (d + 1) * n // 4]
                             for d in range(4)]
    assert got["correct"] is True, got["compared"]
    for p, data in before.items():
        assert open(p, "rb").read() == data, p


@pytest.mark.parametrize("config", ["tiny1m-bh20", "newsgroups-bh16"])
def test_index_config_as_before(config):
    from repro.core.indexer import IndexConfig
    c = harness.cell_of(config, "al-scan")
    ix = c.cfg["index"]
    assert harness.index_config(c, 3 * 10 ** 9) == IndexConfig(
        method=ix["method"], bits=ix["bits"], radius=ix["radius"],
        tables=ix["tables"], seed=harness.index_seed(3 * 10 ** 9),
        batch=c.mix["max_batch"])


def test_index_keys_pass_through():
    c = harness.cell_of("tiny1m-bh20", "al-scan")
    c.cfg["index"].update(min_candidates=7, seeded_projections=False)
    got = harness.index_config(c, 1)
    assert got.min_candidates == 7 and got.seeded_projections is False
    for key in ("no_such_key", "seed", "batch"):
        c = harness.cell_of("tiny1m-bh20", "al-scan")
        c.cfg["index"][key] = 1
        with pytest.raises(TypeError, match=key):
            harness.index_config(c, 1)


@pytest.mark.parametrize("workload", ["tiny1m.al-scan", "newsgroups.al-scan"])
def test_builtin_generators_same_bytes(workload):
    """The dispatch calls a built-in as the harness always has: the same
    key, the default device, the same bytes."""
    c = small_cell(workload)
    seed = 2 ** 33 + 3
    x, y = harness.make_corpus(c, seed)
    kw = dict(c.cfg["corpus"])
    gen = getattr(corpora, kw.pop("generator"))
    x0, y0 = gen(jax.random.fold_in(jax.random.PRNGKey(0), seed % (1 << 32)),
                 **kw)
    assert x.devices() == {jax.devices()[0]}
    assert np.asarray(x).tobytes() == np.asarray(x0).tobytes()
    assert np.asarray(y).tobytes() == np.asarray(y0).tobytes()
