"""One benchmark run: set-up, the measured window, the per-layer reading of
a traced window, and the comparison with the plain reference.

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in ``bench/configs/<config>.json``,
its traffic mix in ``bench/traffic/<traffic>.json`` and each per-layer
metric's reader in ``bench/metrics/<metric>.py``.  A configuration's corpus
generator is a built-in of ``corpora.py`` or ``bench/generators/<name>.py``
(``make(key, mesh, **kw) -> (x, y)``), and its plain reference is
``reference.py`` or, where it names ``"reference": "<name>"``,
``bench/references/<name>.py`` (the interface is in ``reference.py``).

Placement: a generator file gets a mesh over the cell's first ``chips``
devices with one axis, ``"rows"``, and lays the corpus out over it (the
built-ins make theirs on the default device).  The corpus goes to the
program as the generator returned it: the harness gathers nothing, and the
program reads any sharding from ``x.sharding``.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import re
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace

import jax
import numpy as np
from jax.sharding import Mesh

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import corpora  # noqa: E402
import costs  # noqa: E402
import loadgen  # noqa: E402
import reduce  # noqa: E402
import reference  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
CHECK_SEED = 7919     # stream of the answer sample, apart from the load's


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, root: str = ROOT) -> SimpleNamespace:
    """The cell named in BENCHMARK.json, with its configuration, its mix
    and the metrics it reports."""
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    mine = lambda m: workload in m.get("workloads", [workload])
    return cell_of(conf["file"], cell["traffic"], root, cell=cell,
                   end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                   per_layer=[m for m in bench["per_layer"] if mine(m)])


def cell_of(config: str, traffic: str, root: str = ROOT, cell=None,
            end_to_end=(), per_layer=()) -> SimpleNamespace:
    """A configuration (its name under ``bench/configs/``, or its file
    relative to the root) and a mix by name; a pairing that is not a
    declared cell reports only ``setup_s`` (``bench/calibrate.py``
    readings, tests)."""
    path = config if config.endswith(".json") else \
        os.path.join("bench", "configs", config + ".json")
    mix = read_json(os.path.join(root, "bench", "traffic", traffic + ".json"))
    if mix["loop"] != "closed_al":
        raise ValueError(f"traffic {traffic!r}: no loop {mix['loop']!r}")
    cfg = read_json(os.path.join(root, path))
    ref = reference if "reference" not in cfg else \
        load_module("references", cfg["reference"], root)
    return SimpleNamespace(
        root=root, cell=cell or {"name": f"{config}.{traffic}", "chips": 1},
        cfg=cfg, mix=mix, reference=ref,
        end_to_end=list(end_to_end) or [{"name": "setup_s", "unit": "s"}],
        per_layer=list(per_layer),
        peaks_path=os.path.join(root, "bench", "peaks.json"))


def load_module(kind: str, name: str, root: str = ROOT):
    """``bench/<kind>/<name>.py`` as a module: a metric's reader, a corpus
    generator or a reference."""
    path = os.path.join(root, "bench", kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: str = ROOT):
    return load_module("metrics", name, root)


def make_corpus(c: SimpleNamespace, seed: int):
    """(x, y) from the configuration's ``corpus`` object and the run seed.
    A built-in generator makes them on the default device, as it always
    has; a file ``bench/generators/<generator>.py`` gets a mesh with one
    axis, ``"rows"``, over the cell's first ``chips`` devices, and lays
    them out over it as it chooses."""
    kw = dict(c.cfg["corpus"])
    name = kw.pop("generator")
    key = corpora.seed_key(seed)
    if name in corpora.GENERATORS:
        return corpora.GENERATORS[name](key, **kw)
    mesh = Mesh(np.asarray(jax.devices()[:c.cell["chips"]]), ("rows",))
    return load_module("generators", name, c.root).make(key, mesh, **kw)


def index_config(c: SimpleNamespace, seed: int):
    """Every key of the configuration's ``index`` but ``scan_l`` (the
    service's) goes to ``IndexConfig``, which refuses a key it does not
    have; ``seed`` and ``batch`` are the harness's own."""
    from repro.core.indexer import IndexConfig
    ix = {k: v for k, v in c.cfg["index"].items() if k != "scan_l"}
    return IndexConfig(**ix, seed=index_seed(seed), batch=c.mix["max_batch"])


def device_peaks(peaks_path: str, kind: str) -> dict:
    table = read_json(peaks_path)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in {peaks_path}")
    return table[kind]


def enable_compile_cache(path: str = CACHE_DIR) -> None:
    """Fixed path inside the checkout; every compile is cached, however
    short."""
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts traces and backend compiles while ``on``."""

    def __init__(self):
        self.on = False
        self.compiles = 0
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if not self.on:
            return
        if "backend_compile" in name:
            self.compiles += 1
        elif "trace_duration" in name:
            self.traces += 1


def index_seed(seed: int) -> int:
    return seed % (2 ** 31 - 1)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- set-up -------------------------------------------------------------------

def setup(c: SimpleNamespace, seed: int, control: bool = False,
          wrap=None) -> SimpleNamespace:
    """Corpus, hyperplane pool, index and service for one run, with every
    shape of the cell's traffic compiled.  ``control`` puts the reference's
    lower-precision twin in the program's place; ``wrap`` (tests) wraps the
    service's ``query_batch``."""
    if control and not hasattr(c.reference, "Control"):
        raise ValueError(
            f"--control 1: the reference of {c.cfg['name']} "
            f"({c.reference.__file__}) defines no Control")
    phase = {"start": time.perf_counter()}
    from repro.serving.multi_table import MultiTableIndex
    from repro.serving.service import HashQueryService

    cfg, mix = c.cfg, c.mix
    phase["program_import"] = time.perf_counter()
    rng = np.random.default_rng([seed % (1 << 63), 1])
    x, y = make_corpus(c, seed)
    x.block_until_ready()
    phase["corpus"] = time.perf_counter()
    classes = cfg["corpus"]["classes"]
    pool = loadgen.hyperplane_pool(x, y, classes, mix["hyperplanes"], seed)
    phase["pool"] = time.perf_counter()
    index = MultiTableIndex(index_config(c, seed)).fit(x)
    phase["fit"] = time.perf_counter()
    r = SimpleNamespace(x=x, pool=pool, rng=rng, index=index)
    r.service = HashQueryService(index, max_batch=mix["max_batch"],
                                 mode=mix["backend"],
                                 scan_l=cfg["index"]["scan_l"])
    r.initial = loadgen.initial_unlabeled(
        np.asarray(y), classes, mix["labeled_per_class"], rng)
    if control:
        r.service = c.reference.Control(x, cfg, index_seed(seed))
    if wrap is not None:
        r.service = wrap(r.service)
    warm_up(r)
    phase["warm_up"] = time.perf_counter()
    names = list(phase)
    log("setup phases (s): " + json.dumps(
        {b: round(phase[b] - phase[a], 3) for a, b in zip(names, names[1:])}))
    return r


def warm_up(r: SimpleNamespace) -> None:
    """Run the one shape the window uses (a round's masked batch), twice."""
    for _ in range(2):
        r.service.query_batch(r.pool[0], mask=r.initial.copy())


# -- the window ---------------------------------------------------------------

def measure(r: SimpleNamespace, mix: dict, seconds: float) -> SimpleNamespace:
    window_s, rounds = loadgen.closed_al(
        r.service, r.pool, r.initial, mix["rounds_per_learner"], seconds,
        r.rng)
    return SimpleNamespace(window_s=window_s, rounds=rounds)


def end_to_end(c: SimpleNamespace, w: SimpleNamespace, setup_s: float):
    """(metrics, attempted, failed, printed extras)."""
    t = np.asarray([x.t for x in w.rounds])
    m = {"setup_s": setup_s,
         "al_round_ms": 1e3 * w.window_s / len(w.rounds),
         "al_round_p95_ms": 1e3 * float(np.percentile(t, 95))}
    queries = sum(len(x.answers) for x in w.rounds)
    empty = sum(not a.nonempty for x in w.rounds for a in x.answers)
    extra = {"rounds": len(w.rounds), "queries": queries,
             "empty_share": empty / max(queries, 1)}
    return m, queries, 0, extra


def counters(c: SimpleNamespace, w: SimpleNamespace) -> dict:
    """Counts of the window's work and the algorithmic bytes it needed
    (``reranked`` comes from ``answers_to_check``)."""
    cfg, ix = c.cfg, c.cfg["index"]
    n, d = cfg["rows"], cfg["features"]
    b = len(w.rounds[0].answers)
    return {"rounds": len(w.rounds),
            "scan_bytes": len(w.rounds) * costs.scan_bytes(
                n, ix["bits"], ix["tables"], b, ix["scan_l"], True),
            "rerank_bytes": costs.rerank_bytes(w.reranked, d,
                                               b * len(w.rounds))}


# -- correctness --------------------------------------------------------------

def answers_to_check(c: SimpleNamespace, r: SimpleNamespace,
                     w: SimpleNamespace, seed: int) -> list:
    """A sample drawn from the seed of the answers the window produced,
    each with the hyperplane and the mask it was asked with (whole rounds).
    It also counts the unmasked candidates the window re-ranked."""
    rng = np.random.default_rng([seed % (1 << 63), CHECK_SEED])
    per = len(w.rounds[0].answers)
    take = sorted(rng.choice(len(w.rounds),
                             min(len(w.rounds),
                                 max(1, c.mix["check_answers"] // per)),
                             replace=False).tolist())
    w.reranked = loadgen.replay_masks(r.initial, w.rounds, set(take))
    out = []
    for i in take:
        x = w.rounds[i]
        out += [SimpleNamespace(w=r.pool[x.j][q], mask=x.mask,
                                index=a.index, margin=a.margin,
                                nonempty=a.nonempty, candidates=a.candidates)
                for q, a in enumerate(x.answers)]
    return out


def check(c: SimpleNamespace, x, answers: list, seed: int) -> dict:
    ref = c.reference.Reference(x, c.cfg, index_seed(seed))
    got = reference.compare(answers, ref, x.shape[0])
    lim = c.cfg["limits"]
    got["correct"] = got["checked"] > 0 and all(
        got[k] <= v for k, v in lim.items())
    return got


# -- one run ------------------------------------------------------------------

def run(c: SimpleNamespace, seed: int, seconds: float, trace: bool,
        t_start: float, control: bool = False, wrap=None,
        save_trace: str | None = None) -> dict:
    """Set up, measure, read the trace when asked, free the program's
    state, and compare.  Returns the result line as a dict."""
    dev = jax.devices()
    peaks = device_peaks(c.peaks_path, dev[0].device_kind) \
        if dev[0].platform == "tpu" else {}
    r = setup(c, seed, control=control, wrap=wrap)
    setup_s = time.perf_counter() - t_start
    cc = CompileCounter()
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tdir, profiler_options=opts)
    cc.on = True
    with jax.profiler.TraceAnnotation(reduce.WINDOW_SPAN):
        w = measure(r, c.mix, seconds)
    cc.on = False
    if trace:
        jax.profiler.stop_trace()
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in dev)
    metrics, attempted, failed, extra = end_to_end(c, w, setup_s)
    extra.update(compiles_in_window=cc.compiles, traces_in_window=cc.traces)
    log("window: " + json.dumps(extra))
    answers = answers_to_check(c, r, w, seed)
    count = counters(c, w)
    x = r.x
    del r
    gc.collect()
    result = {"correct": None, "attempted": attempted, "failed": failed}
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev), "memory_peak_bytes": int(mem)}
    if trace:
        events = reduce.extract(reduce.find_xplane(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        if save_trace:
            reduce.save(dict(events, counters=count), save_trace)
        ctx = reduce.context(events, count, peaks)
        layer = {}
        for m in c.per_layer:
            v = metric_reader(m["name"], c.root).read(ctx)
            if v is not None:
                layer[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = layer
        device.update(busy_s=ctx["busy_s"], window_s=ctx["window_s"])
        result["breakdown"] = {
            "device_ops": reduce.top_ops(events, ctx["lo"], ctx["hi"]),
            "idle_gaps": reduce.idle_gaps(events, ctx["lo"], ctx["hi"])}
    else:
        result["metrics"] = {m["name"]: {"value": metrics[m["name"]],
                                         "unit": m["unit"]}
                             for m in c.end_to_end}
    result["device"] = device
    got = check(c, x, answers, seed)
    result["correct"] = bool(got.pop("correct"))
    lim = c.cfg["limits"]
    result["compared"] = {k: {"value": got[k], "limit": lim[k]} for k in lim}
    log(f"checked {got['checked']} answers")
    for k in lim:
        log(f"compared {k}: {got[k]} (limit {lim[k]})")
    return result
