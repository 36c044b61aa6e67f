"""Stage spans of the scan query path: one masked batch through
``HashQueryService(mode="scan")`` under the profiler records one
``repro.query`` span that holds every stage span on the same thread, and
one ``repro.fetch`` span per blocking device-to-host read."""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.indexer import IndexConfig
from repro.data.synthetic import tiny1m_like
from repro.serving import HashQueryService, MultiTableIndex

STAGES = ("repro.hash", "repro.scan", "repro.dedup", "repro.mask",
          "repro.rerank", "repro.fetch", "repro.results")
READS = 2      # the candidates with the hits; margins and ids


def _repro_spans(trace_dir):
    """[name, start_ns, end_ns, thread] of every ``repro.*`` host event."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1, paths
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         line.name)
                        for e in line.events if e.name.startswith("repro.")]
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    corpus = tiny1m_like(n_labeled=1500, n_unlabeled=0, d=24, classes=4,
                         seed=0)
    n, d = corpus.x.shape
    index = MultiTableIndex(IndexConfig(method="bh", bits=16, radius=2,
                                        tables=2)).fit(corpus.x)
    svc = HashQueryService(index, max_batch=8, mode="scan", scan_l=16)
    rng = np.random.default_rng(3)
    ws = rng.normal(size=(5, d)).astype(np.float32)
    mask = rng.random(n) < 0.7
    want = svc.query_batch(ws, mask=mask)        # compiles outside the trace
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(trace_dir)
    try:
        got = svc.query_batch(ws, mask=mask)
    finally:
        jax.profiler.stop_trace()
    return want, got, _repro_spans(trace_dir)


def test_stage_spans_nest_in_one_query_span(traced):
    want, got, spans = traced
    query = [s for s in spans if s[0] == "repro.query"]
    assert len(query) == 1
    _, lo, hi, thread = query[0]
    stages = [s for s in spans if s[0] != "repro.query"]
    assert {s[0] for s in stages} == set(STAGES)
    for name, s, e, t in stages:
        assert t == thread, name
        assert lo <= s <= e <= hi, name
    assert [(a.index, a.margin) for a in got] == \
        [(a.index, a.margin) for a in want]


def test_one_fetch_span_per_device_read(traced):
    _, _, spans = traced
    assert sum(s[0] == "repro.fetch" for s in spans) == READS
