"""An index fitted on row-sharded features: its mesh comes from
``x.sharding``, the features stay where they are, the scan runs shard by
shard and each shard re-ranks the rows it owns; answers equal the
one-device index's on the same rows, bit for bit.  The four-device cases
run in a process of their own (the forced device count); the mask read at
the candidates is checked here against the n-row mask it replaced."""
import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.indexer import IndexConfig
from repro.core import search
from repro.core.search import margin_rerank_batch
from repro.data.synthetic import tiny1m_like
from repro.serving import MultiTableIndex
from repro.serving import batch_query as bq

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _four_devices(code: str, **env) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=4", **env)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


SETUP = """
    import json
    import jax, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.indexer import IndexConfig
    from repro.data.synthetic import tiny1m_like
    from repro.serving import MultiTableIndex
    corpus = tiny1m_like(n_labeled=400, n_unlabeled=2600, d=24, classes=4,
                         seed=3)
    x = corpus.x                                   # 750 rows a shard
    mesh = jax.make_mesh((4,), ("rows",))
    xs = jax.device_put(x, NamedSharding(mesh, P("rows", None)))
    rng = np.random.default_rng(5)
    ws = rng.normal(size=(10, x.shape[1])).astype(np.float32)
    mask = rng.random(x.shape[0]) < 0.5
"""


@pytest.mark.parametrize("tables,masked,kernels,minor", [
    (1, False, "0", False), (1, True, "0", False), (2, True, "0", False),
    (1, True, "1", False), (1, True, "0", True)])
def test_sharded_scan_matches_one_device(tables, masked, kernels, minor):
    """ids, margins, top-k, candidates and table hits of query_scan_batch,
    masked and unmasked, on the jnp path and through the kernels (in the
    Pallas interpreter), and with the re-rank reading rows as a TPU lays
    them out (rows on the minor axis; ``minor``)."""
    got = _four_devices(SETUP + f"""
    import repro.serving.multi_table as mtm
    if {minor}:
        mtm.rows_minor = lambda x: True
    cfg = IndexConfig(method="bh", bits=16, tables={tables})
    one = MultiTableIndex(cfg).fit(x)
    mt = MultiTableIndex(cfg).fit(xs)
    m = mask if {masked} else None
    a = one.query_scan_batch(ws, l=32, topk=3, mask=m)
    b = mt.query_scan_batch(ws, l=32, topk=3, mask=m)
    same = {{k: bool(np.array_equal(getattr(a, k), getattr(b, k)))
            for k in ("ids", "margins", "ids_topk", "margins_topk",
                      "nonempty", "table_hits")}}
    same["candidates"] = all(np.array_equal(p, q) for p, q in
                             zip(a.candidates, b.candidates))
    print(json.dumps({{"same": same, "shards": mt.stats()["shards"],
                      "some": bool(a.nonempty.any())}}))
    """, REPRO_USE_KERNELS=kernels)
    assert got["shards"] == 4 and got["some"]
    assert all(got["same"].values()), got["same"]


def test_fit_keeps_features_sharded():
    """After fit and a masked query the features are the caller's array,
    laid out as given; no host copy and no one-device copy of them
    exists; the codes stay sharded too."""
    got = _four_devices(SETUP + """
    mt = MultiTableIndex(IndexConfig(method="bh", bits=16)).fit(xs)
    mt.query_scan_batch(ws, l=32, mask=mask)
    whole = [a for a in jax.live_arrays()
             if a.shape == x.shape and a is not xs]
    one_device = [a for a in jax.live_arrays()
                  if a.size >= x.size // 2 and len(a.sharding.device_set) == 1]
    print(json.dumps({
        "same": mt.x is xs, "sharding": mt.x.sharding == xs.sharding,
        "host": mt.x_np is None, "whole": len(whole),
        "one_device": len(one_device),
        "codes": len(mt._scan_state()[0].sharding.device_set),
        "stats": [mt.stats()["shards"], mt.stats()["rows_per_shard"]]}))
    """)
    assert got["same"] and got["sharding"] and got["host"]
    assert got["whole"] == 0 and got["one_device"] == 0
    assert got["codes"] == 4
    assert got["stats"] == [4, 750]


@pytest.mark.parametrize("kernels,minor", [("0", False), ("1", False),
                                           ("0", True)])
def test_sharded_churn_matches_one_device(kernels, minor):
    """Deletes flag rows dead on their chip, and the compaction past the
    threshold closes each shard's live rows up on that shard (ragged: the
    emptier shards pad with dead rows); through both, the answers equal
    the one-device index's after the same deletes, masked, through the
    kernels (Pallas interpreter) and the rows-minor gather too."""
    got = _four_devices(SETUP + f"""
    import repro.serving.multi_table as mtm
    if {minor}:
        mtm.rows_minor = lambda x: True
    cfg = IndexConfig(method="bh", bits=16, tables=2, compact_threshold=0.4)
    one = MultiTableIndex(cfg).fit(x)
    mt = MultiTableIndex(cfg).fit(xs)
    order = rng.permutation(x.shape[0])
    order = order[np.argsort(order // 750 != 0, kind="stable")]

    def same():
        out = []
        for m in (None, mask):
            a = one.query_scan_batch(ws, l=32, topk=3, mask=m)
            b = mt.query_scan_batch(ws, l=32, topk=3, mask=m)
            out.append(all(np.array_equal(getattr(a, k), getattr(b, k))
                           for k in ("ids", "margins", "ids_topk",
                                     "margins_topk", "nonempty",
                                     "table_hits")) and
                       all(np.array_equal(p, q) for p, q in
                           zip(a.candidates, b.candidates)))
        return all(out)

    steps = []
    for part in (order[:600], order[600:1300]):   # shard 0 empties first
        one.delete(part)
        mt.delete(part)
        steps.append([same(), mt.compactions, mt.stats()["rows_per_shard"]])
    print(json.dumps({{"steps": steps, "pads": mt._pads}}))
    """, REPRO_USE_KERNELS=kernels)
    (ok1, c1, r1), (ok2, c2, r2) = got["steps"]
    assert ok1 and ok2
    assert (c1, r1) == (0, 750)
    assert c2 == 1 and r2 < 750 and got["pads"] > 0


@pytest.fixture(scope="module")
def churned():
    """A one-device index whose stable ids differ from its rows (heavy
    delete and a compaction), with a mask over stable ids."""
    corpus = tiny1m_like(n_labeled=1200, n_unlabeled=0, d=24, classes=4,
                         seed=8)
    mt = MultiTableIndex(IndexConfig(method="bh", bits=14, tables=2,
                                     compact_threshold=0.3)).fit(corpus.x)
    mt.delete(np.arange(0, 1200, 3))
    mt.delete(np.arange(1, 500, 3))
    assert mt.compactions == 1
    rng = np.random.default_rng(9)
    mask = rng.random(mt._next_id) < 0.6
    ws = rng.normal(size=(6, corpus.x.shape[1])).astype(np.float32)
    return mt, ws, mask


@pytest.mark.parametrize("topk", [1, 4])
def test_mask_at_candidates_equals_row_mask(churned, topk):
    """The answers with the mask read at the candidates equal the n-row
    path it replaced: the mask translated to rows, gathered at the
    deduplicated candidates on the device, then the re-rank."""
    mt, ws, mask = churned
    got = mt.query_scan_batch(ws, l=24, topk=topk, mask=mask)
    codes, live = mt._scan_state()
    qcodes = bq.hash_queries_all(mt.families, ws)
    _, idx = mt._scan(codes, qcodes, 24, mt._n_live)
    grows, uniq, _ = bq.dedup_candidates(idx, live)
    valid = bq.mask_candidates(uniq, grows, mt.mask_to_rows(mask))
    margins, top = margin_rerank_batch(mt.x, jnp.asarray(ws), grows, valid,
                                       topk)
    top = np.asarray(top).astype(np.int64)
    margins = np.asarray(margins)
    top[~np.isfinite(margins)] = -1
    want = mt.rows_to_ids(top)
    assert np.array_equal(got.nonempty, np.asarray(valid).any(axis=1))
    assert np.array_equal(got.margins, margins[:, 0])
    assert np.array_equal(got.ids, want[:, 0])
    if topk > 1:
        assert np.array_equal(got.ids_topk, want)
    assert all(mask[got.ids[got.nonempty]])


def test_sharded_fit_refuses_column_sharding():
    got = _four_devices("""
    import json, jax, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.indexer import IndexConfig
    from repro.serving import MultiTableIndex
    mesh = jax.make_mesh((2,), ("rows",), devices=jax.devices()[:2])
    x = jax.device_put(np.ones((5, 8), np.float32),
                       NamedSharding(mesh, P(None, "rows")))
    try:
        MultiTableIndex(IndexConfig(method="bh", bits=8)).fit(x)
        out = "fitted"
    except ValueError as e:
        out = str(e)
    print(json.dumps({"out": out}))
    """)
    assert "row-sharded" in got["out"]


def test_gather_rows_reads_in_place():
    """The row gather that reads each row as a column of x.T gives x[rows]
    exactly, and the re-rank over it the same answers."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(300, 37)).astype(np.float32))
    rows = jnp.asarray(rng.integers(0, 300, (5, 24)), jnp.int32)
    assert np.array_equal(np.asarray(search.gather_rows(x, rows, True)),
                          np.asarray(x[rows]))
    w = jnp.asarray(rng.normal(size=(5, 37)).astype(np.float32))
    valid = jnp.asarray(rng.random((5, 24)) < 0.8)
    for a, b in zip(margin_rerank_batch(x, w, rows, valid, 3),
                    margin_rerank_batch(x, w, rows, valid, 3,
                                        rows_minor=True)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
