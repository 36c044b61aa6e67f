"""Serving subsystem: multi-table recall, dynamic updates, batched query
equivalence, and service micro-batching semantics."""
import jax
import numpy as np
import pytest

from repro.core.indexer import HyperplaneIndex, IndexConfig
from repro.data.synthetic import tiny1m_like
from repro.serving import (HashQueryService, LSMMultiTableIndex,
                           MultiTableIndex)

BITS, RADIUS = 18, 3


@pytest.fixture(scope="module")
def corpus():
    return tiny1m_like(n_labeled=2000, n_unlabeled=0, d=32, classes=5, seed=0)


@pytest.fixture(scope="module")
def queries(corpus):
    rng = np.random.default_rng(1)
    return rng.normal(size=(32, corpus.x.shape[1])).astype(np.float32)


def _cfg(**kw):
    kw.setdefault("method", "bh")
    kw.setdefault("bits", BITS)
    kw.setdefault("radius", RADIUS)
    return IndexConfig(**kw)


def _recall(index, queries, x, top=20):
    """Fraction of queries whose answer lands in the true margin top-`top`."""
    hit = 0
    res = index.query_batch(queries)
    for b in range(queries.shape[0]):
        m = np.abs(x @ queries[b]) / np.linalg.norm(queries[b])
        if res.nonempty[b] and (m < res.margins[b] - 1e-12).sum() < top:
            hit += 1
    return hit / queries.shape[0]


def test_multi_table_recall_at_least_single(corpus, queries):
    single = MultiTableIndex(_cfg(tables=1)).fit(corpus.x)
    multi = MultiTableIndex(_cfg(tables=4)).fit(corpus.x)
    # same seed => table 0 of L=4 is the L=1 table, so candidates only grow
    res1 = single.query_batch(queries)
    res4 = multi.query_batch(queries)
    for b in range(queries.shape[0]):
        assert set(res1.candidates[b]) <= set(res4.candidates[b])
        if res1.nonempty[b]:
            assert res4.margins[b] <= res1.margins[b]
    assert (_recall(multi, queries, corpus.x)
            >= _recall(single, queries, corpus.x))


def test_single_table_matches_hyperplane_index(corpus, queries):
    """L=1 multi-table == the core single-table index (same family key)."""
    key0 = jax.random.fold_in(jax.random.PRNGKey(0), 0)
    hi = HyperplaneIndex(_cfg()).fit(corpus.x, learn_key=key0)
    mt = MultiTableIndex(_cfg(tables=1)).fit(corpus.x)
    assert np.array_equal(np.asarray(hi.codes), mt.codes[0])
    for b in range(8):
        r1, r2 = hi.query(queries[b]), mt.query(queries[b])
        assert np.array_equal(np.sort(r1.candidates), np.sort(r2.candidates))
        assert r1.index == r2.index


def test_insert_delete_roundtrip_equals_rebuild(corpus, queries):
    cfg = _cfg(tables=4)
    grown = MultiTableIndex(cfg).fit(corpus.x[:1500])
    ids = grown.insert(corpus.x[1500:])
    assert np.array_equal(ids, np.arange(1500, 2000))
    fresh = MultiTableIndex(cfg).fit(corpus.x)
    for b in range(queries.shape[0]):
        ra, rb = grown.query(queries[b]), fresh.query(queries[b])
        assert np.array_equal(ra.candidates, rb.candidates)
        assert ra.index == rb.index and ra.margin == rb.margin

    grown.delete(ids)
    assert grown.n == 1500
    back = MultiTableIndex(cfg).fit(corpus.x[:1500])
    for b in range(queries.shape[0]):
        ra, rb = grown.query(queries[b]), back.query(queries[b])
        assert np.array_equal(ra.candidates, rb.candidates)
        assert ra.index == rb.index and ra.margin == rb.margin


def test_delete_never_answered(corpus, queries):
    mt = MultiTableIndex(_cfg(tables=2)).fit(corpus.x)
    res = mt.query_batch(queries)
    victims = np.unique(res.ids[res.ids >= 0])
    mt.delete(victims)
    res2 = mt.query_batch(queries)
    for b in range(queries.shape[0]):
        assert not np.intersect1d(res2.candidates[b], victims).size
    with pytest.raises(KeyError):
        mt.delete(victims[:1])     # double delete


def test_ids_to_rows_never_issued(corpus):
    """Ids outside [0, next_id) raise the documented KeyError — never a raw
    numpy IndexError (and a negative id must not wrap to a valid row)."""
    mt = MultiTableIndex(_cfg()).fit(corpus.x)
    n = corpus.x.shape[0]
    for bad in (-1, n, n + 12345, np.int64(2) ** 40):
        with pytest.raises(KeyError, match="never assigned"):
            mt.ids_to_rows(np.asarray([bad], dtype=np.int64))
    # mixed good/bad still raises, and a valid id resolves afterwards
    with pytest.raises(KeyError, match="never assigned"):
        mt.ids_to_rows(np.asarray([0, n], dtype=np.int64))
    assert mt.ids_to_rows(np.asarray([0], dtype=np.int64))[0] == 0
    # tombstoned-but-not-compacted ids still resolve (delete depends on it)
    mt_keep = MultiTableIndex(_cfg(compact_threshold=None)).fit(corpus.x)
    mt_keep.delete(np.asarray([3], dtype=np.int64))
    assert mt_keep.ids_to_rows(np.asarray([3], dtype=np.int64))[0] == 3
    # before fit: the guarded RuntimeError, not an AttributeError
    with pytest.raises(RuntimeError, match="before fit"):
        MultiTableIndex(_cfg()).ids_to_rows(np.asarray([0], dtype=np.int64))


def test_query_batch_equals_query_loop(corpus, queries):
    """Batched path == loop of single queries, bit for bit."""
    mt = MultiTableIndex(_cfg(tables=4)).fit(corpus.x)
    batch = mt.query_batch(queries)
    for b in range(queries.shape[0]):
        single = mt.query(queries[b])
        assert np.array_equal(batch.candidates[b], single.candidates)
        assert batch.ids[b] == single.index
        if single.nonempty:
            assert batch.margins[b] == single.margin   # exact, not allclose
        assert batch.nonempty[b] == single.nonempty


def test_service_micro_batching_order_and_cache(corpus, queries):
    mt = MultiTableIndex(_cfg(tables=2)).fit(corpus.x)
    svc = HashQueryService(mt, max_batch=8, cache_size=64)

    want = [mt.query(queries[i]) for i in range(20)]
    for i in range(20):
        assert svc.submit(queries[i]) == i
    assert svc.pending == 20
    got = svc.flush()
    assert svc.pending == 0 and len(got) == 20
    for i in range(20):                      # per-request results in order
        assert got[i].index == want[i].index
        assert got[i].margin == want[i].margin

    # second pass: all 20 query codes hit the LRU cache, answers unchanged
    before = svc.cache_hits
    again = svc.query_batch(queries[:20])
    assert svc.cache_hits - before == 20
    assert [r.index for r in again] == [r.index for r in got]
    st = svc.stats()
    assert st["requests"] == 40 and st["batches"] == 6
    assert st["qps"] > 0 and st["mean_batch_latency_ms"] > 0

    # mutation invalidates the cache
    mt.insert(corpus.x[:2])
    before = svc.cache_hits
    svc.query_batch(queries[:4])
    assert svc.cache_hits == before


def test_service_mask_restricts_answers(corpus, queries):
    mt = MultiTableIndex(_cfg(tables=2)).fit(corpus.x)
    svc = HashQueryService(mt, max_batch=16)
    mask = np.zeros(corpus.x.shape[0], dtype=bool)
    mask[: corpus.x.shape[0] // 4] = True
    for res in svc.query_batch(queries, mask=mask):
        if res.nonempty:
            assert mask[res.index]
        else:
            assert res.index == -1


def test_scan_batch(corpus, queries):
    mt = MultiTableIndex(_cfg(tables=2)).fit(corpus.x)
    res = mt.query_scan_batch(queries[:8], l=32)
    assert res.ids.shape == (8,) and np.isfinite(res.margins).all()
    assert res.nonempty.all() and res.table_hits.shape == (2,)
    # scan answers are real near-minimum-margin points
    for b in range(8):
        m = np.abs(corpus.x @ queries[b]) / np.linalg.norm(queries[b])
        assert (m < res.margins[b] - 1e-12).sum() < 0.1 * corpus.x.shape[0]
        # the candidate short-list is a dedup'd union over both tables
        cand = res.candidates[b]
        assert cand.size == np.unique(cand).size <= 2 * 32


def test_scan_batch_after_heavy_delete(corpus, queries):
    """Deleted rows must not crowd live answers out of the top-l scan."""
    mt = MultiTableIndex(_cfg(tables=2)).fit(corpus.x[:200])
    mt.delete(np.arange(190))
    res = mt.query_scan_batch(queries[:4], l=8)
    assert (res.ids >= 190).all() and np.isfinite(res.margins).all()
    mt.delete(np.arange(190, 200))            # now empty
    res = mt.query_scan_batch(queries[:4], l=8)
    assert (res.ids == -1).all() and np.isinf(res.margins).all()
    assert not res.nonempty.any()
    # empty index still honours the (B, topk) shape contract
    res = mt.query_scan_batch(queries[:4], l=8, topk=3)
    assert res.ids_topk.shape == (4, 3) and (res.ids_topk == -1).all()
    assert np.isinf(res.margins_topk).all()


def test_scan_single_launch_any_tables(corpus, queries, monkeypatch):
    """query_scan_batch issues exactly ONE Hamming scan dispatch no matter
    how many tables the index holds (L folds into the query batch).  The
    dispatch target depends on the backend (core.search's jnp path with
    use_kernels off, kernels.ops's scan of the lane-dense codes with it
    on), so count both."""
    import repro.kernels.ops as kops
    import repro.serving.multi_table as mtb
    calls = {"n": 0}
    real = mtb.hamming_topk_grouped
    real_ops = kops.hamming_topk_lanes

    def counting(codes, qs, l, **kw):
        calls["n"] += 1
        return real(codes, qs, l, **kw)

    def counting_ops(codes, qs, l, n, **kw):
        calls["n"] += 1
        return real_ops(codes, qs, l, n, **kw)

    monkeypatch.setattr(mtb, "hamming_topk_grouped", counting)
    monkeypatch.setattr(kops, "hamming_topk_lanes", counting_ops)
    for L in (1, 4):
        mt = MultiTableIndex(_cfg(tables=L)).fit(corpus.x)
        calls["n"] = 0
        res = mt.query_scan_batch(queries, l=16)
        assert calls["n"] == 1
        assert res.table_hits.shape == (L,) and (res.table_hits > 0).all()


def test_scan_matches_per_table_loop(corpus, queries):
    """Stacked single-launch scan == the per-table loop it replaced."""
    mt = MultiTableIndex(_cfg(tables=3)).fit(corpus.x)
    from repro.core.search import hamming_topk_batch
    from repro.serving import batch_query as bq
    res = mt.query_scan_batch(queries[:8], l=16)
    qcodes = bq.hash_queries_all(mt.families, queries[:8])
    per_table = []
    for t in range(3):
        _, idx = hamming_topk_batch(jax.numpy.asarray(mt.codes[t]),
                                    qcodes[t], 16)
        per_table.append(np.asarray(idx, dtype=np.int64))
    for b in range(8):
        union = np.unique(np.concatenate([per_table[t][b] for t in range(3)]))
        assert np.array_equal(np.sort(res.candidates[b]), union)
    ids, margins, _ = bq.batched_rerank(
        mt.x, queries[:8], [np.unique(np.concatenate(
            [per_table[t][b] for t in range(3)])) for b in range(8)], 1)
    assert np.array_equal(res.ids, ids[:, 0])
    assert np.array_equal(res.margins, margins[:, 0])


def test_scan_select_modes_parity(corpus, queries):
    """query_scan_batch answers are identical under histogram and argmin
    selection (IndexConfig.fused_select), on both the kernel and jnp legs,
    including a deep scan at l == n_live and the l > n_live sentinel case
    — the large-l regime the histogram kernel makes viable."""
    n_live = corpus.x.shape[0]
    for use_kernels in (False, True):
        mt = MultiTableIndex(
            _cfg(tables=2, use_kernels=use_kernels)).fit(corpus.x)
        for l in (16, n_live, n_live + 100):
            results = {}
            for select in ("argmin", "hist"):
                mt.config.fused_select = select
                results[select] = mt.query_scan_batch(queries[:8], l=l,
                                                      topk=3)
            a, h = results["argmin"], results["hist"]
            assert np.array_equal(a.ids, h.ids)
            assert np.array_equal(a.margins, h.margins)
            assert np.array_equal(a.ids_topk, h.ids_topk)
            assert np.array_equal(a.margins_topk, h.margins_topk)
            for ca, ch in zip(a.candidates, h.candidates):
                assert np.array_equal(ca, ch)


def test_scan_kernel_path_matches_jnp(corpus, queries):
    """use_kernels=True (fused Pallas scan) answers == pure-jnp scan."""
    mt_j = MultiTableIndex(_cfg(tables=2)).fit(corpus.x)
    mt_k = MultiTableIndex(_cfg(tables=2, use_kernels=True)).fit(corpus.x)
    rj = mt_j.query_scan_batch(queries[:8], l=16, topk=4)
    rk = mt_k.query_scan_batch(queries[:8], l=16, topk=4)
    assert np.array_equal(rj.ids, rk.ids)
    assert np.array_equal(rj.margins, rk.margins)
    assert np.array_equal(rj.ids_topk, rk.ids_topk)
    for b in range(8):
        assert np.array_equal(rj.candidates[b], rk.candidates[b])


def test_scan_topk_wider_than_candidates(corpus, queries):
    """topk > L*l must pad to the requested width, matching query_batch's
    (B, topk) shape contract (impossible slots: id -1 / margin +inf)."""
    mt = MultiTableIndex(_cfg(tables=2)).fit(corpus.x)
    res = mt.query_scan_batch(queries[:4], l=4, topk=40)
    assert res.ids_topk.shape == (4, 40)
    assert res.margins_topk.shape == (4, 40)
    valid = res.ids_topk >= 0
    assert np.isfinite(res.margins_topk[valid]).all()
    assert np.isinf(res.margins_topk[~valid]).all()
    assert valid.sum(axis=1).max() <= 2 * 4     # at most L*l candidates


def test_scan_mask_and_service_mode(corpus, queries):
    mt = MultiTableIndex(_cfg(tables=2)).fit(corpus.x)
    mask = np.zeros(corpus.x.shape[0], dtype=bool)
    mask[: corpus.x.shape[0] // 4] = True
    res = mt.query_scan_batch(queries[:8], l=32, mask=mask)
    nomask = mt.query_scan_batch(queries[:8], l=32)
    for b in range(8):
        if res.nonempty[b]:
            assert mask[res.ids[b]]
        # like the probe path, mask narrows answers but not the reported
        # candidate short-list
        assert np.array_equal(res.candidates[b], nomask.candidates[b])
    # scan-mode service == direct scan calls, and counters advance
    svc = HashQueryService(mt, max_batch=16, mode="scan", scan_l=32)
    got = svc.query_batch(queries[:16])
    want = mt.query_scan_batch(queries[:16], l=32)
    assert [r.index for r in got] == want.ids.tolist()
    assert [r.margin for r in got] == want.margins.tolist()
    st = svc.stats()
    assert st["requests"] == 16 and st["batches"] == 1 and st["qps"] > 0


def test_empty_delete_is_noop_and_prefit_raises(corpus, queries):
    mt = MultiTableIndex(_cfg(tables=2))
    with pytest.raises(RuntimeError, match="before fit"):
        mt.insert(corpus.x[:2])
    with pytest.raises(RuntimeError, match="before fit"):
        mt.delete([0])
    with pytest.raises(RuntimeError, match="before fit"):
        mt.query_scan_batch(queries[:2])
    mt.fit(corpus.x[:200])
    svc = HashQueryService(mt, max_batch=8, cache_size=64)
    svc.query_batch(queries[:4])
    v, before = mt.version, svc.cache_hits
    mt.delete([])                                 # both empty spellings
    mt.delete(np.empty((0,), dtype=np.int64))
    assert mt.version == v                        # no version bump...
    svc.query_batch(queries[:4])
    assert svc.cache_hits - before == 4           # ...so the cache survives
    state_before = mt._scan_state()[0]
    mt.delete([])
    assert mt._scan_state()[0] is state_before    # device scan state kept


def test_compact_id_stability(corpus, queries):
    """delete -> compact -> query: outstanding stable ids keep resolving,
    and both backends answer exactly like a fresh index on the survivors
    (with answers reported in stable-id space)."""
    cfg = _cfg(tables=2, compact_threshold=None)   # manual compaction
    mt = MultiTableIndex(cfg).fit(corpus.x)
    mt.delete(np.arange(0, 2000, 2))
    assert mt.stats()["dead_fraction"] == pytest.approx(0.5)
    survivors = mt.compact()
    assert np.array_equal(survivors, np.arange(1, 2000, 2))
    st = mt.stats()
    assert st["rows"] == 1000 and st["n"] == 1000 and mt.compactions == 1
    assert mt.compact().size == 1000               # idempotent no-op
    assert mt.version == st["version"]             # ...without a bump

    fresh = MultiTableIndex(_cfg(tables=2)).fit(corpus.x[1::2])
    got = mt.query_batch(queries)
    want = fresh.query_batch(queries)
    assert np.array_equal(got.ids, survivors[want.ids])
    assert np.array_equal(got.margins, want.margins)
    for b in range(queries.shape[0]):
        assert np.array_equal(got.candidates[b],
                              survivors[want.candidates[b]])
    gs = mt.query_scan_batch(queries[:8], l=16, topk=4)
    ws_ = fresh.query_scan_batch(queries[:8], l=16, topk=4)
    assert np.array_equal(gs.ids, survivors[ws_.ids])
    assert np.array_equal(gs.margins, ws_.margins)
    ok = ws_.ids_topk >= 0
    assert np.array_equal(gs.ids_topk[ok], survivors[ws_.ids_topk[ok]])
    assert (gs.ids_topk[~ok] == -1).all()

    # outstanding ids still resolve: delete by pre-compaction id works,
    # deleted/compacted-away ids are clearly rejected
    mt.delete(survivors[:10])
    assert mt.n == 990
    with pytest.raises(KeyError):
        mt.delete([0])                             # compacted away
    with pytest.raises(KeyError):
        mt.delete(survivors[:1])                   # tombstoned (not compacted)
    # masks are stable-id-indexed: restrict to the first 100 survivors
    mask = np.zeros(2000, dtype=bool)
    mask[survivors[100:200]] = True
    res = mt.query_scan_batch(queries[:8], l=32, mask=mask)
    assert mask[res.ids[res.ids >= 0]].all()


def test_auto_compaction_threshold(corpus, queries):
    mt = MultiTableIndex(_cfg(tables=2, compact_threshold=0.3)).fit(
        corpus.x[:100])
    mt.delete(np.arange(30))
    assert mt.compactions == 0 and mt.stats()["rows"] == 100  # at, not past
    mt.delete([30])
    assert mt.compactions == 1 and mt.stats()["rows"] == 69
    # fresh ids are assigned past the whole stable-id space, not per-row
    new = mt.insert(corpus.x[:2])
    assert list(new) == [100, 101]
    res = mt.query_batch(queries[:4])
    assert (res.ids >= 31).all()                   # stable ids reported
    # insert -> delete -> compact roundtrip on the new ids
    mt.delete(new)
    assert mt.compactions == 1                     # 2/71 < 0.3: no trigger...
    mt.compact()                                   # ...so compact manually
    assert mt.compactions == 2 and mt.stats()["rows"] == 69


def test_scan_after_50pct_churn_matches_fresh(corpus, queries):
    """Acceptance: 50%-delete churn + auto-compaction, then query_scan_batch
    answers match a freshly built index on the survivors, with stable ids."""
    mt = MultiTableIndex(_cfg(tables=2)).fit(corpus.x)   # default threshold
    victims = np.arange(0, 2000, 2)
    mt.delete(victims)                       # exactly 0.5: not past threshold
    assert mt.compactions == 0
    mt.delete([1])                           # 1001/2000 > 0.5: auto-compacts
    assert mt.compactions == 1
    keep = np.setdiff1d(np.arange(2000), np.r_[victims, 1])
    fresh = MultiTableIndex(_cfg(tables=2)).fit(corpus.x[keep])
    got = mt.query_scan_batch(queries, l=16)
    want = fresh.query_scan_batch(queries, l=16)
    assert np.array_equal(got.ids, keep[want.ids])
    assert np.array_equal(got.margins, want.margins)
    for b in range(queries.shape[0]):
        assert np.array_equal(got.candidates[b], keep[want.candidates[b]])
    svc = HashQueryService(mt, mode="scan", scan_l=16)
    assert [r.index for r in svc.query_batch(queries[:8])] \
        == got.ids[:8].tolist()


def test_index_stats(corpus):
    mt = MultiTableIndex(_cfg(tables=3)).fit(corpus.x)
    st = mt.stats()
    assert st["tables"] == 3 and len(st["per_table"]) == 3
    assert st["n"] == corpus.x.shape[0]
    assert all(s["n"] == corpus.x.shape[0] for s in st["per_table"])


def _dedup_oracle(index, w, l, topk, mask):
    """Plain-NumPy dedup, mask and hit count over the index's own per-table
    top-l (``scan_table_topk``), re-ranked through ``candidate_margins`` so
    margins share the program's per-row expression; ties break to the
    lower stable id, as the device re-rank does."""
    _, ids = index.scan_table_topk(w, l=l)              # (L, B, l) ids
    b = w.shape[0]
    hits = (ids >= 0).sum(axis=(1, 2))
    cands = [np.unique(ids[:, i][ids[:, i] >= 0]) for i in range(b)]
    width = max(1, max(c.size for c in cands))
    pad = np.full((b, width), -1, np.int64)
    for i, c in enumerate(cands):
        pad[i, :c.size] = c
    m = index.candidate_margins(w, pad)
    top = np.full((b, topk), -1, np.int64)
    top_m = np.full((b, topk), np.inf, np.float32)
    nonempty = np.zeros(b, bool)
    for i, c in enumerate(cands):
        keep = np.ones(c.size, bool) if mask is None else mask[c]
        nonempty[i] = keep.any()
        ci, mi = c[keep], m[i, :c.size][keep]
        order = np.lexsort((ci, mi))[:topk]
        top[i, :order.size], top_m[i, :order.size] = ci[order], mi[order]
    return top, top_m, nonempty, cands, hits


def _parity_index(kind, tables, state, x):
    """An index in one of the states the dedup program must handle."""
    if kind == "lsm":
        cfg = _cfg(tables=tables, lsm_auto=False)
        idx = LSMMultiTableIndex(cfg).fit(x[:400])
        idx.insert(x[400:600])                      # a delta segment
        idx.delete(np.r_[np.arange(0, 400, 7), np.arange(410, 600, 11)])
        return idx
    idx = MultiTableIndex(_cfg(tables=tables,
                               compact_threshold=None)).fit(x[:600])
    if state == "heavy_delete":
        idx.delete(np.flatnonzero(np.arange(600) % 10 != 3))   # 90% dead
    elif state == "compact":
        idx.delete(np.arange(0, 600, 2))
        idx.compact()
    return idx


@pytest.mark.parametrize("kind,tables,state,masked", [
    ("mono", 1, "fresh", False),
    ("mono", 1, "fresh", True),
    ("mono", 3, "fresh", False),
    ("mono", 3, "fresh", True),
    ("mono", 1, "heavy_delete", True),
    ("mono", 3, "heavy_delete", False),
    ("mono", 1, "compact", False),
    ("mono", 3, "compact", True),
    ("lsm", 1, "delta", True),
    ("lsm", 3, "delta", False),
    ("lsm", 3, "delta", True),
])
def test_scan_dedup_mask_parity_with_numpy(corpus, queries, kind, tables,
                                           state, masked):
    """query_scan_batch's compiled dedup, mask and hit count == a plain
    NumPy oracle of the same semantics: ids, margins, nonempty, candidate
    lists and table_hits exactly."""
    idx = _parity_index(kind, tables, state, corpus.x)
    w = queries[:8]
    mask = None
    if masked:
        mask = np.random.default_rng(7).random(idx._next_id) < 0.5
    l, topk = 16, 3
    res = idx.query_scan_batch(w, l=l, topk=topk, mask=mask)
    top, top_m, nonempty, cands, hits = _dedup_oracle(idx, w, l, topk, mask)
    assert np.array_equal(res.table_hits, hits)
    assert np.array_equal(res.nonempty, nonempty)
    assert np.array_equal(res.ids_topk, top)
    assert np.array_equal(res.margins_topk, top_m)
    assert np.array_equal(res.ids, top[:, 0])
    assert np.array_equal(res.margins, top_m[:, 0])
    assert len(res.candidates) == len(cands)
    for got, want in zip(res.candidates, cands):
        assert np.array_equal(got, want)
