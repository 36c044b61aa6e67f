"""No-retrace regression tests over the serving jit entrypoints.

The invariant (PR 3's QPS-cliff bug class, asserted here instead of
commented): steady-state serving traffic — tombstone flips, delta
appends, full compaction cycles, ragged async deadline flushes — must
add ZERO new jit traces once each shape bucket is warm.  The geometry
that makes this true: the sticky base pad bucket (compaction swaps never
shrink it), the delta-floor pad bucket, pow2 async batch bucketing, and
liveness masks as traced operands (never cache keys).

``trace_counter`` (tests/conftest.py) snapshots the trace-cache sizes of
every scan/rerank/hash entrypoint via repro.lint.runtime.TraceCounter;
the window asserts no entrypoint grew.  Runs unchanged on all three CI
legs — the counted targets cover the kernel and jnp paths alike.
"""
import numpy as np
import pytest

from repro.core.indexer import IndexConfig
from repro.serving import (AsyncHashQueryService, HashQueryService,
                           LSMMultiTableIndex, MultiTableIndex)

D = 16


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _lsm_cycle(idx, rng, queries):
    """One full mutate/query cycle: delta append -> scan -> tombstone
    flip -> scan -> full compaction fold -> scan."""
    ids = idx.insert(rng.normal(size=(40, D)).astype(np.float32))
    idx.query_scan_batch(queries, l=8, topk=2)
    idx.delete(ids[:10])
    idx.query_scan_batch(queries, l=8, topk=2)
    idx.compact()
    idx.query_scan_batch(queries, l=8, topk=2)


def test_lsm_mutation_cycle_no_retrace(trace_counter):
    rng = np.random.default_rng(2)
    # n=150 lands in the 256-row base bucket; cycle sizes keep every
    # post-compaction base (180, 210) inside it, and 40-row deltas share
    # the single delta-floor bucket — so cycle 2 revisits only warm shapes
    x = rng.normal(size=(150, D)).astype(np.float32)
    queries = rng.normal(size=(8, D)).astype(np.float32)
    cfg = IndexConfig(method="bh", bits=14, tables=2, seed=1, lsm_auto=False)
    idx = LSMMultiTableIndex(cfg).fit(x)

    _lsm_cycle(idx, rng, queries)            # cycle 1: traces warm here
    with trace_counter.assert_no_retrace():
        _lsm_cycle(idx, rng, queries)        # identical cycle 2: zero new


def test_refresh_swap_no_retrace(trace_counter):
    """A steady-state refresh — re-learn, shadow rebuild, generation swap —
    adds ZERO traces on the warm serving path.  The first refresh pays a
    one-time cost (the hash dispatch itself changes: seeded kernel ->
    materialized learned factors) and warms the shadow pre-swap; every
    refresh after that revisits only warm shapes: the shadow is pinned to
    the live sticky base bucket, `_install` hashes at the same pow2 row
    bucket as fit, catch-up hashes pad to pow2, and the swap is pure
    pointer flips."""
    rng = np.random.default_rng(5)
    # n=150 -> 256-row base bucket; every later base (180, 210, 240) and
    # the refresh snapshots stay inside it; 30-row deltas share the
    # delta-floor bucket; queries are a fixed (8, D) batch
    x = rng.normal(size=(150, D)).astype(np.float32)
    queries = rng.normal(size=(8, D)).astype(np.float32)
    cfg = IndexConfig(method="bh", bits=14, tables=2, seed=1, lsm_auto=False,
                      lbh_sample=64, lbh_steps=4)
    idx = LSMMultiTableIndex(cfg).fit(x)
    svc = HashQueryService(idx, max_batch=8, mode="scan", scan_l=8)

    def traffic():
        svc.query_batch(queries)
        svc.insert(rng.normal(size=(30, D)).astype(np.float32))
        svc.query_batch(queries)

    traffic()                        # generation-0 warm
    assert svc.refresh(wait=True)    # refresh 1: one-time learned-path warm
    traffic()                        # generation-1 warm (materialized hash)
    with trace_counter.assert_no_retrace():
        svc.query_batch(queries)
        svc.insert(rng.normal(size=(30, D)).astype(np.float32))
        assert svc.refresh(wait=True)   # refresh 2: zero new traces
        svc.query_batch(queries)
        svc.insert(rng.normal(size=(30, D)).astype(np.float32))
        svc.query_batch(queries)
    assert idx.generation == 2 and idx.refreshes == 2


def test_async_ragged_deadline_flushes_no_retrace(trace_counter):
    rng = np.random.default_rng(3)
    x = rng.normal(size=(200, D)).astype(np.float32)
    cfg = IndexConfig(method="bh", bits=14, tables=2, seed=1)
    idx = LSMMultiTableIndex(cfg).fit(x)
    clock = FakeClock()
    svc = AsyncHashQueryService(idx, max_batch=8, deadline_ms=5.0,
                                mode="scan", scan_l=8,
                                clock=clock, start=False)

    def ragged_round(sizes):
        for b in sizes:
            futs = [svc.submit(rng.normal(size=D).astype(np.float32))
                    for _ in range(b)]
            clock.advance(0.006)             # past deadline: ragged flush
                                             # (margin absorbs float drift)
            while svc.pump():
                pass
            for f in futs:
                f.result(timeout=60)

    # warm every pow2 bucket {1, 2, 4, 8} the bucketing can produce...
    ragged_round([1, 2, 3, 4, 5, 6, 7, 8])
    # ...then a differently-ragged round must hit only warm buckets
    with trace_counter.assert_no_retrace():
        ragged_round([3, 5, 1, 7, 2, 6, 8, 4])
    svc.close()


@pytest.mark.parametrize("use_kernels", [False, True])
def test_al_scan_rounds_no_retrace(trace_counter, use_kernels):
    """The active-learning round shape: a scan-mode service over the
    monolithic index answers a fixed-size batch of NEW hyperplanes under a
    NEW pool mask every round.  After warm-up, the hash, scan, dedup,
    mask and re-rank programs add zero traces: w and the mask are traced
    operands, never cache keys."""
    rng = np.random.default_rng(11)
    x = rng.normal(size=(300, D)).astype(np.float32)
    cfg = IndexConfig(method="bh", bits=20, tables=1, seed=1,
                      use_kernels=use_kernels)
    svc = HashQueryService(MultiTableIndex(cfg).fit(x), max_batch=8,
                           mode="scan", scan_l=16)
    unlabeled = np.ones(x.shape[0], bool)

    def al_round():
        res = svc.query_batch(rng.normal(size=(8, D)).astype(np.float32),
                              mask=unlabeled.copy())
        picks = [r.index for r in res if r.nonempty]
        unlabeled[picks] = False

    al_round()                               # warm-up
    with trace_counter.assert_no_retrace():
        for _ in range(4):
            al_round()
    assert not unlabeled.all()


def test_trace_counter_detects_a_real_retrace(trace_counter):
    """Sanity: the sentinel actually fires — a fresh shape through a
    counted entrypoint must register as a trace-cache growth."""
    from repro.core.search import merge_topk_segments
    import jax.numpy as jnp
    args = [jnp.zeros((1, 3, 4), jnp.int32), jnp.zeros((1, 3, 4), jnp.int32),
            jnp.zeros((1, 3, 4), jnp.int32), jnp.zeros((1, 3, 4), jnp.int32)]
    before = trace_counter.snapshot()
    merge_topk_segments(*args, 4)
    grew = trace_counter.deltas(before)
    assert grew.get("search.merge_topk_segments", 0) >= 0  # may be warm
    with pytest.raises(AssertionError, match="trace-stable"):
        with trace_counter.assert_no_retrace():
            merge_topk_segments(
                jnp.zeros((1, 3, 5), jnp.int32), jnp.zeros((1, 3, 5), jnp.int32),
                jnp.zeros((1, 3, 5), jnp.int32), jnp.zeros((1, 3, 5), jnp.int32),
                5)
