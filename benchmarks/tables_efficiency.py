"""Paper supplementary Tables 1-3 analogue: per-method preprocessing
(projection learning + database hashing) time and answer quality, the
device-scan path's per-query time, and kernel-vs-reference timing."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.indexer import HyperplaneIndex, IndexConfig
from repro.data.synthetic import tiny1m_like
from repro.kernels import ops, ref
from repro.serving import HashQueryService, MultiTableIndex


def _t(fn, *args, repeat=3):
    fn(*args)                                   # compile/warm
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = fn(*args)
    jax.block_until_ready(out) if hasattr(out, "block_until_ready") else None
    return (time.perf_counter() - t0) / repeat


def run(n=20000, d=96, queries=20):
    corpus = tiny1m_like(n_labeled=n, n_unlabeled=0, d=d, classes=10)
    x = corpus.x
    rng = np.random.default_rng(0)
    ws = rng.normal(size=(queries, x.shape[1])).astype(np.float32)
    rows = []
    print("method,fit_s,scan_ms,nonempty_frac,mean_margin_rank")
    for method in ("ah", "eh", "bh", "lbh"):
        cfg = IndexConfig(method=method,
                          bits=32 if method == "ah" else 16, radius=3,
                          lbh_sample=400, lbh_steps=60,
                          eh_sample_dims=min(64, d))
        idx = HyperplaneIndex(cfg).fit(x)
        margins_all = np.abs(x @ ws.T) / np.linalg.norm(ws, axis=1)
        scan_s = 0.0
        nonempty = 0
        ranks = []
        for qi in range(queries):
            res = idx.query(ws[qi])
            nonempty += int(res.nonempty)
            t0 = time.perf_counter()
            i2, m2 = idx.query_scan(ws[qi], l=32)
            scan_s += time.perf_counter() - t0
            ranks.append((margins_all[:, qi] < m2 - 1e-12).sum())
        print(f"{method},{idx.fit_s:.2f},{1e3*scan_s/queries:.2f},"
              f"{nonempty/queries:.2f},{np.mean(ranks):.1f}")
        rows.append((f"tbl_{method}_fit_s", idx.fit_s))
    return rows


def run_kernels(n=100_000, d=384, k=32):
    """Kernel path vs pure-jnp reference (CPU interpret mode timing is not
    TPU-meaningful; the derived column is the arithmetic-intensity /
    bytes-moved model that the TPU roofline uses)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    u = jnp.asarray(rng.normal(size=(d, k)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(d, k)).astype(np.float32))
    rows = []
    t_ref = _t(lambda: jax.block_until_ready(ref.bilinear_hash_ref(x, u, v)))
    codes = ref.bilinear_hash_ref(x, u, v)
    q = codes[0]
    t_ham_ref = _t(lambda: jax.block_until_ready(
        ref.hamming_distance_ref(codes, q)))
    flops = 2 * n * d * k * 2
    hbm = 4 * (n * d + 2 * d * k) + 4 * n * k / 8
    print("kernel,path,ms,derived")
    print(f"bilinear_hash,jnp_ref,{1e3*t_ref:.1f},"
          f"AI={flops/hbm:.1f}flops/byte")
    print(f"hamming_scan,jnp_ref,{1e3*t_ham_ref:.2f},"
          f"bytes={codes.size*4}")
    rows.append(("bilinear_ref_ms", 1e3 * t_ref))
    rows.append(("hamming_ref_ms", 1e3 * t_ham_ref))
    return rows


def run_serving(n=20000, d=96, batch=32, tables_sweep=(1, 2, 4, 8),
                bits=18, radius=3, repeat=5, recall_top=20):
    """QPS / latency / recall vs number of tables L, plus the batched-vs-
    sequential acceptance comparison: one `query_batch` of `batch` queries
    against `batch` sequential single-table `HyperplaneIndex.query` calls."""
    corpus = tiny1m_like(n_labeled=n, n_unlabeled=0, d=d, classes=10)
    x = corpus.x
    rng = np.random.default_rng(0)
    ws = rng.normal(size=(batch, x.shape[1])).astype(np.float32)
    margins_all = np.abs(x @ ws.T) / np.linalg.norm(ws, axis=1)

    # sequential baseline: the seed-era path, one table, one query at a time
    cfg1 = IndexConfig(method="bh", bits=bits, radius=radius)
    hi = HyperplaneIndex(cfg1).fit(x, learn_key=None)
    for w in ws:                                   # warm the jit caches
        hi.query(w)
    t0 = time.perf_counter()
    for w in ws:
        hi.query(w)
    seq_s = time.perf_counter() - t0

    rows = []
    batch1_s = None
    print("tables,fit_s,batch_ms,seq_ms,qps,recall@%d,nonempty_frac,"
          "cache_qps" % recall_top)
    for L in tables_sweep:
        cfg = IndexConfig(method="bh", bits=bits, radius=radius, tables=L,
                          batch=batch)
        mt = MultiTableIndex(cfg).fit(x)
        svc = HashQueryService(mt)
        svc.query_batch(ws)                        # warm
        t0 = time.perf_counter()
        for _ in range(repeat):
            res = mt.query_batch(ws)
        batch_s = (time.perf_counter() - t0) / repeat
        hits = sum(1 for b in range(batch)
                   if res.nonempty[b]
                   and (margins_all[:, b] < res.margins[b] - 1e-12).sum()
                   < recall_top)
        t0 = time.perf_counter()
        svc.query_batch(ws)                        # all query codes cached
        cache_s = time.perf_counter() - t0
        print(f"{L},{mt.fit_s:.2f},{1e3*batch_s:.2f},{1e3*seq_s:.2f},"
              f"{batch/batch_s:.0f},{hits/batch:.2f},"
              f"{res.nonempty.mean():.2f},{batch/cache_s:.0f}")
        rows.append((f"serving_L{L}_batch_ms", 1e3 * batch_s))
        rows.append((f"serving_L{L}_qps", batch / batch_s))
        if L == 1:
            batch1_s = batch_s
    # like-for-like acceptance check: one L=1 batch vs the same number of
    # sequential single-table queries (only meaningful when L=1 was swept)
    if batch1_s is not None:
        speedup = seq_s / batch1_s
        print(f"# batched {batch}-query batch vs {batch} sequential queries "
              f"(both single-table): {speedup:.1f}x "
              f"{'FASTER' if speedup > 1 else 'SLOWER'}")
        rows.append(("serving_batch_speedup", speedup))
    return rows


if __name__ == "__main__":
    run()
    run_kernels()
    run_serving()
