"""Bring-up check on a TPU: the Tiny-1M active-learning deployment (paper
Fig. 4) through the normal entry points, with compiled Pallas kernels.

    python chip_smoke.py [--seed S]       # one chip
    python chip_smoke.py --chips 4        # row-sharded scan over 4 chips

One chip: a Tiny-1M-like corpus (1.06M rows x 385 features, 10 classes) is
indexed with 20-bit BH codes by ``make_selector("bh", ...)``; three
active-learning iterations each send C = 10 masked hyperplane queries
through the probe backend, then the scan backend answers the same ten at
scan depth 128.  The same run with ``use_kernels=False`` is the reference:
codes, top-l ids, picks and margins must agree bit for bit, every pick must
be unlabeled, and its margin (recomputed in float64) must be no smaller
than the exhaustive minimum.

``--chips 4``: only the multi-chip path: an index fitted on the corpus
row-sharded over 4 chips (``HashQueryService(mode="scan")`` over it),
against the single-chip index.

Timings printed here are bring-up readings, not benchmark numbers.  Any
fault raises; the last line of a passing run is one JSON object naming the
device.  There is no CPU fallback: without a TPU the script exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

C = 10            # classes = hyperplane queries per AL iteration
SCAN_L = 128      # scan depth of the scan backend


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, msg: str) -> None:
    """Fail the run (not an assert: the checks must hold under -O too)."""
    if not ok:
        raise RuntimeError(msg)


def _tiny1m(seed: int, n_labeled: int = 60000, n_unlabeled: int = 1000000,
            d: int = 384):
    from repro.data.synthetic import tiny1m_like
    return tiny1m_like(n_labeled=n_labeled, n_unlabeled=n_unlabeled, d=d,
                       classes=C, seed=seed)


class _Recorder:
    """Selector wrapper that keeps every batch the AL loop sends and every
    answer the service gives (the loop itself reports only means)."""

    def __init__(self, inner):
        self.inner = inner
        self.name = inner.name
        self.rounds = []            # (w_all, unlabeled, results, picks)

    def prepare(self, corpus):
        self.inner.prepare(corpus)
        self.index = self.inner.index
        return self

    def select_batch(self, w_all, unlabeled):
        svc = self.inner.service
        answers = []
        query_batch = svc.query_batch

        def recorded(ws, mask=None):
            res = query_batch(ws, mask=mask)
            answers.extend(res)
            return res

        svc.query_batch = recorded
        try:
            picks, oks = self.inner.select_batch(w_all, unlabeled)
        finally:
            del svc.query_batch
        self.rounds.append((np.array(w_all), unlabeled.copy(), answers,
                            list(picks)))
        return picks, oks

    def finish(self):
        self.inner.finish()


def run_al(corpus, use_kernels: bool, seed: int, iters: int):
    """make_selector -> prepare -> `iters` AL iterations (probe backend)."""
    from repro.svm.active import ALConfig, make_selector, run_active_learning
    sel = _Recorder(make_selector("bh", bits=20, radius=4, seed=seed,
                                  use_kernels=use_kernels))
    t0 = time.perf_counter()
    res = run_active_learning(corpus, sel, ALConfig(
        iterations=iters, eval_every=iters, seed=seed))
    return sel, res, time.perf_counter() - t0


def _f64_margin(x, w, row):
    """|x_row . w| / ||w|| in float64, and the float32 rounding bound of
    the same expression (d·eps·sum|x_i w_i| / ||w||, the standard dot
    product bound, plus the division's rounding)."""
    xr, w = np.asarray(x[row], np.float64), np.asarray(w, np.float64)
    norm = max(float(np.linalg.norm(w)), 1e-12)
    margin = abs(float(xr @ w)) / norm
    eps = float(np.finfo(np.float32).eps)
    bound = w.size * eps * float(np.abs(xr) @ np.abs(w)) / norm \
        + 2 * eps * margin
    return margin, bound


def check_answers(corpus, rec) -> None:
    """Every pick unlabeled; reported margins match a float64 recompute
    within the float32 rounding bound and are no smaller than the
    exhaustive minimum (up to that bound)."""
    from repro.svm.active import ExhaustiveSelector
    exhaustive = ExhaustiveSelector().prepare(corpus)
    for it, (w_all, unlabeled, answers, picks) in enumerate(rec.rounds, 1):
        opt = exhaustive.select_all(jnp.asarray(w_all), unlabeled)
        for c, (ans, pick) in enumerate(zip(answers, picks)):
            where = f"iter {it} class {c} row {pick}"
            check(unlabeled[pick], f"{where}: pick is labeled")
            check(ans.nonempty and ans.index == pick,
                  f"{where}: empty lookup or answer {ans.index}")
            m64, tol = _f64_margin(corpus.x, w_all[c], pick)
            check(abs(m64 - ans.margin) <= tol,
                  f"{where}: margin {ans.margin} vs f64 {m64} (tol {tol})")
            best, tol_best = _f64_margin(corpus.x, w_all[c], int(opt[c]))
            check(m64 >= best - tol - tol_best,
                  f"{where}: margin {m64} below exhaustive {best}")


def count_mismatches(rec_k, rec_r, idx_k, idx_r, scan_k, scan_r,
                     topl_k, topl_r) -> dict:
    """Element mismatches between the kernel path and the reference."""
    mm = {"codes": int(sum((a != b).sum()
                           for a, b in zip(idx_k.codes, idx_r.codes)))}
    mm["rounds"] = int(len(rec_k.rounds) != len(rec_r.rounds))
    picks = margins = 0
    for (wk, uk, ak, pk), (wr, ur, ar, pr) in zip(rec_k.rounds, rec_r.rounds):
        mm["rounds"] += int(not np.array_equal(wk, wr)
                            or not np.array_equal(uk, ur))
        picks += sum(int(a != b) for a, b in zip(pk, pr))
        margins += sum(int(np.float32(a.margin) != np.float32(b.margin))
                       for a, b in zip(ak, ar))
    mm["picks"], mm["margins"] = picks, margins
    mm["topl_dists"] = int((topl_k[0] != topl_r[0]).sum())
    mm["topl_ids"] = int((topl_k[1] != topl_r[1]).sum())
    mm["scan_picks"] = sum(int(a.index != b.index)
                           for a, b in zip(scan_k, scan_r))
    mm["scan_margins"] = sum(int(np.float32(a.margin) != np.float32(b.margin))
                             for a, b in zip(scan_k, scan_r))
    mm["scan_candidates"] = sum(int(not np.array_equal(a.candidates,
                                                       b.candidates))
                                for a, b in zip(scan_k, scan_r))
    return mm


def check_lowering(index, w, unlabeled) -> None:
    """The hash and scan calls of the kernel path lower to Mosaic custom
    calls, not to the Pallas interpreter."""
    from repro.kernels import ops
    from repro.serving import batch_query as bq
    seeds = jnp.asarray([f.seed for f in index.families], jnp.uint32)
    hash_txt = ops.bilinear_hash_seeded_grouped.lower(
        jnp.asarray(w), seeds, index.config.bits).as_text()
    codes_dev, _ = index._scan_state()
    qcodes = bq.hash_queries_all(index.families, w, use_kernels=True)
    active = jnp.asarray(unlabeled[index._live_rows])
    scan_txt = jax.jit(lambda c, q, a: ops.hamming_topk_lanes(
        c, q, SCAN_L, index._n_live, active=a)).lower(
        codes_dev, qcodes, active).as_text()
    for name, txt in (("hash", hash_txt), ("scan", scan_txt)):
        check("tpu_custom_call" in txt,
              f"{name} call has no Mosaic kernel")


def one_chip(seed: int, corpus=None, iters: int = 3, lowering: bool = True):
    from repro.serving.service import HashQueryService
    t0 = time.perf_counter()
    corpus = _tiny1m(seed) if corpus is None else corpus
    n, d = corpus.x.shape
    log(f"corpus: {n} rows x {d} features ({corpus.x.nbytes / 1e9:.2f} GB "
        f"f32), made in {time.perf_counter() - t0:.1f}s")

    rec_k, res_k, al_k = run_al(corpus, True, seed, iters)
    idx_k = rec_k.index
    log(f"bring-up: kernel path index build {idx_k.fit_s:.2f}s (first call,"
        f" compile included); {iters} AL iterations end to end {al_k:.1f}s, "
        f"selection {res_k.select_seconds:.2f}s")
    check_answers(corpus, rec_k)
    rec_r, res_r, al_r = run_al(corpus, False, seed, iters)
    idx_r = rec_r.index
    log(f"bring-up: reference (use_kernels=False) index build "
        f"{idx_r.fit_s:.2f}s; {iters} AL iterations {al_r:.1f}s")

    # scan backend: the last iteration's hyperplanes with its unlabeled mask
    w_all, unlabeled = rec_k.rounds[-1][0], rec_k.rounds[-1][1]
    svc_k = HashQueryService(idx_k, mode="scan", scan_l=SCAN_L)
    svc_r = HashQueryService(idx_r, mode="scan", scan_l=SCAN_L)
    t0 = time.perf_counter()
    scan_k = svc_k.query_batch(w_all, mask=unlabeled)
    first = time.perf_counter() - t0
    walls = []
    for _ in range(5):
        t0 = time.perf_counter()
        scan_k = svc_k.query_batch(w_all, mask=unlabeled)
        walls.append(time.perf_counter() - t0)
    log(f"bring-up: scan batch of {C} masked queries at l={SCAN_L}: first "
        f"call {first:.3f}s (compile included), then "
        f"{sorted(walls)[len(walls) // 2] * 1e3:.2f} ms median wall of 5")
    scan_r = svc_r.query_batch(w_all, mask=unlabeled)
    topl_k = idx_k.scan_table_topk(w_all, l=SCAN_L)
    topl_r = idx_r.scan_table_topk(w_all, l=SCAN_L)

    mm = count_mismatches(rec_k, rec_r, idx_k, idx_r, scan_k, scan_r,
                          topl_k, topl_r)
    log("mismatches kernel vs reference: " + json.dumps(mm))
    check(not any(mm.values()),
          f"kernel path differs from reference: {mm}")
    for a in scan_k:
        check(a.nonempty and unlabeled[a.index],
              "scan pick is labeled")
    if lowering:
        check_lowering(idx_k, w_all, unlabeled)
        log("lowering: hash and scan calls contain tpu_custom_call")


def four_chips(seed: int, corpus=None) -> None:
    """An index fitted on the corpus row-sharded over a 4-chip mesh vs the
    single-chip index."""
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.core.indexer import IndexConfig
    from repro.serving.multi_table import MultiTableIndex
    from repro.serving.service import HashQueryService
    devices = jax.devices()
    check(len(devices) == 4,
          f"--chips 4 needs 4 devices, found {len(devices)}")
    mesh = Mesh(np.array(devices), ("data",), axis_types=(AxisType.Auto,))
    corpus = _tiny1m(seed) if corpus is None else corpus
    cfg = IndexConfig(method="bh", bits=20, radius=4, seed=seed)
    index = MultiTableIndex(cfg).fit(corpus.x)
    xs = jax.device_put(jnp.asarray(corpus.x),
                        NamedSharding(mesh, P("data", None)))
    t0 = time.perf_counter()
    sharded_index = MultiTableIndex(cfg).fit(xs)
    log(f"bring-up: sharded index build {time.perf_counter() - t0:.2f}s")
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(C, corpus.x.shape[1])).astype(np.float32)
    unlabeled = corpus.y < 0
    single = HashQueryService(index, mode="scan", scan_l=SCAN_L)
    sharded = HashQueryService(sharded_index, mode="scan", scan_l=SCAN_L)
    t0 = time.perf_counter()
    sharded.query_batch(w, mask=unlabeled)
    log(f"bring-up: sharded scan first call {time.perf_counter() - t0:.3f}s "
        f"(compile included)")
    codes_dev, _ = sharded_index._scan_state()
    placement = sorted(str(s.device) for s in codes_dev.addressable_shards)
    log(f"code shards: {len(codes_dev.sharding.device_set)} devices "
        f"{placement}")
    check(len(set(placement)) == 4,
          f"shards not one per chip: {placement}")
    check(sharded_index.x is xs, "the sharded features were copied")
    n = corpus.x.shape[0]
    for step in ("fit", "delete"):
        if step == "delete":     # a tenth of the rows, flagged dead on chip
            gone = rng.choice(n, n // 10, replace=False)
            index.delete(gone)
            sharded_index.delete(gone)
        ref = single.query_batch(w, mask=unlabeled)
        ref_topl = index.scan_table_topk(w, l=SCAN_L)
        got = sharded.query_batch(w, mask=unlabeled)
        got_topl = sharded_index.scan_table_topk(w, l=SCAN_L)
        mm = {
            "picks": sum(int(a.index != b.index) for a, b in zip(got, ref)),
            "margins": sum(int(np.float32(a.margin) != np.float32(b.margin))
                           for a, b in zip(got, ref)),
            "candidates": sum(int(not np.array_equal(a.candidates,
                                                     b.candidates))
                              for a, b in zip(got, ref)),
            "topl_dists": int((got_topl[0] != ref_topl[0]).sum()),
            "topl_ids": int((got_topl[1] != ref_topl[1]).sum()),
        }
        log(f"mismatches sharded vs single-chip after {step}: "
            + json.dumps(mm))
        check(not any(mm.values()), f"sharded scan differs: {mm}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (default device is {dev.platform}); "
              f"nothing to check", file=sys.stderr)
        return 2
    from repro.utils.compile_cache import enable_compile_cache
    log(f"device: {dev.device_kind} x{len(jax.devices())}; compile cache "
        f"{enable_compile_cache()}")
    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    stats = dev.memory_stats() or {}
    log(f"bring-up: peak HBM {stats.get('peak_bytes_in_use', 0) / 2**30:.2f}"
        f" GiB of {stats.get('bytes_limit', 0) / 2**30:.2f} GiB")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
