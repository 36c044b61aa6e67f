"""SVM active learning with hash-accelerated min-margin selection (paper §5).

Protocol (faithful to the paper's setup):
- start from a small labeled seed (init_per_class per class);
- at every AL iteration, each class's one-vs-all SVM issues one hyperplane
  query; the returned min-margin point is added to the shared labeled pool
  with its true label; all SVMs are then retrained (warm-started);
- metrics: MAP over the remaining unlabeled pool, the selected points'
  margins (vs. the exhaustive optimum), and per-class nonempty-lookup counts;
- an empty hash lookup falls back to random selection (paper §5.2).

Selectors: random / exhaustive (the two baselines) and one per hash family
(AH, EH, BH, LBH) through a MultiTableIndex built once over the pool and
fronted by a HashQueryService — the C per-iteration hyperplane queries are
issued as ONE micro-batch (hashing, multi-probe and re-rank all batched)
instead of C serial single-query passes.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.indexer import IndexConfig
from repro.data.synthetic import Corpus
from repro.serving.async_service import AsyncHashQueryService
from repro.serving.multi_table import MultiTableIndex
from repro.serving.service import HashQueryService
from repro.svm.linear_svm import average_precision, train_ova


@dataclasses.dataclass
class ALConfig:
    iterations: int = 100
    init_per_class: int = 5
    svm_steps: int = 20
    svm_l2: float = 1e-3
    svm_lr: float = 0.5
    eval_every: int = 10
    seed: int = 0


@dataclasses.dataclass
class ALResult:
    name: str
    eval_iters: np.ndarray     # iterations at which MAP was computed
    map_curve: np.ndarray      # (len(eval_iters),)
    min_margins: np.ndarray    # (iterations,) mean selected margin per iter
    exhaustive_margins: np.ndarray  # (iterations,) mean optimal margin
    nonempty: np.ndarray       # (C,) nonempty lookups per class
    select_seconds: float
    total_seconds: float
    fit_seconds: float = 0.0


# ---------------------------------------------------------------------------
# Selectors
# ---------------------------------------------------------------------------

class RandomSelector:
    name = "random"

    def __init__(self, seed: int = 0):
        self.rng = np.random.default_rng(seed)

    def prepare(self, corpus: Corpus):
        return self

    def select(self, c: int, w: np.ndarray, unlabeled: np.ndarray):
        pool = np.flatnonzero(unlabeled)
        return int(self.rng.choice(pool)), True

    def select_batch(self, w_all: np.ndarray, unlabeled: np.ndarray):
        out = [self.select(c, w_all[c], unlabeled)
               for c in range(w_all.shape[0])]
        return [i for i, _ in out], [ok for _, ok in out]


class ExhaustiveSelector:
    name = "exhaustive"

    def prepare(self, corpus: Corpus):
        self.x = jnp.asarray(corpus.x)
        return self

    def select_all(self, w_all: jnp.ndarray, unlabeled: np.ndarray):
        """(C,) argmin-margin indices over the unlabeled pool, per class.

        The products run at full f32 precision: the TPU's default matmul
        rounds operands to bf16, which can miss the true minimum."""
        margins = jnp.abs(jnp.matmul(self.x, w_all.T, precision="highest"))
        # (n, C); ||w|| drops in the argmin
        margins = jnp.where(jnp.asarray(unlabeled)[:, None], margins, jnp.inf)
        return np.asarray(jnp.argmin(margins, axis=0))

    def select(self, c: int, w, unlabeled: np.ndarray):
        m = jnp.abs(jnp.matmul(self.x, w, precision="highest"))
        m = jnp.where(jnp.asarray(unlabeled), m, jnp.inf)
        return int(jnp.argmin(m)), True

    def select_batch(self, w_all: np.ndarray, unlabeled: np.ndarray):
        picks = self.select_all(jnp.asarray(w_all), unlabeled)
        return [int(i) for i in picks], [True] * len(picks)


class HashSelector:
    """Min-margin selection through a MultiTableIndex + HashQueryService.

    All C per-iteration hyperplane queries go through the service as one
    micro-batch; an empty (post-mask) lookup falls back to random selection
    exactly as the paper prescribes (§5.2).

    With ``use_async`` each learner submits its own query to an
    AsyncHashQueryService (future per class — the paper's C concurrent
    learners, each unaware of the others) and the deadline-flush loop
    coalesces them into shared device launches; ``flush()`` after the
    submit burst bounds the last learner's wait.  Answers are bit-identical
    to the synchronous batch.
    """

    def __init__(self, index_config: IndexConfig, seed: int = 0,
                 use_async: bool = False, deadline_ms: float = 2.0):
        self.config = index_config
        self.name = index_config.method
        self.rng = np.random.default_rng(seed)
        self.use_async = use_async
        self.deadline_ms = deadline_ms
        self.index: MultiTableIndex | None = None
        self.service: HashQueryService | AsyncHashQueryService | None = None

    def prepare(self, corpus: Corpus):
        self.index = MultiTableIndex(self.config).fit(corpus.x)
        if self.use_async:
            self.service = AsyncHashQueryService(
                self.index, max_batch=self.config.batch,
                deadline_ms=self.deadline_ms)
        else:
            self.service = HashQueryService(self.index,
                                            max_batch=self.config.batch)
        return self

    def finish(self) -> None:
        """Release the flush thread (async mode); sync mode is a no-op."""
        if isinstance(self.service, AsyncHashQueryService):
            self.service.close()

    def select(self, c: int, w, unlabeled: np.ndarray):
        picks, oks = self.select_batch(
            np.asarray(w, np.float32)[None, :], unlabeled)
        return picks[0], oks[0]

    def select_batch(self, w_all: np.ndarray, unlabeled: np.ndarray):
        if isinstance(self.service, AsyncHashQueryService):
            # each class = one independent learner submitting its own query;
            # the service coalesces the burst into shared launches
            futures = [self.service.submit(w_all[c], mask=unlabeled)
                       for c in range(w_all.shape[0])]
            self.service.flush()
            results = [f.result() for f in futures]
        else:
            results = self.service.query_batch(w_all, mask=unlabeled)
        picks, oks = [], []
        for res in results:
            if res.nonempty:
                picks.append(res.index)
                oks.append(True)
            else:
                picks.append(int(self.rng.choice(np.flatnonzero(unlabeled))))
                oks.append(False)
        return picks, oks


def make_selector(method: str, *, bits: int, radius: int, seed: int = 0,
                  use_async: bool = False, deadline_ms: float = 2.0,
                  **index_kw):
    if method == "random":
        return RandomSelector(seed)
    if method == "exhaustive":
        return ExhaustiveSelector()
    # The paper doubles AH's bits (dual-bit hashing spirit).
    eff_bits = 2 * bits if method == "ah" else bits
    cfg = IndexConfig(method=method, bits=eff_bits, radius=radius, seed=seed,
                      **index_kw)
    return HashSelector(cfg, seed, use_async=use_async,
                        deadline_ms=deadline_ms)


# ---------------------------------------------------------------------------
# The AL loop
# ---------------------------------------------------------------------------

def run_active_learning(corpus: Corpus, selector, config: ALConfig) -> ALResult:
    t_start = time.perf_counter()
    selector.prepare(corpus)
    fit_s = getattr(getattr(selector, "index", None), "fit_s", 0.0)

    x = jnp.asarray(corpus.x)
    labels = jnp.asarray(corpus.y)
    n, d = corpus.x.shape
    c_num = corpus.num_classes
    rng = np.random.default_rng(config.seed)

    labeled = np.zeros(n, bool)
    for c in range(c_num):
        idx = np.flatnonzero(corpus.y == c)
        labeled[rng.choice(idx, min(config.init_per_class, idx.size),
                           replace=False)] = True

    w_all = jnp.zeros((c_num, d), jnp.float32)
    w_all = train_ova(w_all, x, labels, jnp.asarray(labeled), c_num,
                      l2=config.svm_l2, steps=5 * config.svm_steps,
                      lr=config.svm_lr)

    exhaustive = ExhaustiveSelector().prepare(corpus)
    x_np = corpus.x
    norms_w = lambda W: np.maximum(np.linalg.norm(W, axis=1), 1e-12)

    eval_iters, map_curve = [], []
    min_margins, exh_margins = [], []
    nonempty = np.zeros(c_num, np.int64)
    select_s = 0.0

    # x and labels are operands, not closed-over constants: a constant the
    # size of the corpus would be embedded in the compiled program
    @jax.jit
    def mean_ap(w_all, labeled_mask, x, labels):
        unl = ~labeled_mask
        scores = x @ w_all.T                       # (n, C)
        def ap_c(c):
            pos = (labels == c) & unl
            s = jnp.where(unl, scores[:, c], -jnp.inf)
            return average_precision(s, pos)
        return jnp.mean(jax.vmap(ap_c)(jnp.arange(c_num)))

    def record_eval(it):
        eval_iters.append(it)
        map_curve.append(float(mean_ap(w_all, jnp.asarray(labeled), x,
                                       labels)))

    record_eval(0)
    try:
        for it in range(1, config.iterations + 1):
            w_np = np.asarray(w_all)
            nw = norms_w(w_np)
            unlabeled = ~labeled

            t0 = time.perf_counter()
            if hasattr(selector, "select_batch"):
                # all C hyperplane queries answered as one micro-batch
                picks, oks = selector.select_batch(w_np, unlabeled)
                nonempty += np.asarray(oks, dtype=np.int64)
            else:
                picks = []
                for c in range(c_num):
                    idx, ok = selector.select(c, w_np[c], unlabeled)
                    picks.append(idx)
                    nonempty[c] += int(ok)
            select_s += time.perf_counter() - t0

            # metrics: achieved vs optimal margin this round
            opt = exhaustive.select_all(w_all, unlabeled)
            sel_m = [abs(float(x_np[i] @ w_np[c])) / nw[c]
                     for c, i in enumerate(picks)]
            opt_m = [abs(float(x_np[i] @ w_np[c])) / nw[c]
                     for c, i in enumerate(opt)]
            min_margins.append(float(np.mean(sel_m)))
            exh_margins.append(float(np.mean(opt_m)))

            labeled[np.asarray(picks)] = True
            w_all = train_ova(w_all, x, labels, jnp.asarray(labeled), c_num,
                              l2=config.svm_l2, steps=config.svm_steps,
                              lr=config.svm_lr)
            if it % config.eval_every == 0 or it == config.iterations:
                record_eval(it)
    finally:
        if hasattr(selector, "finish"):
            selector.finish()       # async selectors release their thread

    return ALResult(
        name=selector.name,
        eval_iters=np.asarray(eval_iters),
        map_curve=np.asarray(map_curve),
        min_margins=np.asarray(min_margins),
        exhaustive_margins=np.asarray(exh_margins),
        nonempty=nonempty,
        select_seconds=select_s,
        total_seconds=time.perf_counter() - t_start,
        fit_seconds=fit_s,
    )
