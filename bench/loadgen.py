"""The one traffic generator.  A traffic mix is a JSON file of parameters
under ``bench/traffic/``; its ``loop`` names the shape of the load, and
``closed_al`` is the one there is: one active-learning learner.  Each round
sends the C hyperplanes of its one-vs-rest classifiers as one masked batch,
waits for the picks, and takes them out of the unlabeled pool.

Hyperplanes come from a pool made once in set-up: one-vs-rest centroid
classifiers over the labeled rows (the stand-in for a retrained SVM), each
plus Gaussian noise of ``perturbation`` times its norm, spread over the
dimensions (the stand-in for one round of retraining).
"""
from __future__ import annotations

import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _centroid_classifiers(x, y, classes_onehot_ids):
    """w_c = mean of class c minus mean of the other labeled rows."""
    onehot = (y[:, None] == classes_onehot_ids[None, :]).astype(jnp.float32)
    sums = jnp.dot(onehot.T, x, precision=jax.lax.Precision.HIGHEST)
    cnt = onehot.sum(axis=0)[:, None]
    total, n_lab = sums.sum(axis=0, keepdims=True), cnt.sum()
    return sums / cnt - (total - sums) / (n_lab - cnt)


def hyperplane_pool(x, y, classes: int, spec: dict, seed: int):
    """(P, C, d) float32 host array of perturbed classifiers; P is the
    number of rounds that fit ``pool_bytes``, at least 8."""
    d = x.shape[1]
    base = _centroid_classifiers(x, y, jnp.arange(classes, dtype=y.dtype))
    p = int(np.clip(spec["pool_bytes"] // (classes * d * 4), 8, 512))
    key = jax.random.fold_in(jax.random.PRNGKey(1), seed % (1 << 32))
    noise = jax.random.normal(key, (p, classes, d), jnp.float32)
    scale = spec["perturbation"] * jnp.linalg.norm(base, axis=1) / np.sqrt(d)
    return np.asarray(base[None] + scale[None, :, None] * noise)


def initial_unlabeled(y_host: np.ndarray, classes: int, per_class: int,
                      rng: np.random.Generator) -> np.ndarray:
    """The pool mask: every row except ``per_class`` labeled seeds per
    class."""
    unlabeled = np.ones(y_host.shape[0], bool)
    for c in range(classes):
        idx = np.flatnonzero(y_host == c)
        unlabeled[rng.choice(idx, min(per_class, idx.size),
                             replace=False)] = False
    return unlabeled


def closed_al(service, pool: np.ndarray, initial: np.ndarray,
              rounds_per_learner: int, seconds: float,
              rng: np.random.Generator):
    """Rounds back to back until ``seconds`` have passed; the round that
    crosses the end finishes and counts.  Every ``rounds_per_learner``
    rounds a new learner starts from the initial pool, so no run uses up
    the candidates near its hyperplanes and the work per round is the same
    over any window.  An answer with no unmasked candidate falls back to a
    uniform unlabeled row, drawn by rejection (O(1), whatever the pool
    size).  Returns (window seconds, rounds); each round records its pool
    entry, whether it restarted, its answers, its picks and its time."""
    rounds = []
    order = rng.integers(0, pool.shape[0], size=1 << 16)
    unlabeled = initial.copy()
    n = initial.shape[0]

    def fallback():
        while True:
            i = int(rng.integers(n))
            if unlabeled[i]:
                return i

    t0 = time.perf_counter()
    while True:
        j = int(order[len(rounds) % order.size])
        restart = len(rounds) % rounds_per_learner == 0
        ts = time.perf_counter()
        if restart:
            unlabeled[:] = initial
        res = service.query_batch(pool[j], mask=unlabeled)
        picks = np.asarray([r.index if r.nonempty else fallback()
                            for r in res], np.int64)
        unlabeled[picks] = False
        te = time.perf_counter()
        rounds.append(SimpleNamespace(j=j, t=te - ts, answers=res,
                                      picks=picks, restart=restart))
        if te - t0 >= seconds:
            return te - t0, rounds


def replay_masks(initial: np.ndarray, rounds: list, keep) -> int:
    """Walk the rounds again: set ``mask`` (the pool each round was asked
    with) on the rounds whose index is in ``keep``, and return how many
    unmasked candidates all rounds re-ranked."""
    unlabeled = initial.copy()
    reranked = 0
    for i, x in enumerate(rounds):
        if x.restart:
            unlabeled[:] = initial
        reranked += sum(int(np.count_nonzero(unlabeled[a.candidates]))
                        for a in x.answers)
        if i in keep:
            x.mask = unlabeled.copy()
        unlabeled[x.picks] = False
    return reranked
