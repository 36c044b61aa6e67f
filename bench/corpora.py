"""Corpora made on the device from the run's seed, one jitted call each.

They follow the program's own stand-ins for the paper's two data sets
(``repro.data.synthetic.tiny1m_like`` / ``newsgroups_like``) in
distribution, but draw from ``jax.random`` on the device instead of NumPy
on the host, so a 1.06M-row corpus costs a fraction of a second of set-up.
What changed from the host generators is listed under ``assumed`` in each
configuration file.  A configuration names its generator in
``corpus.generator``; the keyword arguments are the rest of that object.
These two are the built-ins (``GENERATORS``); any other name is a file
``bench/generators/<name>.py`` (``harness.make_corpus``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _bias_normalize(x):
    """Append the bias feature 1 and l2-normalize each row (paper §2)."""
    x = jnp.concatenate([x, jnp.ones((x.shape[0], 1), x.dtype)], axis=1)
    return x / jnp.maximum(jnp.linalg.norm(x, axis=1, keepdims=True), 1e-12)


@partial(jax.jit, static_argnames=("n_labeled", "n_unlabeled", "d",
                                   "classes"))
def tiny1m(key, *, n_labeled: int, n_unlabeled: int, d: int, classes: int):
    """Dense GIST-like rows: ``classes`` Gaussian classes of
    n_labeled/classes rows each around unit means, plus an unlabeled tail
    (label -1) pushed away from the class centroid.  Rows come in a random
    order.  Returns (x (n, d+1) f32, y (n,) int32)."""
    k_mean, k_scale, k_pos, k_z = jax.random.split(key, 4)
    means = jax.random.normal(k_mean, (classes, d), jnp.float32)
    means = means / jnp.linalg.norm(means, axis=1, keepdims=True)
    scales = 0.25 + 0.15 * jax.random.uniform(k_scale, (classes, d))
    per = n_labeled // classes
    y = jnp.concatenate([jnp.repeat(jnp.arange(classes, dtype=jnp.int32),
                                    per),
                         jnp.full((n_unlabeled,), -1, jnp.int32)])
    y = jax.random.permutation(k_pos, y)
    z = jax.random.normal(k_z, (y.shape[0], d), jnp.float32)
    c = jnp.maximum(y, 0)
    centroid = means.mean(axis=0)
    x = jnp.where((y >= 0)[:, None], means[c] + scales[c] * z,
                  0.9 * (z - 0.8 * centroid[None, :]))
    return _bias_normalize(x), y


def _zipf_clipped_logits(a: float, cap: int) -> np.ndarray:
    """log P(k), k = 1..cap, of NumPy's ``zipf(a).clip(max=cap)``: the mass
    past cap collects at cap."""
    k = np.arange(1, cap, dtype=np.float64)
    tail = np.arange(cap, 2_000_000, dtype=np.float64)
    zeta = np.sum(k ** -a) + np.sum(tail ** -a) + tail[-1] ** (1 - a) / (a - 1)
    p = np.append(k ** -a / zeta, 0.0)
    p[-1] = 1.0 - p.sum()
    return np.log(p)


@partial(jax.jit, static_argnames=("n", "d", "classes", "topics_per_class",
                                   "terms_per_doc"))
def newsgroups(key, *, n: int, d: int, classes: int, topics_per_class: int,
               terms_per_doc: int):
    """Sparse tf-idf rows stored dense: each document draws terms_per_doc
    words, from its class's topic words with 12x the background weight,
    with Zipf(1.6) counts clipped at 20; then tf-idf and the bias.
    Returns (x (n, d+1) f32, y (n,) int32)."""
    k_y, k_top, k_mix, k_bg, k_tw, k_cnt = jax.random.split(key, 6)
    y = jax.random.randint(k_y, (n,), 0, classes, jnp.int32)
    # each class's topic words: the first topics_per_class of a random
    # permutation of the vocabulary
    topics = jnp.argsort(jax.random.uniform(k_top, (classes, d)), axis=1)
    topics = topics[:, :topics_per_class].astype(jnp.int32)
    # p(word) = (1 + 12·[word is a topic word of the class]) / (d·Z) is the
    # mixture of a uniform word (weight 1/Z) and a uniform topic word
    # (weight 12·T/(d·Z)), with Z = 1 + 12·T/d
    z = 1.0 + 12.0 * topics_per_class / d
    p_topic = (12.0 * topics_per_class / d) / z
    shape = (n, terms_per_doc)
    use_topic = jax.random.uniform(k_mix, shape) < p_topic
    bg = jax.random.randint(k_bg, shape, 0, d, jnp.int32)
    tw = topics[y[:, None], jax.random.randint(k_tw, shape, 0,
                                               topics_per_class)]
    words = jnp.where(use_topic, tw, bg)
    counts = 1 + jax.random.categorical(
        k_cnt, jnp.asarray(_zipf_clipped_logits(1.6, 20), jnp.float32),
        shape=shape)
    rows = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], shape)
    x = jnp.zeros((n, d), jnp.float32).at[rows, words].add(
        counts.astype(jnp.float32))
    df = jnp.sum(x > 0, axis=0) + 1
    x = x * jnp.log(n / df)[None, :].astype(jnp.float32)
    return _bias_normalize(x), y


GENERATORS = {"tiny1m": tiny1m, "newsgroups": newsgroups}


def seed_key(seed: int):
    """The corpus's key for a run seed (any whole number; folded into 32
    bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(0), seed % (1 << 32))
