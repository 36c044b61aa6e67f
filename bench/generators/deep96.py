"""Deep1B-like corpus: 96-d image descriptors plus the bias feature,
made on the chips that hold it, row-sharded over the mesh's ``"rows"``
axis.  Each shard makes its own rows on its own chip, ``BLOCK`` rows a
launch into its block of the corpus, so no chip ever holds its rows twice.

The rows follow ``corpora.tiny1m`` at d = 96: ``classes`` Gaussian classes
around unit means with per-dimension scales in [0.25, 0.4], an unlabelled
tail (label -1) pushed away from the class centroid, then the bias feature
and l2-normalisation.  Each shard holds n_labeled / shards labelled rows,
an equal share of every class, in a random order of its own.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

import corpora

BLOCK = 1 << 20


def make(key, mesh, *, n_labeled: int, n_unlabeled: int, d: int,
         classes: int):
    """(x (n, d + 1) float32, y (n,) int32), both row-sharded over
    ``mesh``'s ``"rows"`` axis; n = n_labeled + n_unlabeled."""
    shards = mesh.devices.size
    n = n_labeled + n_unlabeled
    if n % shards or n_labeled % (shards * classes):
        raise ValueError(f"{n} rows, {n_labeled} labelled, do not divide "
                         f"over {shards} shards and {classes} classes")
    n_local = n // shards
    k_mean, k_scale, k_pos, k_z = jax.random.split(key, 4)
    means = jax.random.normal(k_mean, (classes, d), jnp.float32)
    means = means / jnp.linalg.norm(means, axis=1, keepdims=True)
    scales = 0.25 + 0.15 * jax.random.uniform(k_scale, (classes, d))
    y = _labels(mesh, n_local, n_labeled // shards, classes)(k_pos)
    x = jax.jit(lambda: jnp.zeros((n, d + 1), jnp.float32),
                out_shardings=NamedSharding(mesh, P("rows", None)))()
    blk = min(BLOCK, n_local)
    step = _rows(mesh, blk)
    for start in range(0, n_local - blk + 1, blk):
        x = step(x, y, means, scales, k_z, start)
    if n_local % blk:
        x = _rows(mesh, n_local % blk)(x, y, means, scales, k_z,
                                       n_local - n_local % blk)
    return x, y


def _labels(mesh, n_local: int, labelled: int, classes: int):
    def local(k_pos):
        y = jnp.concatenate([
            jnp.repeat(jnp.arange(classes, dtype=jnp.int32),
                       labelled // classes),
            jnp.full((n_local - labelled,), -1, jnp.int32)])
        shard = jax.lax.axis_index("rows")
        return jax.random.permutation(jax.random.fold_in(k_pos, shard), y)

    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P(),
                                 out_specs=P("rows"), check_vma=False))


def _rows(mesh, blk: int):
    """One launch: each shard makes its rows [start, start + blk) into its
    block of x (donated)."""
    def local(x, y, means, scales, k_z, start):
        shard = jax.lax.axis_index("rows")
        k = jax.random.fold_in(jax.random.fold_in(k_z, shard), start)
        z = jax.random.normal(k, (blk, means.shape[1]), jnp.float32)
        yb = jax.lax.dynamic_slice_in_dim(y, start, blk)
        c = jnp.maximum(yb, 0)
        xb = jnp.where((yb >= 0)[:, None], means[c] + scales[c] * z,
                       0.9 * (z - 0.8 * means.mean(axis=0)[None, :]))
        return jax.lax.dynamic_update_slice_in_dim(
            x, corpora._bias_normalize(xb), start, 0)

    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("rows", None), P("rows"), P(), P(), P(), P()),
        out_specs=P("rows", None), check_vma=False), donate_argnums=0)
