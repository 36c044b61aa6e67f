"""Vectorized batched query path for the multi-table index.

Three host/device stages, each batched over B queries x L tables:

1. hashing — all L tables' query codes in one ``vmap``ped bilinear pass
   (BH/LBH share the stacked (L, d, k) projection layout; AH/EH fall back
   to a per-table loop since their parameters aren't stackable);
2. multi-probe key generation — one broadcast XOR of the (B,) query keys
   against the precomputed ring masks (core.tables.probe_masks);
3. re-rank — a single gather + batched reduce over the padded candidate
   matrix (core.search.margin_rerank_batch), bit-identical to issuing the
   same queries one at a time.

The scan backend (MultiTableIndex.query_scan_batch) shares stage 1 (the
stacked query hashing below) and stage 3, but replaces the host probe of
stage 2 with the fused device scan; its candidate unions are built on
device, so PAD_MULTIPLE only governs the probe path's rerank shapes.  The
scan depth l the fused kernel selects at is a free knob under histogram
selection (see kernels/README.md) — deep-l scans reach this module only
as wider rerank gathers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.functions import BHHash, SeededBHHash, bilinear_signs
from repro.core.search import (LANE, lane_rows, margin_rerank_batch,
                               to_lanes)
from repro.utils.bits import flip_packed, pack_signs

PAD_MULTIPLE = 128  # candidate-matrix padding quantum (bounds jit retraces)


def _stackable(families) -> bool:
    return (all(isinstance(f, BHHash) for f in families)
            and len({f.u.shape for f in families}) == 1)


def _seed_stackable(families) -> bool:
    """True when the whole family list can hash through ONE grouped
    seed-generated kernel launch: every table is a SeededBHHash over the
    same (d, k).  LBH (learned factors) and the classic sampled BHHash keep
    the materialized path — same interface, they just don't qualify."""
    return (all(type(f) is SeededBHHash for f in families)
            and len({f.u.shape for f in families}) == 1)


@functools.lru_cache(maxsize=16)
def _seed_array(seeds: tuple) -> jax.Array:
    """(L,) uint32 device array of the tables' seeds, built once per family
    set (keyed by the seed values, so a refresh swap to new families gets
    its own array)."""
    return jnp.asarray(seeds, jnp.uint32)


def _seeds_of(families) -> jax.Array:
    return _seed_array(tuple(int(f.seed) for f in families))


def _seeded_grouped_codes(families, pts) -> jax.Array:
    """(L, n, W) database-style codes via the grouped seeded kernel: zero
    projection-weight HBM reads, one launch for all L tables."""
    from repro.kernels import ops
    return ops.bilinear_hash_seeded_grouped(pts, _seeds_of(families),
                                            families[0].k)


@functools.partial(jax.jit, static_argnames=("k",))
def _seeded_query_codes(w, seeds, k: int):
    """(B, d) normals, (L,) seeds -> (L, B, W) query codes: the grouped
    seeded kernel and the query-side flip as one program."""
    from repro.kernels import ops
    return flip_packed(ops.bilinear_hash_seeded_grouped(w, seeds, k), k)


@jax.jit
def _bh_query_codes(u_stack, v_stack, w):
    """(L, d, k) x2, (B, d) -> (L, B, W) packed query codes (sign-flipped)."""
    return jax.vmap(lambda u, v: pack_signs(-bilinear_signs(w, u, v)))(
        u_stack, v_stack)


@jax.jit
def _bh_db_codes(u_stack, v_stack, x):
    """(L, d, k) x2, (n, d) -> (L, n, W) packed database codes."""
    return jax.vmap(lambda u, v: pack_signs(bilinear_signs(x, u, v)))(
        u_stack, v_stack)


@functools.lru_cache(maxsize=16)
def _replicated_query_codes(mesh, k: int):
    """``_seeded_query_codes`` run alike on every device of ``mesh``, for
    queries replicated over it (a kernel is not partitioned for you)."""
    return jax.jit(jax.shard_map(
        lambda w, seeds: _seeded_query_codes(w, seeds, k), mesh=mesh,
        in_specs=(P(), P()), out_specs=P(), check_vma=False))


def hash_queries_all(families, w, use_kernels: bool = False,
                     mesh=None) -> jax.Array:
    """Query-side codes for all tables: (L, B, W) uint32.

    use_kernels=True routes all-SeededBHHash families through the grouped
    seed-generated Pallas kernel (factors regenerated in-register — no
    projection weights stream from HBM); the query-side sign flip
    h(P_w) = -h(w) is the packed-bit complement of the database-style
    codes (sgn flips every bit: prod >= 0 pairs exactly with prod < 0
    under the sgn(0)=+1 convention), so the result is bit-identical to
    the stacked jnp path.  ``mesh``: w is replicated over it, and so are
    the codes.  Runs under the ``repro.hash`` host span.
    """
    with TraceAnnotation("repro.hash"):
        w = jnp.asarray(w, jnp.float32)
        if use_kernels and _seed_stackable(families):
            if mesh is not None:
                return _replicated_query_codes(mesh, families[0].k)(
                    w, _seeds_of(families))
            return _seeded_query_codes(w, _seeds_of(families),
                                       families[0].k)
        if _stackable(families):
            u = jnp.stack([f.u for f in families])
            v = jnp.stack([f.v for f in families])
            return _bh_query_codes(u, v, w)
        return jnp.stack([f.hash_query(w) for f in families])


def hash_database_all(families, x, use_kernels: bool = False) -> jax.Array:
    """Database-side codes for all tables: (L, n, W) uint32.

    use_kernels=True: see hash_queries_all — all-SeededBHHash families hash
    through one grouped seeded kernel launch, bit-identical to the stacked
    jnp path.
    """
    x = jnp.asarray(x, jnp.float32)
    if use_kernels and _seed_stackable(families):
        return _seeded_grouped_codes(families, x)
    if _stackable(families):
        u = jnp.stack([f.u for f in families])
        v = jnp.stack([f.v for f in families])
        return _bh_db_codes(u, v, x)
    return jnp.stack([f.hash_database(x) for f in families])


def hash_database_sharded(families, x, mesh, axis: str,
                          block: int = 1 << 20):
    """Lane-dense database codes of features row-sharded over mesh axis
    ``axis``, hashed on each shard from its own rows and left there:
    (L, W, shards · lane_rows(n_local) / 128, 128) uint32, sharded along
    the lane rows — the layout ``core.search.sharded_topk_lanes`` scans.

    Each shard hashes its n_local rows ``block`` at a time, one launch a
    block (a loop inside one program would let XLA cast the whole shard to
    the product's operand type at once), with the stacked bilinear product
    of ``_bh_db_codes`` — bit-identical to the seeded kernel, which
    regenerates the same projections — because an XLA product reads the
    rows in whatever layout the device keeps them, where a kernel's operand
    would first be copied, whole, into rows-major order."""
    if not _stackable(families):
        raise ValueError("row-sharded features need BH-family tables")
    u = jnp.stack([f.u for f in families])
    v = jnp.stack([f.v for f in families])
    n_local = x.shape[0] // mesh.shape[axis]
    blk = min(block, n_local // LANE * LANE) or n_local
    shards = mesh.shape[axis]
    lanes = jax.jit(lambda: jnp.zeros(
        (u.shape[0], -(-u.shape[2] // 32),
         shards * lane_rows(n_local) // LANE, LANE), jnp.uint32),
        out_shardings=NamedSharding(mesh, P(None, None, axis, None)))()
    step = sharded_hash_step(u, v, mesh, axis, blk)
    for start in range(0, n_local - blk + 1, blk):
        lanes = step(x, lanes, start)
    if n_local % blk:
        tail = sharded_hash_step(u, v, mesh, axis, n_local % blk)
        lanes = tail(x, lanes, n_local - n_local % blk)
    return lanes


def sharded_hash_step(u, v, mesh, axis: str, blk: int):
    """One block of ``hash_database_sharded``: each shard hashes its rows
    [start, start + blk) and writes their lane-dense codes into ``lanes``
    (donated) at lane row start / 128; start is a multiple of 128."""
    def local(xs, lanes, start):
        c = _bh_db_codes(u, v, jax.lax.dynamic_slice_in_dim(xs, start, blk))
        return jax.lax.dynamic_update_slice_in_dim(
            lanes, to_lanes(c, -(-blk // LANE) * LANE), start // LANE, 2)

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(axis, None), P(None, None, axis, None),
                                    P()),
        out_specs=P(None, None, axis, None), check_vma=False),
        donate_argnums=1)


@jax.jit
def dedup_candidates(idx, live_rows=None, rows=None):
    """Device-side union/dedup of a scan's per-table top-l, one program.

    idx: (L, B, l) int32 scan ids, -1 in impossible slots.  Per query, the
    L·l ids are sorted and repeats and sentinels invalidated.  With
    ``live_rows`` (the monolithic index's live-row map) the ids are scan
    positions and map through it; without, they are rows already and clip
    to ``rows`` (a traced count, so a growing row space never retraces).
    Returns (grows (B, L·l) rows, uniq (B, L·l) bool, hits (L,) live
    slots per table).
    """
    b = idx.shape[1]
    flat = jnp.sort(jnp.transpose(idx, (1, 0, 2)).reshape(b, -1), axis=1)
    uniq = flat >= 0
    uniq &= jnp.concatenate(
        [jnp.ones((b, 1), bool), flat[:, 1:] != flat[:, :-1]], axis=1)
    if live_rows is None:
        grows = jnp.clip(flat, 0, rows - 1)
    else:
        grows = live_rows[jnp.clip(flat, 0, live_rows.shape[0] - 1)]
    return grows, uniq, (idx >= 0).sum(axis=(1, 2))


@jax.jit
def dedup_packed(idx, live_rows=None, rows=None):
    """``dedup_candidates`` with what the host reads packed into one
    (B + 1, L·l) int32 array: per query its deduplicated candidate rows
    (-1 where a slot repeats or is empty), and in the last row the (L,)
    per-table hits.  Returns (grows (B, L·l) for the re-rank, packed)."""
    grows, uniq, hits = dedup_candidates(idx, live_rows, rows)
    last = jnp.zeros((1, grows.shape[1]), jnp.int32)
    last = last.at[0, :hits.shape[0]].set(hits.astype(jnp.int32))
    cand = jnp.where(uniq, grows, -1).astype(jnp.int32)
    return grows, jnp.concatenate([cand, last])


@jax.jit
def mask_candidates(uniq, grows, mask_rows):
    """uniq & mask_rows[grows]: the deduplicated candidates the (n,)
    row-space mask admits, one program."""
    return uniq & mask_rows[grows]


def union_candidates(per_table: list[np.ndarray]) -> np.ndarray:
    """Union of per-table candidate id lists, first occurrence order."""
    arrs = [a for a in per_table if a.size]
    if not arrs:
        return np.empty((0,), dtype=np.int64)
    cat = np.concatenate(arrs)
    _, first = np.unique(cat, return_index=True)
    return cat[np.sort(first)]


def pad_candidates(cands: list[np.ndarray]):
    """Ragged candidate lists -> (ids (B, C), valid (B, C)) with C padded to
    PAD_MULTIPLE so the jitted re-rank sees few distinct shapes."""
    b = len(cands)
    cmax = max((c.size for c in cands), default=0)
    c_pad = max(PAD_MULTIPLE, -(-cmax // PAD_MULTIPLE) * PAD_MULTIPLE)
    ids = np.zeros((b, c_pad), dtype=np.int64)
    valid = np.zeros((b, c_pad), dtype=bool)
    for i, c in enumerate(cands):
        ids[i, :c.size] = c
        valid[i, :c.size] = True
    return ids, valid


def batched_rerank(x, w, cands: list[np.ndarray], l: int = 1, mask=None):
    """Exact-margin re-rank of B ragged candidate lists in one device call.

    x: (n, d) device database; w: (B, d) normals; mask: optional (n,) bool —
    candidates outside it are ignored (e.g. already-labeled points in AL).
    Returns (ids (B, l) int64, margins (B, l) f32, nonempty (B,) bool); slots
    without a valid candidate hold id -1 / margin +inf.
    """
    ids, valid = pad_candidates(cands)
    if mask is not None:
        valid &= np.asarray(mask, bool)[ids]
    nonempty = valid.any(axis=1)
    margins, top = margin_rerank_batch(x, jnp.asarray(w, jnp.float32),
                                       jnp.asarray(ids), jnp.asarray(valid), l)
    margins = np.asarray(margins)
    top = np.asarray(top).astype(np.int64)
    top[~np.isfinite(margins)] = -1
    return top, margins, nonempty
