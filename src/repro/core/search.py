"""Device-side Hamming search: single-device scan and the sharded,
constant-communication distributed scan (beyond-paper, for multi-node
serving of the index).

The distributed layout: the packed code table (n, W) is sharded along rows
over one mesh axis (the `data` axis of the production mesh).  Each shard
scans locally (memory-bound popcount pass — see kernels/hamming.py for the
Pallas TPU kernel), selects its local top-L, and only the L (distance, index)
pairs cross the interconnect via one small all-gather: O(L * shards * 8B),
independent of n.
"""
from __future__ import annotations

import os
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.utils.bits import hamming_packed

# Matches kernels.hamming.DIST_SENTINEL: fill distance for impossible top-k
# slots (l > n).  Kept as a literal so this module stays importable without
# the kernels package.
DIST_SENTINEL = 0x3FFFFFFF

# Lane width of a TPU vreg, and the rows of one (8, 128) uint32 tile of one
# code word in the lane-dense code layout (see to_lanes).
LANE = 128
LANE_TILE = 8 * LANE


# Rows a lane-dense table past one block is padded to a multiple of: the
# largest fused-scan row block (kernels.ops.lane_block, which cuts the table
# into blocks by the VMEM cap) then divides it.
LANE_BLOCK = 16 * LANE_TILE


def lane_rows(n: int) -> int:
    """Rows a lane-dense code table of n rows is padded to: whole lane rows
    up to one tile, whole tiles up to ``LANE_BLOCK``, whole ``LANE_BLOCK``s
    past it, so every fused-scan block the table is cut into covers whole
    (8, 128) tiles."""
    if n <= LANE_TILE:
        return -(-n // LANE) * LANE
    step = LANE_TILE if n <= LANE_BLOCK else LANE_BLOCK
    return -(-n // step) * step


def to_lanes(codes, n_pad: int):
    """(G, n, W) packed codes -> the lane-dense (G, W, n_pad / 128, 128)
    layout the fused scan kernels read: row r of word w at
    [g, w, r // 128, r % 128], rows past n zero.  A (G, n, W) operand with
    W = 1 would fill one lane of each (8, 128) tile — 128x its bytes."""
    g, n, w = codes.shape
    codes = jnp.pad(codes, ((0, 0), (0, n_pad - n), (0, 0)))
    return jnp.swapaxes(codes, 1, 2).reshape(g, w, n_pad // LANE, LANE)


def from_lanes(codes, n: int):
    """Inverse of ``to_lanes``: the first n rows as (G, n, W)."""
    g, w, r, lane = codes.shape
    return jnp.swapaxes(codes.reshape(g, w, r * lane), 1, 2)[:, :n]


def env_use_kernels() -> bool:
    """Default for the use_kernel(s) knobs: the Pallas kernels run where
    the default backend is a TPU, and the pure-jnp paths elsewhere (off the
    TPU the kernels could only run in the Pallas interpreter).  The
    ``REPRO_USE_KERNELS`` env var overrides the platform (CI runs legs with
    it set to 1 and to 0 so both paths stay exercised on the CPU).
    Explicit arguments always win — the env var only moves the default."""
    env = os.environ.get("REPRO_USE_KERNELS")
    if env is None or not env.strip():
        return jax.default_backend() == "tpu"
    return env.strip().lower() not in ("0", "false", "no", "off")


def env_fused_select(select: str | None = None) -> str:
    """Resolve the fused-scan selection algorithm: ``"hist"`` (the default,
    two-pass counting-sort/histogram select — O(block_n·B) tile passes
    independent of l) or ``"argmin"`` (the legacy l-round masked-argmin
    kernel / lax.top_k fallback — the escape hatch if the histogram path
    misbehaves on some backend).  Explicit arguments win; otherwise the
    ``REPRO_FUSED_SELECT`` env var moves the default (CI runs a leg with
    it set to ``argmin`` so the fallback stays exercised).  Both produce
    bit-identical results on every scan path — this knob only trades
    selection cost."""
    if select is not None:
        if select not in ("hist", "argmin"):
            raise ValueError(f"fused_select must be 'hist' or 'argmin', "
                             f"got {select!r}")
        return select
    env = os.environ.get("REPRO_FUSED_SELECT", "").strip().lower()
    return env if env in ("hist", "argmin") else "hist"


def env_cand_pack(pack: str | None = None) -> str:
    """Resolve the fused-scan candidate emission width: ``"16"`` (the
    default — int16 (dist, id) pairs, half the candidate HBM/interconnect
    bytes), ``"8"`` (uint8 distances + int16 ids, only legal while
    32·W < 255, i.e. k <= 224 — kernels.hamming.cand_encoding guards), or
    ``"none"`` (the int32 escape hatch, e.g. for a backend whose narrow
    stores misbehave).  Explicit arguments win; otherwise the
    ``REPRO_CAND_PACK`` env var moves the default.  Packing only narrows
    what leaves a kernel block / crosses the interconnect — every pack is
    bit-identical after the widening merge, so the knob trades bytes, not
    answers.  The pure-jnp scan paths have no block emission to narrow;
    they accept-and-ignore the knob and match by construction."""
    if pack is not None:
        if pack not in ("none", "16", "8"):
            raise ValueError(f"cand_pack must be 'none', '16' or '8', "
                             f"got {pack!r}")
        return pack
    env = os.environ.get("REPRO_CAND_PACK", "").strip().lower()
    return env if env in ("none", "16", "8") else "16"


def _pad_topk(dists, ids, l: int):
    """Pad the trailing top-k axis out to l slots with the impossible-slot
    contract shared by every scan path: (DIST_SENTINEL, id -1)."""
    have = dists.shape[-1]
    if have >= l:
        return dists, ids
    pad = [(0, 0)] * (dists.ndim - 1) + [(0, l - have)]
    return (jnp.pad(dists, pad, constant_values=DIST_SENTINEL),
            jnp.pad(ids, pad, constant_values=-1))


@partial(jax.jit, static_argnames=("l",))
def hamming_topk(codes, query, l: int):
    """Single-device scan: smallest-distance top-l.

    codes: (n, W) uint32; query: (W,) uint32 -> (dists (l,), idx (l,)).
    When l > n the tail slots carry (DIST_SENTINEL, -1), matching the
    kernel path (kernels.ops.hamming_topk).
    """
    d = hamming_packed(codes, query[None, :])
    neg, idx = jax.lax.top_k(-d, min(l, d.shape[0]))
    return _pad_topk(-neg, idx, l)


@partial(jax.jit, static_argnames=("l",))
def hamming_topk_batch(codes, queries, l: int):
    """Batched scan: top-l per query in one pass.

    codes: (n, W) uint32; queries: (B, W) uint32
    -> (dists (B, l), idx (B, l)); l > n tails are (DIST_SENTINEL, -1).
    """
    d = hamming_packed(codes[None, :, :], queries[:, None, :])   # (B, n)
    neg, idx = jax.lax.top_k(-d, min(l, d.shape[1]))
    return _pad_topk(-neg, idx, l)


def hamming_topk_grouped(codes, queries, l: int, select: str | None = None,
                         active=None, pack: str | None = None):
    """Grouped scan, pure-jnp: group g's queries vs group g's codes only.

    Same contract as kernels.ops.hamming_topk_grouped (the Pallas fused
    path): codes (G, n, W), queries (G, B, W) -> (dists (G, B, l),
    ids (G, B, l)) sorted ascending by (distance, id); when l > n the tail
    columns carry (DIST_SENTINEL, -1).  One XLA dispatch regardless of G —
    the multi-table scan folds its L tables into G.

    select: ``"hist"`` (default, env-overridable via REPRO_FUSED_SELECT)
    routes through the counting-sort reference ``hamming_topk_grouped_hist``;
    ``"argmin"`` keeps the legacy lax.top_k selection.  Bit-identical.

    active: optional (n,) bool liveness flags shared by all G groups —
    False rows (tombstones / device padding) rank at the sentinel, so the
    result is the top-l of the live rows alone with (DIST_SENTINEL, -1) in
    impossible slots.  Traced (not a jit key): mutable-index serving flips
    tombstones without retracing the scan.

    pack is accepted for call-site symmetry with the kernel path and
    ignored: candidate packing narrows a kernel block's HBM emission, and
    the jnp scans have no block emission — their merged output equals every
    packed variant by construction (the parity suite asserts it).
    """
    del pack
    if env_fused_select(select) == "hist":
        return hamming_topk_grouped_hist(codes, queries, l, active)
    return _grouped_topk_lax(codes, queries, l, active)


@partial(jax.jit, static_argnames=("l",))
def _grouped_topk_lax(codes, queries, l: int, active=None):
    """Legacy grouped selection: full distance matrix + lax.top_k."""
    g, n, w = codes.shape
    d = hamming_packed(codes[:, None, :, :], queries[:, :, None, :])  # G,B,n
    if active is not None:
        d = jnp.where(active[None, None, :], d, jnp.int32(DIST_SENTINEL))
    neg, idx = jax.lax.top_k(-d, min(l, n))
    d, i = _pad_topk(-neg, idx, l)
    if active is not None:
        i = jnp.where(d >= DIST_SENTINEL, jnp.int32(-1), i)
    return d, i


@partial(jax.jit, static_argnames=("l",))
def hamming_topk_grouped_hist(codes, queries, l: int, active=None):
    """Pure-jnp reference of the two-pass histogram (counting-sort) select
    the Pallas kernel ``hamming_topk_hist_kernel`` runs per block — here
    over the whole row axis at once.  Bit-identical to the lax.top_k path
    (ties to the lowest id, l > n tails = (DIST_SENTINEL, -1)).

    Pass 1 bisects the distance CDF (count(d <= mid), one compare-reduce
    per probe over the ≤ 32·W+1 possible values) to the per-query cutoff
    radius r.  Pass 2 keeps rows with d < r plus the lowest-index ties at
    r, scatters them into their cumsum-assigned slots, and lex-sorts only
    those min(l, n) survivors by (distance, id) — the sort shrinks from n
    rows to l.  This is the selection the ``REPRO_USE_KERNELS=0`` leg
    serves with, so the counting-sort logic is exercised on both CI legs.
    """
    g, n, w = codes.shape
    b = queries.shape[1]
    d = hamming_packed(codes[:, None, :, :], queries[:, :, None, :])  # G,B,n
    if active is not None:
        # masked rows (tombstones / padding) sit at the sentinel: they can
        # never reach the cutoff radius (r <= max_dist < sentinel), so when
        # fewer than t live rows exist the spare slots keep their
        # (DIST_SENTINEL, -1) initializers — the l > n contract exactly
        d = jnp.where(active[None, None, :], d, jnp.int32(DIST_SENTINEL))
    t = min(l, n)
    max_dist = 32 * w
    lo = jnp.zeros((g, b, 1), jnp.int32)
    hi = jnp.full((g, b, 1), max_dist, jnp.int32)
    for _ in range(max(1, max_dist.bit_length())):
        mid = (lo + hi) >> 1
        cnt = jnp.sum((d <= mid).astype(jnp.int32), axis=2, keepdims=True)
        ge = cnt >= t
        hi = jnp.where(ge, mid, hi)
        lo = jnp.where(ge, lo, mid + 1)
    r = hi
    less = jnp.sum((d < r).astype(jnp.int32), axis=2, keepdims=True)
    tie = d == r
    tie_rank = jnp.cumsum(tie.astype(jnp.int32), axis=2) - 1
    keep = (d < r) | (tie & (tie_rank < (t - less)))
    # slot in [0, t) for kept rows (row order), t = dropped (scatter no-op)
    slot = jnp.where(keep, jnp.cumsum(keep.astype(jnp.int32), axis=2) - 1, t)
    gi = jnp.arange(g)[:, None, None]
    bi = jnp.arange(b)[None, :, None]
    ids = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), d.shape)
    out_d = jnp.full((g, b, t + 1), jnp.int32(DIST_SENTINEL))
    out_i = jnp.full((g, b, t + 1), jnp.int32(-1))
    out_d = out_d.at[gi, bi, slot].set(d, mode="drop")[..., :t]
    out_i = out_i.at[gi, bi, slot].set(ids, mode="drop")[..., :t]
    out_d, out_i = jax.lax.sort((out_d, out_i), dimension=2, num_keys=2)
    return _pad_topk(out_d, out_i, l)


# -- two-segment (LSM base+delta) merge contract -----------------------------
#
# serving.lsm.LSMMultiTableIndex stores the index as an immutable base
# segment plus a small mutable delta segment.  Each segment is scanned
# independently (fused kernel / pure jnp — any of the bit-identical scan
# paths) and the per-(group, query) candidate lists are combined here.  The
# contract that makes the merged answer bit-identical to a monolithic scan:
# ids must be globally comparable (the LSM row space keeps row order ==
# stable-id order), sentinel slots are (DIST_SENTINEL, -1), and tombstoned
# rows never reach the top-l: the scans' traced ``active`` mask ranks dead
# rows at the sentinel inside selection.


@partial(jax.jit, static_argnames=("l",))
def merge_topk_segments(d_a, i_a, d_b, i_b, l: int):
    """Lexicographic (dist, id) merge of two per-(group, query) top-k lists.

    d_*/i_* : (..., l_a) and (..., l_b) candidate lists, each already
    sorted ascending by (distance, id) with (DIST_SENTINEL, -1) sentinels
    in impossible slots.  Ids must share one id space (the caller offsets
    segment-local ids first).  Returns the combined top-l, sorted by the
    same (distance, id) order — exactly what a single scan over the
    concatenated segments would produce, because real distances never
    reach DIST_SENTINEL, so sentinels sort last.
    """
    d = jnp.concatenate([d_a, d_b], axis=-1)
    i = jnp.concatenate([i_a, i_b], axis=-1)
    d, i = jax.lax.sort((d, i), dimension=d.ndim - 1, num_keys=2)
    return _pad_topk(d[..., :l], i[..., :l], l)


# interconnect packing (the sharded analogue of the kernels' candidate
# packing): what crosses the all-gather is bounded exactly like a kernel
# block's emission — distances <= 32·W, ids SHARD-LOCAL (< shard rows) with
# the global offset reconstructed after the gather from each row's position
# on the gather axis.  int16 halves the gather bytes; the post-gather widen
# restores the identical int32 values, so the merge (and its tie order) is
# unchanged bit for bit.
_SENT16 = 0x7FFF      # kernels.hamming.CAND_SENTINELS["16"]


def _narrow_gather(cd, ci, pack: str, w: int, rows: int):
    """Narrow one shard's (…, l) candidate lists for the all-gather.
    Sentinel distances (DIST_SENTINEL) clamp to the int16 sentinel; -1 ids
    survive the int16 cast.  Returns (cd, ci, packed_d, packed_i) — either
    array stays int32 when its values don't fit the narrow dtype
    (32·W >= the int16 sentinel, or shard rows past the int16 id range)."""
    pack_d = pack != "none" and 32 * w < _SENT16
    pack_i = pack != "none" and rows - 1 <= _SENT16
    if pack_d:
        cd = jnp.minimum(cd, _SENT16).astype(jnp.int16)
    if pack_i:
        ci = ci.astype(jnp.int16)
    return cd, ci, pack_d, pack_i


def _widen_gather(all_d, all_i, pack_d: bool, pack_i: bool, rows: int,
                  axis_dim: int):
    """Undo _narrow_gather after the all-gather: widen to int32, map the
    int16 sentinel back to DIST_SENTINEL, and add each shard's global row
    offset (shard position on the gather axis × shard rows) back to the
    non-sentinel ids."""
    shards = all_d.shape[0]
    if pack_d:
        all_d = all_d.astype(jnp.int32)
        all_d = jnp.where(all_d == _SENT16, jnp.int32(DIST_SENTINEL), all_d)
    if pack_i:
        all_i = all_i.astype(jnp.int32)
    shape = [shards] + [1] * (all_i.ndim - 1)
    offsets = (jnp.arange(shards, dtype=jnp.int32) * rows).reshape(shape)
    return all_d, jnp.where(all_i < 0, -1, all_i + offsets)


def _local_then_merge(codes_shard, query, l: int, axis: str,
                      use_kernel: bool, select: str, pack: str):
    if use_kernel:
        # fused Pallas scan+select: the shard's distance vector stays in
        # VMEM; only l (distance, id) pairs reach HBM before the gather.
        from repro.kernels import ops
        cand_d, idx = ops.hamming_topk(codes_shard, query, l, select=select,
                                       pack=pack)
    else:
        d = hamming_packed(codes_shard, query[None, :])
        neg, idx = jax.lax.top_k(-d, min(l, d.shape[0]))
        cand_d, idx = _pad_topk(-neg, idx, l)
    rows, w = codes_shard.shape
    # ids stay SHARD-LOCAL across the gather (impossible slots stay -1);
    # the global offset is recovered from the gather-axis position.
    cand_i = jnp.where(idx < 0, -1, idx).astype(jnp.int32)
    cand_d, cand_i, pk_d, pk_i = _narrow_gather(cand_d, cand_i, pack, w,
                                                rows)
    all_d = jax.lax.all_gather(cand_d, axis)             # (S, l)
    all_i = jax.lax.all_gather(cand_i, axis)
    all_d, all_i = _widen_gather(all_d, all_i, pk_d, pk_i, rows, 0)
    all_d, all_i = all_d.reshape(-1), all_i.reshape(-1)
    neg2, sel = jax.lax.top_k(-all_d, l)
    return -neg2, all_i[sel]


def hamming_topk_sharded(codes, query, l: int, mesh, axis: str = "data",
                         use_kernel: bool | None = None,
                         select: str | None = None,
                         pack: str | None = None):
    """Distributed top-l Hamming scan over a row-sharded code table.

    codes must be shardable by `axis` on dim 0.  Returns replicated
    (dists, idx) — idx are global row ids.  The local stage runs the fused
    Pallas kernel by default (``use_kernel=False`` falls back to the
    pure-jnp scan, bit-identical including l > shard-rows sentinels;
    ``None`` reads REPRO_USE_KERNELS); the all-gather merge is unchanged
    either way, and ties still resolve to the lowest global row id because
    shards are contiguous row ranges gathered in shard order.
    """
    if use_kernel is None:
        use_kernel = env_use_kernels()
    select = env_fused_select(select)
    pack = env_cand_pack(pack)
    return _sharded_fn(mesh, axis, l, use_kernel, select, pack)(codes, query)


@lru_cache(maxsize=256)
def _sharded_fn(mesh, axis: str, l: int, use_kernel: bool, select: str,
                pack: str):
    """Jitted shard_map closure for hamming_topk_sharded, cached per
    (mesh, axis, l, use_kernel, select, pack) so steady serving traffic
    doesn't rebuild and re-trace the distributed scan on every call."""
    return jax.jit(jax.shard_map(
        partial(_local_then_merge, l=l, axis=axis, use_kernel=use_kernel,
                select=select, pack=pack),
        mesh=mesh,
        in_specs=(P(axis, None), P()),
        out_specs=(P(), P()),
        check_vma=False,
    ))


def _sharded_scan_local(codes, queries, active, l: int, n_local: int,
                        axis: str, use_kernel: bool, select: str, pack: str):
    """Local grouped scan + small all-gather merge for one shard.

    codes: (G, W, lane_rows(n_local) / 128, 128) — this shard's lane-dense
    block: its n_local rows of every group (contiguous global rows from
    shard · n_local); queries: (G, B, W) replicated; active: this shard's
    (n_local,) liveness flags, or None (every row live).  Emits the shard's
    top-l of its live rows per (group, query), carries SHARD-LOCAL ids
    (narrowed per ``pack``) across the gather, then widens, restores global
    row ids from each row's gather-axis position, and lex-sorts the S·l
    candidates by (distance, id) so ties resolve to the lowest global id,
    exactly like the single-device grouped scan.
    """
    if use_kernel:
        from repro.kernels import ops
        cd, ci = ops.hamming_topk_lanes(codes, queries, l, n_local,
                                        select=select, active=active,
                                        pack=pack)
    else:
        cd, ci = hamming_topk_grouped(from_lanes(codes, n_local), queries,
                                      l, select=select, active=active)
    ci = ci.astype(jnp.int32)
    cd, ci, pk_d, pk_i = _narrow_gather(cd, ci, pack, queries.shape[2],
                                        n_local)
    all_d = jax.lax.all_gather(cd, axis)          # (S, G, B, l)
    all_i = jax.lax.all_gather(ci, axis)
    all_d, all_i = _widen_gather(all_d, all_i, pk_d, pk_i, n_local, 0)
    g, b = queries.shape[0], queries.shape[1]
    all_d = jnp.moveaxis(all_d, 0, 2).reshape(g, b, -1)
    all_i = jnp.moveaxis(all_i, 0, 2).reshape(g, b, -1)
    all_d, all_i = jax.lax.sort((all_d, all_i), dimension=2, num_keys=2)
    return all_d[:, :, :l], all_i[:, :, :l]


def sharded_topk_lanes(codes, queries, l: int, mesh, axis: str,
                       n_local: int, active=None,
                       use_kernel: bool | None = None,
                       select: str | None = None, pack: str | None = None):
    """Distributed grouped top-l scan over lane-dense codes laid out
    row-sharded over mesh axis ``axis``: shard s holds global rows
    [s · n_local, (s + 1) · n_local) as its own (G, W, lane_rows(n_local) /
    128, 128) block.  Queries (G, B, W) are replicated.  active: optional
    (shards · n_local,) bool liveness flags sharded like the rows — False
    rows (tombstones, or the padding a shard with fewer rows carries) rank
    at the sentinel inside each local selection, so the result is the
    top-l of the live rows alone.

    Each shard runs ONE local grouped launch for all G groups x B queries
    on its own block; only the (S, G, B, l) candidate pairs cross the
    interconnect — O(G·B·l·S·8) bytes, independent of n.  Returns
    replicated (dists (G, B, l), ids (G, B, l)) with global row ids,
    bit-identical to the single-device grouped scan of the live rows
    (ties to the lowest global row, (DIST_SENTINEL, -1) in impossible
    slots).  The program is named ``sharded_scan``.
    """
    if use_kernel is None:
        use_kernel = env_use_kernels()
    select = env_fused_select(select)
    pack = env_cand_pack(pack)
    fn = _sharded_scan_fn(mesh, axis, l, n_local, active is not None,
                          use_kernel, select, pack)
    return fn(codes, queries) if active is None else fn(codes, queries,
                                                        active)


@lru_cache(maxsize=64)
def _sharded_scan_fn(mesh, axis: str, l: int, n_local: int, masked: bool,
                     use_kernel: bool, select: str, pack: str):
    """The jitted ``sharded_scan`` program, cached so the serving hot path
    doesn't rebuild and re-trace the distributed scan on every batch."""
    body = partial(_sharded_scan_local, l=l, n_local=n_local, axis=axis,
                   use_kernel=use_kernel, select=select, pack=pack)
    specs = (P(None, None, axis, None), P())
    if masked:
        local = jax.shard_map(body, mesh=mesh, in_specs=specs + (P(axis),),
                              out_specs=(P(), P()), check_vma=False)
    else:
        local = jax.shard_map(lambda c, q: body(c, q, None), mesh=mesh,
                              in_specs=specs, out_specs=(P(), P()),
                              check_vma=False)

    def sharded_scan(codes, queries, *active):
        return local(codes, queries, *active)
    return jax.jit(sharded_scan)


@partial(jax.jit, static_argnames=("l",))
def margin_rerank(x, w, candidates, l: int):
    """Exact re-rank of a candidate list by margin |w.x| / ||w||.

    x: (n, d) database; w: (d,) hyperplane normal; candidates: (c,) int ids.
    Returns (margins (l,), ids (l,)) sorted ascending by margin.
    """
    cx = x[candidates]                         # (c, d) gather
    m = jnp.abs(cx @ w) / jnp.maximum(jnp.linalg.norm(w), 1e-12)
    neg, sel = jax.lax.top_k(-m, min(l, candidates.shape[0]))
    return -neg, candidates[sel]


def rows_minor(x) -> bool:
    """True where the device keeps 2-D ``x`` with its rows on the minor
    (lane) axis — a TPU lays out an (n, d) float32 array with d < 128 or
    d = 385 that way, to pad less.  An XLA gather of rows from such an
    array first copies the whole of it into rows-major order."""
    layout = getattr(getattr(x, "format", None), "layout", None)
    return tuple(getattr(layout, "major_to_minor", ())) == (1, 0)


def gather_rows(x, rows, rows_minor: bool = False):
    """x[rows]: (n, d) rows at an int array of row ids -> rows.shape + (d,).
    Where ``rows_minor`` (see ``rows_minor``), each row is a dynamic slice
    of one column of x.T, which reads x where it lies: a gather would
    first copy all of x."""
    if not rows_minor:
        return x[rows]
    d = x.shape[1]
    flat = rows.reshape(-1)
    xt = x.T

    def one(i, out):
        col = jax.lax.dynamic_slice(xt, (0, flat[i]), (d, 1))
        return jax.lax.dynamic_update_slice(out, col, (0, i))

    out = jax.lax.fori_loop(0, flat.shape[0], one,
                            jnp.zeros((d, flat.shape[0]), x.dtype),
                            unroll=8)
    return out.T.reshape(rows.shape + (d,))


def _margins(cx, w_batch):
    """|w.x| / ||w|| of gathered (B, C, d) rows.  Multiply+reduce instead
    of einsum: the d-reduction order is then independent of B and C, so
    batched answers are bit-identical to the same queries issued one at a
    time, and to the same rows re-ranked on another shard."""
    m = jnp.abs(jnp.sum(cx * w_batch[:, None, :], axis=-1))
    return m / jnp.maximum(jnp.linalg.norm(w_batch, axis=1, keepdims=True),
                           1e-12)


@partial(jax.jit, static_argnames=("l", "rows_minor"))
def margin_rerank_batch(x, w_batch, candidates, valid, l: int,
                        rows_minor: bool = False):
    """Batched exact re-rank: one gather + one batched reduce for B queries.

    x: (n, d) database; w_batch: (B, d) hyperplane normals;
    candidates: (B, C) int ids padded to a common length C;
    valid: (B, C) bool mask for the padding (False rows rank last);
    rows_minor: x's device layout (``rows_minor``), which picks the gather.
    Returns (margins (B, l), ids (B, l)) sorted ascending by margin;
    padded-out slots come back with margin +inf and their padded id.
    """
    cx = gather_rows(x, candidates, rows_minor)        # (B, C, d)
    m = jnp.where(valid, _margins(cx, w_batch), jnp.inf)
    neg, sel = jax.lax.top_k(-m, min(l, candidates.shape[1]))
    return -neg, jnp.take_along_axis(candidates, sel, axis=1)


def _sharded_rerank_local(x, w_batch, candidates, valid, l: int,
                          n_local: int, axis: str, rows_minor: bool):
    """One shard's part of ``sharded_rerank``: the candidates this shard
    owns gathered from its own block, margins as ``margin_rerank_batch``
    computes them, +inf where another shard owns the row; the owners'
    margins combine exactly by a min across shards, then top-l."""
    local = candidates - jax.lax.axis_index(axis) * n_local
    own = valid & (local >= 0) & (local < n_local)
    cx = gather_rows(x, jnp.clip(local, 0, n_local - 1), rows_minor)
    m = jnp.where(own, _margins(cx, w_batch), jnp.inf)
    m = jax.lax.pmin(m, axis)
    neg, sel = jax.lax.top_k(-m, min(l, candidates.shape[1]))
    return -neg, jnp.take_along_axis(candidates, sel, axis=1)


def sharded_rerank(x, w_batch, candidates, valid, l: int, mesh, axis: str,
                   rows_minor: bool = False):
    """``margin_rerank_batch`` over features row-sharded over mesh axis
    ``axis`` (n_local rows a shard): each chip re-ranks the candidates it
    owns from its own block, so the features never move, and the answers
    are bit-identical to the one-device re-rank of the same rows.  w_batch,
    candidates (global rows) and valid are replicated; so are the results.
    The program is named ``sharded_rerank``."""
    n_local = x.shape[0] // mesh.shape[axis]
    return _sharded_rerank_fn(mesh, axis, l, n_local, rows_minor)(
        x, w_batch, candidates, valid)


@lru_cache(maxsize=64)
def _sharded_rerank_fn(mesh, axis: str, l: int, n_local: int,
                       rows_minor: bool):
    local = jax.shard_map(
        partial(_sharded_rerank_local, l=l, n_local=n_local, axis=axis,
                rows_minor=rows_minor),
        mesh=mesh, in_specs=(P(axis, None), P(), P(), P()),
        out_specs=(P(), P()), check_vma=False)

    def sharded_rerank(x, w_batch, candidates, valid):
        return local(x, w_batch, candidates, valid)
    return jax.jit(sharded_rerank)


@partial(jax.jit, static_argnames=("l",))
def margin_rerank_segmented(base_x, delta_x, split, w_batch, candidates,
                            valid, l: int):
    """``margin_rerank_batch`` over a row space stored as two segments.

    Rows < ``split`` gather from ``base_x`` (the LSM index's immutable,
    device-resident base — uploaded once per compaction cycle, never per
    insert), rows >= split from ``delta_x`` at offset row - split.  Both
    arrays may carry padding rows past their true lengths (never selected:
    ``valid`` is False wherever candidates point past the real data).
    ``split`` is a traced scalar, so the jit cache is keyed only by the
    (padded, power-of-two-bucketed) array shapes, not by where the
    base/delta boundary happens to sit.

    Bit-identical to margin_rerank_batch on the concatenation
    [base_x[:split]; delta_x[:rows-split]]: the two clipped gathers + where
    produce the same cx rows, and the margin math is the same expression.
    """
    is_base = candidates < split
    cb = base_x[jnp.clip(candidates, 0, base_x.shape[0] - 1)]
    cd = delta_x[jnp.clip(candidates - split, 0, delta_x.shape[0] - 1)]
    cx = jnp.where(is_base[..., None], cb, cd)
    m = jnp.where(valid, _margins(cx, w_batch), jnp.inf)
    neg, sel = jax.lax.top_k(-m, min(l, candidates.shape[1]))
    return -neg, jnp.take_along_axis(candidates, sel, axis=1)


# -- replicated-shard merge contract (serving.cluster) -----------------------
#
# serving.cluster.ShardReplicaRouter splits the row space over S shards and
# asks one healthy replica per shard for its per-table (distance, id) top-l
# BEFORE any re-rank.  Merging at the Hamming level is what preserves the
# (dist, id) tie contract under partial coverage: any row in the covered-rows
# global top-l is necessarily in its own shard's local top-l, so the merged
# list equals what one scan over the union of covered shards would produce —
# including tie order (lowest id) and l > n sentinels.  Merging *answers*
# (post-rerank margins) would not be bit-identical: each shard's candidate
# union is a superset of the covered-rows index's, and a superset member can
# displace the true answer.  The margins for the merged candidate set are
# then recomputed per owning shard via ``margin_batch`` below — the margin's
# d-reduction is per-row (multiply+reduce), so the values match
# ``margin_rerank_batch`` bit for bit regardless of which index computes them.


def merge_topk_shards(dists: list, ids: list, l: int):
    """Host-side lexicographic (dist, id) merge of per-shard top-l lists.

    dists/ids: equal-length lists of (..., l_s) numpy arrays, one per
    covered shard, each sorted ascending by (distance, id) with
    (DIST_SENTINEL, -1) sentinels in impossible slots.  Ids must already be
    GLOBAL (the router maps shard-local stable ids to global ids first).
    Returns (dists (..., l), ids (..., l)) int32/int64 — the combined
    top-l in the same order a single scan over the union would produce:
    real distances never reach DIST_SENTINEL, so sentinels sort last, and
    equal distances resolve to the lowest global id.
    """
    d = np.concatenate([np.asarray(a, dtype=np.int64) for a in dists],
                       axis=-1)
    i = np.concatenate([np.asarray(a, dtype=np.int64) for a in ids],
                       axis=-1)
    # one composite key per slot: dist in the high bits, id+1 in the low 32
    # (sentinel slots carry id -1 -> 0, real ids are < 2^32-1), so a single
    # stable argsort realises the (dist, id) lexicographic order.
    order = np.argsort((d << 32) | (i + 1), axis=-1, kind="stable")
    d = np.take_along_axis(d, order, axis=-1)[..., :l]
    i = np.take_along_axis(i, order, axis=-1)[..., :l]
    have = d.shape[-1]
    if have < l:
        pad = [(0, 0)] * (d.ndim - 1) + [(0, l - have)]
        d = np.pad(d, pad, constant_values=DIST_SENTINEL)
        i = np.pad(i, pad, constant_values=-1)
    return d.astype(np.int32), i


@jax.jit
def margin_batch(x, w_batch, candidates, valid):
    """Per-candidate exact margins |w.x| / ||w|| with NO selection.

    x: (n, d) database; w_batch: (B, d); candidates: (B, C) int row ids
    (invalid slots may be -1 — they are clipped for the gather and masked);
    valid: (B, C) bool.  Returns (B, C) float32 margins aligned to the
    candidate positions, +inf at invalid slots.  Same margin expression as
    ``margin_rerank_batch`` (multiply+reduce over d, per-row), so the
    values are bit-identical to what any index computes for the same rows —
    the property the cluster router's cross-shard re-rank leans on.
    """
    cx = x[jnp.clip(candidates, 0, x.shape[0] - 1)]
    return jnp.where(valid, _margins(cx, w_batch), jnp.inf)


@jax.jit
def margin_batch_segmented(base_x, delta_x, split, w_batch, candidates,
                           valid):
    """``margin_batch`` over the LSM base+delta two-segment row space.

    Rows < ``split`` (traced) gather from base_x, rows >= split from
    delta_x at offset row - split; same clipped-gather + where construction
    as ``margin_rerank_segmented``, so the margins equal a monolithic
    ``margin_batch`` over the concatenated live rows bit for bit.
    """
    is_base = candidates < split
    cb = base_x[jnp.clip(candidates, 0, base_x.shape[0] - 1)]
    cd = delta_x[jnp.clip(candidates - split, 0, delta_x.shape[0] - 1)]
    cx = jnp.where(is_base[..., None], cb, cd)
    return jnp.where(valid, _margins(cx, w_batch), jnp.inf)
