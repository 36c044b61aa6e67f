"""Pallas TPU kernels: packed-code Hamming distance scan and fused top-k.

dist[i] = popcount( XOR(codes[i, :], query[:]) ) summed over words.

This is the serving-side hot loop of the index: a memory-bound streaming
pass over the code table (k/8 bytes per point — the information-theoretic
minimum).  TPU exposes no popcount instruction, so the kernels use the SWAR
bit-trick (shift/mask adds) on 32-bit lanes in VMEM; the table is read from
HBM exactly once.

Two families of kernels live here:

- ``hamming_distance{_batch}_kernel`` — emit the full (n,) / (n, B) int32
  distance matrix to HBM and leave selection to jax.lax.top_k.  Fine for
  B=1 (4 bytes/point vs k/8-byte codes), but at B=32, k=128 the distance
  matrix costs 2·n·B·4 = 256 bytes/point of HBM round-trip against a
  16-byte/point code table — the scan stops being bandwidth-bound on codes.
- ``hamming_topk_fused_kernel`` — fuse selection into the scan.  Each grid
  block popcounts its lane-dense code tile (see below) against its B
  queries into a (B, block_n) VMEM tile (rows on lanes) and selects the
  block-local smallest-l candidates there (deterministic ties: lowest row
  index wins); only (grid, B, l) candidate (distance, row-id) pairs ever
  reach HBM.  A tiny second-stage merge over grid·l ≪ n rows (see
  kernels/ops.py) yields the final (B, l) answer, bit-identical to
  lax.top_k over the full distance matrix.
- ``hamming_topk_hist_kernel`` — same contract, cheaper selection.  The
  argmin kernel pays l rounds of masked argmin over the (B, block_n) tile:
  O(l·block_n·B) VPU work that dominates once HBM traffic is minimized.
  Hamming distances over k-bit codes are bounded integers in [0, 32·W],
  exactly the counting-sort regime: a two-pass **distance-histogram
  select** first finds, per query, the cutoff radius r_b — the smallest
  distance whose histogram prefix sum (CDF) reaches l — then emits every
  row with dist < r_b plus the lowest-row-index ties at r_b.  The CDF is
  evaluated lazily by bisection over the ≤ 32·W+1 possible distance
  values (count(dist ≤ mid) is one compare-reduce pass); row ranks are
  exact MXU products over 128-row chunks, and each output slot locates
  its row within one chunk, so selection costs
  O(block_n·B·log(32W) + l·B·(block_n/128 + 128)) instead of
  O(l·block_n·B) — independent of l for the tile passes, which makes deep
  scans (l in the hundreds) cheap.  A ``dma=True``
  variant additionally streams code tiles HBM→VMEM through a manually
  double-buffered ``pltpu.make_async_copy`` pipeline over the (G, blocks)
  grid, so popcount of tile i overlaps the fetch of tile i+1 (on CPU
  interpret mode the copies are synchronous — the variant exists for TPU,
  where BlockSpec streaming is replaced by explicit prefetch).

The fused kernels run on a (groups, blocks-per-group) grid: the code table
may be G stacked sub-tables (multi-table serving stacks L tables of
n_live rows each) and each block is matched against only its own group's
B query rows — so an L-table batched query is ONE kernel launch.

The fused kernels read codes LANE-DENSE: a (G, W, n_pad / 128, 128) array
in which row r of word w sits at [g, w, r // 128, r % 128].  A (G, n, W)
table with W = 1 word would fill one lane of 128 in every (8, 128) tile
the kernel's operand must be laid out in — 128x its bytes in HBM — while
the lane-dense table is exactly its bytes.  ``core.search.to_lanes`` builds
it; a block is (1, W, block_n / 128, 128), so a block that does not cover
the whole table needs block_n to be a multiple of 1024 (whole tiles).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Sentinel distance for masked (padded / out-of-range) rows: far above any
# real Hamming distance (<= 32·W) but negatable in int32.
DIST_SENTINEL = 0x3FFFFFFF

# Narrow-width candidate emission.  A fused-scan block only ever emits
# bounded values: distances <= 32·W and BLOCK-LOCAL row ids < block_n, so
# the (dist, id) pairs can leave VMEM as int16 (or uint8 distances where
# 32·W fits) and be widened at the tiny merge — the candidate term of the
# HBM traffic model shrinks 2x (int16) / 2.67x (uint8+int16); see
# ops.scan_traffic_model.  Each narrow dtype carries its own sentinel (its
# max value) so masked / impossible slots still sort after every real
# distance after packing; cand_encoding() is the overflow guard that keeps
# that ordering sound.
CAND_SENTINELS = {"none": DIST_SENTINEL, "16": 0x7FFF, "8": 0xFF}
_CAND_ID_MAX = 0x7FFF                  # ids are int16 in both narrow packs

# Per-core VMEM budget every launch must fit: double-buffered block inputs/
# outputs plus scratch.  Mirrored by repro.lint.kernel_contracts, which
# abstractly evaluates each registered entrypoint's launch geometry against
# it — keep the two in sync.
VMEM_BUDGET_BYTES = 16 * 2**20

# Lane width of a vreg.  The fused selects keep rows on lanes, so block_n
# must be a multiple of it (ops._block_rows rounds to it).
LANE = 128


def _lane_spec(w: int, block_n: int):
    """BlockSpec of one lane-dense (1, W, block_n / 128, 128) code tile on
    the (groups, blocks) grid."""
    return pl.BlockSpec((1, w, block_n // LANE, LANE),
                        lambda t, i: (t, 0, i, 0))


def cand_encoding(pack: str, w: int, block_n: int):
    """Resolve a candidate pack name to (dist_dtype, id_dtype, sentinel).

    The guard: real distances (<= 32·W) must stay STRICTLY below the narrow
    sentinel — otherwise a genuine max-distance row would collide with the
    masked-slot encoding and sort as if dead — and block-local row ids
    (< block_n) must fit the id dtype.  Raises ValueError on overflow
    instead of silently corrupting the tie/sentinel contract.
    """
    if pack not in CAND_SENTINELS:
        raise ValueError(f"cand pack must be one of {sorted(CAND_SENTINELS)},"
                         f" got {pack!r}")
    sent = CAND_SENTINELS[pack]
    if pack == "none":
        return jnp.int32, jnp.int32, sent
    if 32 * w >= sent:
        raise ValueError(
            f"cand pack {pack!r}: max Hamming distance 32·W = {32 * w} "
            f"would reach the narrow sentinel {sent} — masked slots could "
            f"no longer sort after real candidates (use a wider pack)")
    if block_n - 1 > _CAND_ID_MAX:
        raise ValueError(
            f"cand pack {pack!r}: block_n = {block_n} exceeds the int16 "
            f"block-local id range ({_CAND_ID_MAX + 1})")
    return (jnp.int16 if pack == "16" else jnp.uint8), jnp.int16, sent


def _popcount_u32(x):
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((x * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


def _kernel(codes_ref, query_ref, out_ref):
    x = jnp.bitwise_xor(codes_ref[...], query_ref[...])   # (BN, W) ^ (1, W)
    out_ref[...] = _popcount_u32(x).sum(axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def hamming_distance_kernel(codes, query, *, block_n: int = 2048,
                            interpret: bool = False):
    """codes: (n, W) uint32 with n % block_n == 0; query: (W,) uint32.
    Returns (n,) int32 distances."""
    n, w = codes.shape
    grid = (n // block_n,)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, w), lambda i: (i, 0)),
            pl.BlockSpec((1, w), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(codes, query[None, :])
    return out[:, 0]


def _batch_kernel(codes_ref, queries_ref, out_ref, *, n_words: int):
    # codes: (BN, W); queries: (B, W) resident whole (B*W words is tiny).
    out_ref[...] = _popcount_tile(codes_ref[...], queries_ref[...], n_words)


def _topk_fused_kernel(*refs, n_words: int, l: int, block_n: int,
                       n_valid: int, pack: str = "none",
                       masked: bool = False):
    """One grid step: scan a lane-dense (W, block_n / 128, 128) code tile
    against this group's B queries and emit the block-local smallest-l
    (distance, row-id) pairs.

    The (B, block_n) distance tile lives only in VMEM scratch (``acc_ref``)
    — it is never written to HBM.  Selection is l rounds of masked argmin;
    ``jnp.min`` over the row-iota of the minima keeps ties deterministic
    (lowest row index wins), matching lax.top_k's stable order.  Each
    round's (B, 1) minima land in column j of a (B, l) register tile, and
    the tile is stored once after the last round.

    Emitted ids are BLOCK-LOCAL (< block_n) and distances are clamped to
    the pack's sentinel, so both fit the narrow candidate dtype; the merge
    in ops.py widens and adds the block base back.  Selection still runs on
    the full int32 tile — only the HBM emission narrows.

    masked=True threads an extra (1, block_n) int32 activity row: rows
    whose flag is 0 (tombstones / pad) go to the sentinel before selection,
    exactly like rows past n_valid.
    """
    if masked:
        (codes_ref, queries_ref, act_ref,
         out_d_ref, out_i_ref, acc_ref) = refs
    else:
        codes_ref, queries_ref, out_d_ref, out_i_ref, acc_ref = refs
    acc = _popcount_lanes(codes_ref[0], queries_ref[0], n_words)
    # group-local row ids for this block; rows past the group's live region
    # (block padding) are masked to the sentinel so they always rank last.
    base = pl.program_id(1) * block_n
    rows = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
    acc = jnp.where(base + rows >= n_valid, jnp.int32(DIST_SENTINEL), acc)
    if masked:
        acc = jnp.where(act_ref[...] > 0, acc, jnp.int32(DIST_SENTINEL))
    acc_ref[...] = acc
    big_row = jnp.int32(jnp.iinfo(jnp.int32).max)
    b = acc.shape[0]
    slots = jax.lax.broadcasted_iota(jnp.int32, (b, l), 1)

    def select_one(j, tiles):
        out_d, out_i = tiles
        acc = acc_ref[...]
        dmin = jnp.min(acc, axis=1, keepdims=True)                # (B, 1)
        hit = acc == dmin
        rmin = jnp.min(jnp.where(hit, rows, big_row), axis=1,
                       keepdims=True)                             # (B, 1)
        acc_ref[...] = jnp.where(rows == rmin, jnp.int32(DIST_SENTINEL),
                                 acc)
        return (jnp.where(slots == j, dmin, out_d),
                jnp.where(slots == j, rmin, out_i))

    zero = jnp.zeros((b, l), jnp.int32)
    out_d, out_i = jax.lax.fori_loop(0, l, select_one, (zero, zero))
    out_d_ref[0, 0], out_i_ref[0, 0] = _pack_cand(out_d, out_i, pack,
                                                  n_words, block_n)


@functools.partial(jax.jit, static_argnames=("l", "n_valid", "block_n",
                                             "interpret", "pack"))
def hamming_topk_fused_kernel(codes, queries, l: int, n_valid: int, *,
                              active=None, block_n: int = 2048,
                              interpret: bool = False, pack: str = "none"):
    """Fused scan+select over G stacked code groups in ONE device launch.

    codes: (G, W, n_pad / 128, 128) uint32, lane-dense (module docstring),
    with n_pad % block_n == 0; queries: (G, B, W) uint32; n_valid: live
    rows per group (rows >= n_valid are padding).  Returns (dists, ids):
    (G, grid, B, l) block-local
    candidates, ids LOCAL to each block (< block_n — the merge adds the
    block base back); masked slots carry the pack's sentinel.  l must
    satisfy l <= block_n.

    pack selects the candidate emission width (``cand_encoding``): "none"
    = int32 pairs, "16" = int16 pairs, "8" = uint8 distances + int16 ids.
    Selection always runs on the int32 VMEM tile; only the HBM-bound
    emission narrows, so results are bit-identical after widening.

    active: optional (1, n_pad) int32 per-row activity flags, shared by all
    G groups; rows with flag 0 are masked to the sentinel before selection.
    A TRACED operand (its value is not a jit key), so mutable-index serving
    can flip tombstones without recompiling the scan.
    """
    g, w, r, _ = codes.shape
    b = queries.shape[1]
    grid_n = r * LANE // block_n
    d_dtype, i_dtype, _ = cand_encoding(pack, w, block_n)
    out_shapes = [jax.ShapeDtypeStruct((g, grid_n, b, l), d_dtype),
                  jax.ShapeDtypeStruct((g, grid_n, b, l), i_dtype)]
    in_specs = [_lane_spec(w, block_n),
                pl.BlockSpec((1, b, w), lambda t, i: (t, 0, 0))]
    operands = [codes, queries]
    if active is not None:
        in_specs.append(pl.BlockSpec((1, block_n), lambda t, i: (0, i)))
        operands.append(active)
    return pl.pallas_call(
        functools.partial(_topk_fused_kernel, n_words=w, l=l,
                          block_n=block_n, n_valid=n_valid, pack=pack,
                          masked=active is not None),
        grid=(g, grid_n),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, b, l), lambda t, i: (t, i, 0, 0)),
            pl.BlockSpec((1, 1, b, l), lambda t, i: (t, i, 0, 0)),
        ],
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM((b, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*operands)


def _popcount_tile(codes, queries, n_words: int):
    """(block_n, W) codes vs (B, W) queries -> (block_n, B) int32 distances.
    Word-by-word XOR keeps everything on 2-D VPU lanes (see _batch_kernel)."""
    acc = jnp.zeros((codes.shape[0], queries.shape[0]), jnp.int32)
    for w in range(n_words):
        x = jnp.bitwise_xor(codes[:, w][:, None], queries[:, w][None, :])
        acc += _popcount_u32(x)
    return acc


def _popcount_lanes(codes, queries, n_words: int):
    """(W, block_n / 128, 128) lane-dense codes vs (B, W) queries ->
    (B, block_n) int32 distances with the rows on lanes: the fused selects
    reduce over rows, and a (B, block_n) tile fills every lane where a
    (block_n, B) one would use B of 128.  Built one 128-row lane row of the
    codes at a time, each broadcast over the B query sublanes."""
    parts = []
    for j in range(codes.shape[1]):
        acc = _popcount_u32(jnp.bitwise_xor(codes[0, j:j + 1, :],
                                            queries[:, 0:1]))
        for w in range(1, n_words):
            acc += _popcount_u32(jnp.bitwise_xor(codes[w, j:j + 1, :],
                                                 queries[:, w:w + 1]))
        parts.append(acc)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _dot_exact(a, b):
    """Product of small non-negative integer tiles on the MXU, exact on
    every backend: bf16 holds each operand (0/1 or <= 256) exactly and the
    f32 accumulator holds every sum below 2**24."""
    return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32).astype(jnp.int32)


def _gather_exact(onehot, vals, vmax: int):
    """One-hot gather ``onehot @ vals`` of integers in [0, vmax], split
    into 8-bit digits so each MXU pass stays exact."""
    out = _dot_exact(onehot, vals & 0xFF)
    for shift in range(8, max(8, vmax.bit_length()), 8):
        out += _dot_exact(onehot, (vals >> shift) & 0xFF) << shift
    return out


def _chunk_prefix(x):
    """Inclusive prefix counts of a 0/1 (B, block_n) tile along its rows,
    by 128-row chunks.  Returns (within (B, nc, 128): the count inside the
    chunk up to and including each row, start (B, nc): the count in all
    earlier chunks).  The in-chunk scan is one MXU product with a
    triangular 0/1 matrix; the chunk offsets are a small VPU reduction."""
    b, bn = x.shape
    nc = bn // LANE
    r = jax.lax.broadcasted_iota(jnp.int32, (LANE, LANE), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (LANE, LANE), 1)
    within = _dot_exact(x.reshape(b * nc, LANE),
                        (r <= c).astype(jnp.int32)).reshape(b, nc, LANE)
    tot = within[:, :, LANE - 1]                              # (B, nc)
    earlier = (jax.lax.broadcasted_iota(jnp.int32, (1, nc, nc), 2)
               < jax.lax.broadcasted_iota(jnp.int32, (1, nc, nc), 1))
    start = jnp.sum(jnp.where(earlier, tot[:, None, :], 0), axis=2)
    return within, start


def _hist_select(acc, base, l: int, n_valid: int, max_dist: int,
                 block_n: int, act=None):
    """Two-pass counting-sort select over one (B, block_n) distance tile.

    Pass 1 finds, per query, the cutoff radius r_b = the smallest distance
    value whose histogram prefix sum reaches t = min(l, live rows in this
    block).  The prefix sums (the distance CDF) are evaluated lazily by
    bisection over [0, max_dist] — each probe is one compare-reduce pass —
    instead of materializing all ≤ max_dist+1 bins: O(block_n·B·log maxd).

    Pass 2 keeps the rows with dist < r_b plus the deterministically-tied
    rows at r_b (lowest row index wins, matching lax.top_k's stable order;
    the tie rank is a row prefix count) and emits kept row j to output
    slot j.  Rows are cut into 128-row chunks: ``_chunk_prefix`` gives each
    row its rank, slot j finds its chunk by comparing j with the (B, nc)
    chunk offsets, and one MXU product with that one-hot gathers the
    chunk's ranks and distances, whose lane count locates the row:
    O(l·B·(nc + 128)) instead of O(l·B·block_n).  Output slots are in row
    order, NOT distance order — the contract only requires the exact
    smallest-l *set* per block (ties to lowest row); the second-stage
    lexicographic (distance, id) merge in ops.py restores sorted order.

    acc: (B, block_n) int32 distances; act: optional (1, block_n) int32
    activity row.  Returns (out_d, out_i): (B, l) int32 with BLOCK-LOCAL
    ids (< block_n; the merge adds the block base back); slots past the
    live-row count carry (DIST_SENTINEL, block_n - 1) exactly like the
    exhausted slots of the argmin kernel — the merge maps them to id -1.
    """
    b, bn = acc.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
    live = base + rows < n_valid                              # (1, block_n)
    if act is None:
        t = jnp.minimum(jnp.clip(n_valid - base, 0, block_n), l)  # scalar
    else:
        # activity flags (tombstones / pad) shrink the live count further;
        # traced, so flipping a tombstone never recompiles the select
        live = live & (act > 0)
        t = jnp.minimum(jnp.sum(live.astype(jnp.int32)), l)
    acc = jnp.where(live, acc, jnp.int32(DIST_SENTINEL))

    # -- pass 1: cutoff radius per query via bisection on the distance CDF.
    # invariant: count(acc <= hi) >= t (true at hi = max_dist: every live
    # row's distance is <= 32·W and padding rows sit at the sentinel).
    lo = jnp.zeros((b, 1), jnp.int32)
    hi = jnp.full((b, 1), max_dist, jnp.int32)
    for _ in range(max(1, max_dist.bit_length())):
        mid = (lo + hi) >> 1
        cnt = jnp.sum((acc <= mid).astype(jnp.int32), axis=1, keepdims=True)
        ge = cnt >= t
        hi = jnp.where(ge, mid, hi)
        lo = jnp.where(ge, lo, mid + 1)
    r = hi                                                    # (B, 1)

    # -- pass 2: keep mask with lowest-row-index ties at the cutoff.
    less = jnp.sum((acc < r).astype(jnp.int32), axis=1, keepdims=True)
    tie = acc == r
    within, start = _chunk_prefix(tie.astype(jnp.int32))
    tie_rank = (within + start[:, :, None]).reshape(b, bn) - 1
    keep = (acc < r) | (tie & (tie_rank < (t - less)))

    # -- emit: kept row j goes to slot j, located chunk by chunk, one query
    # at a time so every temporary is a small (l, 128) tile.
    within, start = _chunk_prefix(keep.astype(jnp.int32))
    end = start + within[:, :, LANE - 1]                      # (B, nc)
    nc = bn // LANE
    # bound the distances so the one-hot gather stays exact: every kept
    # row sits at <= max_dist, the rest never reach an output slot.
    acc_c = jnp.minimum(acc, max_dist + 1).reshape(b, nc, LANE)
    slot = jax.lax.broadcasted_iota(jnp.int32, (l, 1), 0)
    chunk_id = jax.lax.broadcasted_iota(jnp.int32, (1, nc), 1)
    lane_id = jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1)
    query = jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)

    def emit(within_ref, acc_ref, start_ref, end_ref):
        within_ref[...] = within
        acc_ref[...] = acc_c
        start_ref[...] = start[:, None, :]
        end_ref[...] = end[:, None, :]

        def emit_one(q, tiles):
            start_q, end_q = start_ref[q], end_ref[q]          # (1, nc)
            onehot = ((start_q <= slot) & (slot < end_q)).astype(jnp.int32)
            off = jnp.sum(onehot * start_q, axis=1, keepdims=True)  # (l, 1)
            chunk = jnp.sum(onehot * chunk_id, axis=1, keepdims=True)
            rank = _dot_exact(onehot, within_ref[q])           # (l, 128)
            lane = jnp.sum((rank <= slot - off).astype(jnp.int32), axis=1,
                           keepdims=True)                      # (l, 1)
            dist = _gather_exact(onehot, acc_ref[q], max_dist + 1)
            d_sel = jnp.sum(jnp.where(lane_id == lane, dist, 0), axis=1,
                            keepdims=True)
            mine = query == q
            return (jnp.where(mine, d_sel, tiles[0]),
                    jnp.where(mine, chunk * LANE + lane, tiles[1]))

        zero = jnp.zeros((l, b), jnp.int32)
        return jax.lax.fori_loop(0, b, emit_one, (zero, zero))

    d_sel, i_sel = pl.run_scoped(
        emit, pltpu.VMEM((b, nc, LANE), jnp.int32),
        pltpu.VMEM((b, nc, LANE), jnp.int32),
        pltpu.VMEM((b, 1, nc), jnp.int32), pltpu.VMEM((b, 1, nc), jnp.int32))
    slot_ok = slot < t                                        # (l, 1)
    out_d = jnp.where(slot_ok, d_sel, jnp.int32(DIST_SENTINEL))
    out_i = jnp.where(slot_ok, i_sel, bn - 1)
    return out_d.T, out_i.T                                   # (B, l) each


def _pack_cand(out_d, out_i, pack: str, n_words: int, block_n: int):
    """Narrow one block's int32 (B, l) candidates to the pack's emission
    dtypes: distances clamp to the narrow sentinel (real distances stay
    strictly below it — cand_encoding guards), block-local ids just cast."""
    d_dtype, i_dtype, d_sent = cand_encoding(pack, n_words, block_n)
    return (jnp.minimum(out_d, d_sent).astype(d_dtype),
            out_i.astype(i_dtype))


def _topk_hist_kernel(*refs, n_words: int, l: int, block_n: int,
                      n_valid: int, max_dist: int, pack: str = "none",
                      masked: bool = False):
    """One grid step of the histogram-select fused scan (BlockSpec-streamed
    code tiles; see _topk_hist_dma_kernel for the manual-DMA variant).
    masked=True threads a (1, block_n) int32 activity row into the select
    (rows with flag 0 rank at the sentinel)."""
    if masked:
        codes_ref, queries_ref, act_ref, out_d_ref, out_i_ref = refs
        act = act_ref[...]
    else:
        codes_ref, queries_ref, out_d_ref, out_i_ref = refs
        act = None
    acc = _popcount_lanes(codes_ref[0], queries_ref[0], n_words)
    base = pl.program_id(1) * block_n
    out_d, out_i = _hist_select(acc, base, l, n_valid, max_dist, block_n,
                                act)
    out_d_ref[0, 0], out_i_ref[0, 0] = _pack_cand(out_d, out_i, pack,
                                                  n_words, block_n)


def _topk_hist_dma_kernel(*refs, n_words: int, l: int,
                          block_n: int, n_valid: int, max_dist: int,
                          grid_n: int, pack: str = "none",
                          masked: bool = False):
    """Histogram-select step with a double-buffered HBM→VMEM code pipeline.

    The lane-dense code stack stays in HBM (memory_space=ANY), so a tile's
    copy slices only whole 128-row lane rows (Mosaic refuses a DMA whose
    last dimension is a sub-128 W); each sequential step of
    the (G, blocks) grid waits on the async copy of its own tile (started
    by the previous step) and immediately starts the copy of the next tile
    into the other buffer, so the popcount of tile i overlaps the fetch of
    tile i+1.  VMEM scratch persists across grid steps (the grid is
    ("arbitrary", "arbitrary"), i.e. sequential), which is what carries the
    in-flight copy across the step boundary.
    """
    if masked:
        (codes_hbm_ref, queries_ref, act_ref,
         out_d_ref, out_i_ref, buf_ref, sem_ref) = refs
        act = act_ref[...]
    else:
        (codes_hbm_ref, queries_ref,
         out_d_ref, out_i_ref, buf_ref, sem_ref) = refs
        act = None
    t, i = pl.program_id(0), pl.program_id(1)
    step = t * grid_n + i                  # linear position in the grid
    n_steps = pl.num_programs(0) * grid_n
    slot = jax.lax.rem(step, 2)
    nxt_slot = jax.lax.rem(step + 1, 2)
    nxt_t = (step + 1) // grid_n
    nxt_i = jax.lax.rem(step + 1, grid_n)

    def copy_tile(slot_idx, g_idx, blk_idx):
        return pltpu.make_async_copy(
            codes_hbm_ref.at[g_idx, :, pl.dslice(blk_idx * (block_n // LANE),
                                                  block_n // LANE)],
            buf_ref.at[slot_idx],
            sem_ref.at[slot_idx])

    @pl.when(step == 0)                    # warm-up: fetch the first tile
    def _():
        copy_tile(slot, t, i).start()

    @pl.when(step + 1 < n_steps)           # prefetch the next tile
    def _():
        copy_tile(nxt_slot, nxt_t, nxt_i).start()

    copy_tile(slot, t, i).wait()
    acc = _popcount_lanes(buf_ref[slot], queries_ref[0], n_words)
    out_d, out_i = _hist_select(acc, i * block_n, l, n_valid, max_dist,
                                block_n, act)
    out_d_ref[0, 0], out_i_ref[0, 0] = _pack_cand(out_d, out_i, pack,
                                                  n_words, block_n)


@functools.partial(jax.jit, static_argnames=("l", "n_valid", "block_n",
                                             "interpret", "dma", "pack"))
def hamming_topk_hist_kernel(codes, queries, l: int, n_valid: int, *,
                             active=None, block_n: int = 2048,
                             interpret: bool = False, dma: bool = False,
                             pack: str = "none"):
    """Histogram-select fused scan: same shapes, grid and block-local
    candidate contract as ``hamming_topk_fused_kernel`` (ids are BLOCK-LOCAL,
    masked slots carry the pack's sentinel; each block's l slots hold the
    exact block-local smallest-l set with ties to the lowest row index),
    but selection is the two-pass counting-sort of ``_hist_select`` instead
    of l argmin rounds.  The per-block slot order differs from the argmin
    kernel (row order, not distance order) — results are bit-identical
    after the (distance, id) merge in ops.hamming_topk_grouped.

    pack narrows the candidate emission dtypes exactly as in
    ``hamming_topk_fused_kernel`` ("none" / "16" / "8"); selection always
    runs on the int32 VMEM tile.

    codes: (G, W, n_pad / 128, 128) uint32, lane-dense (module docstring).
    dma=True streams code tiles through the manually double-buffered async
    copy pipeline (``codes`` stays in HBM/ANY memory space); dma=False uses
    ordinary BlockSpec streaming.  Both are exact.

    active: optional (1, n_pad) int32 per-row activity flags shared by all
    G groups (0 = tombstone / pad -> sentinel before selection); traced, so
    serving can flip tombstones without recompiling.
    """
    g, w, r, _ = codes.shape
    b = queries.shape[1]
    grid_n = r * LANE // block_n
    max_dist = 32 * w
    d_dtype, i_dtype, _ = cand_encoding(pack, w, block_n)
    out_shapes = [jax.ShapeDtypeStruct((g, grid_n, b, l), d_dtype),
                  jax.ShapeDtypeStruct((g, grid_n, b, l), i_dtype)]
    out_specs = [
        pl.BlockSpec((1, 1, b, l), lambda t, i: (t, i, 0, 0)),
        pl.BlockSpec((1, 1, b, l), lambda t, i: (t, i, 0, 0)),
    ]
    act_spec = pl.BlockSpec((1, block_n), lambda t, i: (0, i))
    if not dma:
        in_specs = [_lane_spec(w, block_n),
                    pl.BlockSpec((1, b, w), lambda t, i: (t, 0, 0))]
        operands = [codes, queries]
        if active is not None:
            in_specs.append(act_spec)
            operands.append(active)
        return pl.pallas_call(
            functools.partial(_topk_hist_kernel, n_words=w, l=l,
                              block_n=block_n, n_valid=n_valid,
                              max_dist=max_dist, pack=pack,
                              masked=active is not None),
            grid=(g, grid_n),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shapes,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
        )(*operands)
    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),            # codes stay in HBM
        pl.BlockSpec((1, b, w), lambda t, i: (t, 0, 0)),
    ]
    operands = [codes, queries]
    if active is not None:
        in_specs.append(act_spec)
        operands.append(active)
    return pl.pallas_call(
        functools.partial(_topk_hist_dma_kernel, n_words=w, l=l,
                          block_n=block_n, n_valid=n_valid,
                          max_dist=max_dist, grid_n=grid_n, pack=pack,
                          masked=active is not None),
        grid=(g, grid_n),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((2, w, block_n // LANE, LANE), jnp.uint32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*operands)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def hamming_distance_batch_kernel(codes, queries, *, block_n: int = 2048,
                                  interpret: bool = False):
    """Batched scan: codes (n, W) with n % block_n == 0; queries (B, W).
    Returns (n, B) int32 distances — the code table streams from HBM once
    for the whole batch instead of once per query."""
    n, w = codes.shape
    b = queries.shape[0]
    grid = (n // block_n,)
    return pl.pallas_call(
        functools.partial(_batch_kernel, n_words=w),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, w), lambda i: (i, 0)),
            pl.BlockSpec((b, w), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, b), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(codes, queries)
