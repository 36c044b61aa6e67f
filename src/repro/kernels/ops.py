"""Jit'd public wrappers around the Pallas kernels.

These handle shape padding (kernels require block-aligned shapes), choose
interpret mode automatically off-TPU, and compose with lax.top_k / XLA
matmuls where the MXU/XLA path is already optimal.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.search import (LANE_BLOCK, LANE_TILE, env_cand_pack,
                               env_fused_select, to_lanes)
from repro.kernels.bilinear_hash import (bilinear_hash_kernel,
                                         bilinear_hash_seeded_kernel)
from repro.kernels.hamming import (DIST_SENTINEL, LANE, cand_encoding,
                                   hamming_distance_batch_kernel,
                                   hamming_distance_kernel,
                                   hamming_topk_fused_kernel,
                                   hamming_topk_hist_kernel)
from repro.kernels.lbh_grad import lbh_chain_kernel
from repro.utils.bits import n_words

WORD = 32
SUBLANE = 8   # f32/i32 sublane quantum: the query batch is padded to it


def _interpret_default(interpret):
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


# Elements of one (B, block_n) int32 distance tile.  The fused selects keep
# a few such tiles live in VMEM; past this size a large query batch would
# overflow a v5e core's 16 MiB scoped VMEM, so the row block shrinks.
_TILE_ELEMS = 2 ** 18


def _block_rows(n: int, block_n: int, b: int = 1) -> int:
    """Row-block size for an n-row scan of a b-query batch: at most
    block_n (and at most _TILE_ELEMS / b), at least min(n, 256), rounded UP
    to the lane width (a raw min(block_n, n) could pick e.g. 300, which is
    not a legal (8, 128)-tiled block, and the fused selects lay a block's
    rows along 128-wide lanes)."""
    bn = min(block_n, max(256, n), max(256, _TILE_ELEMS // b))
    return -(-bn // LANE) * LANE


def _pad_to(x, axis: int, mult: int):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("block_n", "block_k", "block_d",
                                             "interpret"))
def bilinear_hash(x, u, v, *, block_n: int = 256, block_k: int = 128,
                  block_d: int = 512, interpret: bool | None = None):
    """Packed BH/LBH codes for a batch of points.

    x: (n, d); u, v: (d, k).  Returns (n, ceil(k/32)) uint32 — identical to
    ref.bilinear_hash_ref (pad bits forced to 0).
    """
    n, d = x.shape
    k = u.shape[1]
    w = n_words(k)
    # single k-block: the out BlockSpec's lane dim is block_k//32, which
    # only tiles the packed axis legally when it spans ALL of it (a smaller
    # k-block would write 4-lane slivers against the 128-lane tile grid)
    block_k = k + ((-k) % block_k)
    x = _pad_to(_pad_to(x.astype(jnp.float32), 0, block_n), 1, block_d)
    u = _pad_to(_pad_to(u.astype(jnp.float32), 0, block_d), 1, block_k)
    v = _pad_to(_pad_to(v.astype(jnp.float32), 0, block_d), 1, block_k)
    packed = bilinear_hash_kernel(
        x, u, v, block_n=block_n, block_k=block_k, block_d=block_d,
        interpret=_interpret_default(interpret))
    packed = packed[:n, :w]
    # zero-projection pad columns hash to sgn(0)=+1; mask them off so packed
    # codes match pack_signs semantics (pad bits = 0).
    rem = k - (w - 1) * WORD
    if rem < WORD:
        mask = jnp.uint32((1 << rem) - 1)
        packed = packed.at[:, -1].set(packed[:, -1] & mask)
    return packed


@functools.partial(jax.jit, static_argnames=("k", "block_n", "block_k",
                                             "block_d", "interpret"))
def bilinear_hash_seeded_grouped(x, seeds, k: int, *, block_n: int = 256,
                                 block_k: int = 128, block_d: int = 512,
                                 interpret: bool | None = None):
    """Packed seed-generated BH codes for G tables in ONE launch.

    x: (n, d) shared by all tables; seeds: (G,) uint32 per-table seeds
    (SeededBHHash.seed / seed_from_key).  Returns (G, n, ceil(k/32)) uint32
    with group g bit-identical to
    ``bilinear_hash(x, *seeded_projections(seeds[g], d, k))``:

    - pad ROWS of x are zero, so the gaussians the kernel generates past
      the true d multiply exactly 0.0 into every accumulator lane (a ±0.0
      term never changes a float sum except in the sign of a zero total,
      and the sign pack uses ``>= 0``, which both zeros satisfy);
    - pad COLUMNS past the true k produce gaussian-derived bits where the
      materialized path's zero-padded projections give sgn(0)=+1, but both
      live past bit k and the same mask below forces them to 0.

    Zero projection-weight HBM reads — this is the hashing half of the
    HBM-minimal serving path (hash_traffic_model counts the win).
    """
    n, d = x.shape
    w = n_words(k)
    x = _pad_to(_pad_to(x.astype(jnp.float32), 0, block_n), 1, block_d)
    # single k-block, same lane-tiling rule as bilinear_hash above
    k_pad = k + ((-k) % block_k)
    codes = bilinear_hash_seeded_kernel(
        x, seeds.reshape(-1, 1).astype(jnp.uint32), k=k_pad,
        block_n=block_n, block_k=k_pad, block_d=block_d,
        interpret=_interpret_default(interpret))
    codes = codes[:, :n, :w]
    rem = k - (w - 1) * WORD
    if rem < WORD:
        mask = jnp.uint32((1 << rem) - 1)
        codes = codes.at[:, :, -1].set(codes[:, :, -1] & mask)
    return codes


def bilinear_hash_seeded(x, seed, k: int, *, block_n: int = 256,
                         block_k: int = 128, block_d: int = 512,
                         interpret: bool | None = None):
    """Single-table seed-generated hash: (n, ceil(k/32)) uint32 codes,
    bit-identical to ``bilinear_hash(x, *seeded_projections(seed, d, k))``.
    """
    codes = bilinear_hash_seeded_grouped(
        x, jnp.atleast_1d(jnp.asarray(seed, jnp.uint32)), k,
        block_n=block_n, block_k=block_k, block_d=block_d,
        interpret=interpret)
    return codes[0]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def hamming_distances(codes, query, *, block_n: int = 2048,
                      interpret: bool | None = None):
    """(n,) int32 distances between packed code rows and one packed query."""
    n = codes.shape[0]
    bn = _block_rows(n, block_n)
    padded = _pad_to(codes, 0, bn)
    d = hamming_distance_kernel(padded, query, block_n=bn,
                                interpret=_interpret_default(interpret))
    return d[:n]


def hamming_topk(codes, query, l: int, *, block_n: int = 4096,
                 interpret: bool | None = None, select: str | None = None,
                 pack: str | None = None):
    """Smallest-l Hamming matches: (dists (l,), idx (l,)).

    Routed through the fused scan+select kernel — the full distance vector
    never leaves VMEM.  Bit-identical to lax.top_k(-dists, l) (ties break
    to the lowest index); slots past n carry DIST_SENTINEL / id -1.
    """
    d, idx = hamming_topk_grouped(codes[None], query[None, None, :], l,
                                  block_n=block_n, interpret=interpret,
                                  select=select, pack=pack)
    return d[0, 0], idx[0, 0]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def hamming_distances_batch(codes, queries, *, block_n: int = 2048,
                            interpret: bool | None = None):
    """(B, n) int32 distances between one code table and B packed queries."""
    n = codes.shape[0]
    bn = _block_rows(n, block_n)
    padded = _pad_to(codes, 0, bn)
    # sublane-align the query batch; extra rows are scanned then dropped.
    q = _pad_to(queries, 0, 8)
    d = hamming_distance_batch_kernel(padded, q, block_n=bn,
                                      interpret=_interpret_default(interpret))
    return d[:n, :queries.shape[0]].T


def hamming_topk_batch(codes, queries, l: int, *, block_n: int = 4096,
                       interpret: bool | None = None,
                       select: str | None = None,
                       pack: str | None = None):
    """Batched smallest-l matches: (dists (B, l), idx (B, l)).

    Fused scan+select: HBM traffic is the code table plus O(grid·B·l)
    candidate pairs instead of the full (n, B) distance matrix (see
    scan_traffic_model).  Bit-identical to lax.top_k over the distances.
    """
    d, idx = hamming_topk_grouped(codes[None], queries[None], l,
                                  block_n=block_n, interpret=interpret,
                                  select=select, pack=pack)
    return d[0], idx[0]


def hamming_topk_grouped(codes, queries, l: int, *, block_n: int = 4096,
                         interpret: bool | None = None,
                         select: str | None = None, dma: bool = False,
                         active=None, pack: str | None = None):
    """Fused smallest-l scan over G stacked code groups, ONE kernel launch.

    codes: (G, n, W) uint32 — G sub-tables over the same row space (the
    multi-table index stacks its L tables' live codes); queries: (G, B, W)
    uint32 — group g's queries are matched against group g's codes only.
    Returns (dists (G, B, l) int32, ids (G, B, l) int32) with ids local to
    the group's row space, sorted ascending by (distance, id) — bit-identical
    to per-group jax.lax.top_k(-dists).  When l > n the tail columns carry
    (DIST_SENTINEL, -1).  The codes are laid out lane-dense
    (``core.search.to_lanes``) inside the same program; a caller that keeps
    them lane-dense calls ``hamming_topk_lanes``.

    select: block-local selection algorithm — ``"hist"`` (default;
    counting-sort select, O(block_n·B·log 32W) tile passes independent of
    l) or ``"argmin"`` (legacy l-round masked argmin; the
    ``REPRO_FUSED_SELECT=argmin`` escape hatch).  dma=True additionally
    routes the hist kernel through its manually double-buffered HBM→VMEM
    copy pipeline (TPU overlap; argmin ignores it).  All combinations are
    bit-identical — the env knob and flags only trade selection cost.

    active: optional (n,) bool per-row liveness flags shared by all G
    groups — False rows (tombstones / pad) are masked to the sentinel
    inside selection, so the result is the top-l of the live rows alone.
    Traced (NOT a jit key): mutable-index serving flips tombstones without
    recompiling the scan.

    pack: candidate emission width — ``"16"`` (default; int16 (dist, id)
    pairs, half the candidate HBM bytes), ``"8"`` (uint8 distances, legal
    while 32·W < 255), or ``"none"`` (int32 escape hatch); None reads
    REPRO_CAND_PACK.  Kernels emit BLOCK-LOCAL ids clamped to the pack's
    sentinel; this wrapper widens at the merge (sentinel -> DIST_SENTINEL,
    id += block base), so every pack is bit-identical end to end.
    """
    select = env_fused_select(select)
    pack = env_cand_pack(pack)
    return _topk_grouped_rows(codes, queries, active, l, block_n=block_n,
                              interpret=_interpret_default(interpret),
                              select=select, dma=dma, pack=pack)


@functools.partial(jax.jit, static_argnames=("l", "block_n", "interpret",
                                             "select", "dma", "pack"))
def _topk_grouped_rows(codes, queries, active, l: int, *, block_n: int,
                       interpret: bool, select: str, dma: bool, pack: str):
    """``hamming_topk_grouped``'s program: the (G, n, W) codes laid out
    lane-dense, then the fused scan.  A block that does not cover the table
    is cut to whole (8, 128) tiles."""
    n = codes.shape[1]
    bn = _block_rows(n, block_n, -(-queries.shape[1] // SUBLANE) * SUBLANE)
    if LANE_TILE <= bn < n:
        bn -= bn % LANE_TILE
    return _topk_grouped_impl(to_lanes(codes, -(-n // bn) * bn), queries,
                              active, l, n=n, block_n=bn,
                              interpret=interpret, select=select, dma=dma,
                              pack=pack)


def lane_block(n_pad: int, b: int) -> int:
    """Row block of a fused scan over a lane-dense table of n_pad rows
    (``core.search.lane_rows``) at a b-query batch: the largest the VMEM
    cap (``_TILE_ELEMS`` / b, at most the table's padding granule
    ``LANE_BLOCK``) allows — the whole table where it fits one block, else
    the largest power-of-two multiple of 1024 rows under the cap that
    divides n_pad.  A scan's cost is mostly per block (the select
    emits l slots a block, and the merge sorts them all): 2.5e7 codes, 10
    queries on one v5e take 92.7 ms a scan at 4096 rows a block, 32.7 ms
    at 16384."""
    cap = max(LANE_TILE, min(LANE_BLOCK, _TILE_ELEMS // b))
    if n_pad <= cap:
        return n_pad
    bn = LANE_TILE
    while bn * 2 <= cap and n_pad % (bn * 2) == 0:
        bn *= 2
    return bn


def hamming_topk_lanes(codes, queries, l: int, n: int, *,
                       interpret: bool | None = None,
                       select: str | None = None, active=None,
                       pack: str | None = None):
    """``hamming_topk_grouped`` over codes kept lane-dense: codes
    (G, W, n_pad / 128, 128) with n_pad = ``core.search.lane_rows(n)``,
    rows >= n padding.  The scan reads them where they lie: no pad and no
    relayout of the table in the program."""
    select = env_fused_select(select)
    pack = env_cand_pack(pack)
    b = -(-queries.shape[1] // SUBLANE) * SUBLANE
    return _topk_grouped_impl(codes, queries, active, l, n=n,
                              block_n=lane_block(codes.shape[2] * LANE, b),
                              interpret=_interpret_default(interpret),
                              select=select, dma=False, pack=pack)


@functools.partial(jax.jit, static_argnames=("l", "n", "block_n",
                                             "interpret", "select", "dma",
                                             "pack"))
def _topk_grouped_impl(codes, queries, active, l: int, *, n: int,
                       block_n: int, interpret: bool, select: str, dma: bool,
                       pack: str):
    g, w, r, _ = codes.shape
    b = queries.shape[1]
    q = _pad_to(queries, 1, SUBLANE)
    bn = block_n
    l_k = min(l, bn)    # a block holds bn rows; l_k = bn already emits all
    act = None
    if active is not None:
        act = _pad_to(active.astype(jnp.int32)[None, :], 1, r * LANE)
    if select == "hist":
        cd, ci = hamming_topk_hist_kernel(
            codes, q, l_k, n, active=act, block_n=bn, interpret=interpret,
            dma=dma, pack=pack)
    else:
        cd, ci = hamming_topk_fused_kernel(
            codes, q, l_k, n, active=act, block_n=bn, interpret=interpret,
            pack=pack)
    grid_n = cd.shape[1]
    # widen the narrow block emission: the pack sentinel (the clamp of
    # DIST_SENTINEL — real distances <= 32·W sit strictly below it, which
    # cand_encoding guards) maps back to DIST_SENTINEL, and the block-local
    # ids get their block's row base added.  Sentinel-slot ids (-1 + base)
    # are garbage but harmless: their distance is DIST_SENTINEL, so the
    # final where() below rewrites them to -1, and ties among sentinel
    # slots collapse to identical (DIST_SENTINEL, -1) pairs.
    _, _, d_sent = cand_encoding(pack, w, bn)
    cd = cd.astype(jnp.int32)
    cd = jnp.where(cd == d_sent, jnp.int32(DIST_SENTINEL), cd)
    blk = (jnp.arange(grid_n, dtype=jnp.int32) * bn)[None, :, None, None]
    ci = ci.astype(jnp.int32) + blk
    # second-stage merge over grid·l_k candidates per (group, query):
    # lexicographic (distance, id) sort keeps ties at the lowest id, exactly
    # like lax.top_k over the full distance row.
    cd = cd.transpose(0, 2, 1, 3).reshape(g, -1, grid_n * l_k)[:, :b]
    ci = ci.transpose(0, 2, 1, 3).reshape(g, -1, grid_n * l_k)[:, :b]
    cd, ci = jax.lax.sort((cd, ci), dimension=2, num_keys=2)
    cd, ci = cd[..., :l], ci[..., :l]
    if cd.shape[-1] < l:          # l > n_pad: pad out the impossible tail
        pad = [(0, 0), (0, 0), (0, l - cd.shape[-1])]
        cd = jnp.pad(cd, pad, constant_values=DIST_SENTINEL)
        ci = jnp.pad(ci, pad, constant_values=-1)
    ci = jnp.where(cd >= DIST_SENTINEL, -1, ci)
    return cd, ci


# bytes of one emitted (distance, id) candidate pair per pack width:
# int32+int32, int16+int16, uint8+int16 (ids stay 16-bit — block-local row
# numbers need the range; only the distance narrows further).
CAND_PAIR_BYTES = {"none": 8, "16": 4, "8": 3}


def scan_cand_model(n: int, b: int, l: int, block_n: int = 4096,
                    g: int = 1, pack: str = "16") -> int:
    """Modeled HBM bytes of the fused scan's candidate emission alone: the
    (g, grid, B, l) block-local (distance, id) pairs, written once by the
    kernel and read back once by the merge.  This is the term candidate
    packing shrinks (2x for int16, 8/3x for uint8) and the term
    check_regression.py gates — at B=32, l=128 it rivals the code stream
    itself, so halving it is the difference between a scan that is
    code-stream-bound and one that is not."""
    bn = _block_rows(n, block_n, -(-b // SUBLANE) * SUBLANE)
    grid = -(-n // bn)
    return 2 * g * grid * b * min(l, bn) * CAND_PAIR_BYTES[pack]


def scan_traffic_model(n: int, w: int, b: int, l: int = 16,
                       block_n: int = 4096, fused: bool = True,
                       g: int = 1, pack: str = "16") -> int:
    """Modeled HBM bytes for one batched Hamming scan launch.

    g is the group count of the launch: a grouped scan (G stacked
    sub-tables, the multi-table serving path) streams G·n·W·4 code bytes
    and G·B·W·4 query bytes, and emits G·grid·B·l candidate pairs — every
    term scales by G, so ratios are G-invariant but per-launch totals are
    not (g=1 used to under-model what query_scan_batch actually runs by
    exactly a factor of L).

    Unfused: stream the code groups once (g·n·W·4) plus write and read back
    the full g·(n, B) int32 distance matrices for lax.top_k (2·g·n·B·4).
    Fused: stream the code groups once plus write and read back only the
    (g, grid, B, l) block-local candidate (distance, id) pairs
    (scan_cand_model; ``pack`` picks the pair width — "16" is the serving
    default, "none" the int32 legacy).  Query bytes (g·B·W·4) are counted
    for both; at B=32, k=128, l=16, block_n=4096 the fused int16 path cuts
    traffic ~16x vs unfused (272 -> ~17 bytes/point, any g; the code
    stream's 16 bytes/point bound the ratio at ~17x regardless of pack).
    Selection algorithm (hist/argmin) does not change traffic — both
    kernels emit the same candidate pairs; see scan_select_model for the
    term that differs.
    """
    code_bytes = g * (n * w * 4 + b * w * 4)
    if not fused:
        return code_bytes + 2 * g * n * b * 4
    return code_bytes + scan_cand_model(n, b, l, block_n, g, pack)


def hash_traffic_model(n: int, d: int, k: int, g: int = 1,
                       seeded: bool = False) -> int:
    """Modeled HBM bytes for hashing n points into G tables of k bits.

    Per table: stream the points (n·d·4), stream the materialized (d, k)
    U, V factors (2·d·k·4) — or NOTHING when ``seeded`` (the kernel
    regenerates the factors in-register from the table's 32-bit seed) —
    and write the packed codes (n·W·4).  At serving shapes the weight
    stream dominates small-batch hashing (B=32, d=64, k=128: 74240 vs
    8704 bytes per table, an 8.5x cut), and it is the only term that
    scales with L for a FIXED query batch — seeded hashing makes growing
    L free on the hash side.  The point stream is counted once per table
    (the grouped kernel re-reads x per group; grid reuse across g is a
    compiler choice we don't model)."""
    w = n_words(k)
    weights = 0 if seeded else 2 * d * k * 4
    return g * (n * d * 4 + weights + n * w * 4)


def scan_select_model(n: int, b: int, l: int = 16, k: int = 128,
                      block_n: int = 4096, select: str = "hist",
                      g: int = 1) -> int:
    """Modeled VPU element-ops the fused scan spends on *selection* for one
    launch (popcount cost is identical either way and excluded).  HBM
    traffic (scan_traffic_model) is also selection-invariant — both kernels
    emit the same (grid, B, l) candidate pairs — so this is the term that
    decides fused-scan latency once traffic is minimized.

    - ``argmin``: l rounds of masked argmin over each (block_n, B) tile;
      each round is ~3 full-tile passes (min-reduce, tie-break row min,
      sentinel mask update) -> 3·l·block_n·B per block.  Grows linearly
      with l — at l=512 the selection costs 1536 tile passes.
    - ``hist``: two-pass counting-sort select; the distance-CDF bisection
      is ceil(log2(32·ceil(k/32)+1)) compare-reduce tile passes, plus ~6
      fixed passes (cutoff counts, tie and keep masks, chunk-prefix
      offsets); the row prefix counts are MXU products (not counted), and
      the emission costs ~4·(block_n/128 + 128)·l·B per block (each slot
      compares against the chunk offsets, then scans one 128-row chunk
      for its rank and distance) -> flat in l in the tile term.

    The crossover sits near l ≈ 5; everywhere the serving paths operate
    (l ≥ 8) the histogram select is cheaper, and at l = 128, B = 32 it
    models ~11x fewer element-ops.  Deterministic arithmetic —
    benchmarks/check_regression.py gates on the modeled ratio, which
    cannot flake.
    """
    bn = _block_rows(n, block_n, -(-b // SUBLANE) * SUBLANE)
    grid = -(-n // bn)
    l_k = min(l, bn)
    w = n_words(k)
    if select == "argmin":
        per_block = 3 * l_k * bn * b
    else:
        cdf_steps = max(1, (32 * w).bit_length())
        per_block = ((cdf_steps + 6) * bn * b
                     + 4 * (bn // LANE + LANE) * l_k * b)
    return g * grid * per_block


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def lbh_chain(p, q, r, *, block_m: int = 512, interpret: bool | None = None):
    """(s*q, s*p) fused chain; m padded to block_m internally."""
    m = p.shape[0]
    bm = min(block_m, max(128, m))
    pp = _pad_to(p.astype(jnp.float32), 0, bm)
    qp = _pad_to(q.astype(jnp.float32), 0, bm)
    rp = _pad_to(_pad_to(r.astype(jnp.float32), 0, bm), 1, bm)
    sq, sp = lbh_chain_kernel(pp, qp, rp, block_m=bm,
                              interpret=_interpret_default(interpret))
    return sq[:m], sp[:m]


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def lbh_grad(x, u, v, r, *, block_m: int = 512, interpret: bool | None = None):
    """Full eq.-18 gradient using the fused chain kernel for the middle."""
    p = x @ u
    q = x @ v
    sq, sp = lbh_chain(p, q, r, block_m=block_m, interpret=interpret)
    return -(sq @ x), -(sp @ x)
