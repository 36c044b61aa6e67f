"""L independent bilinear-hash tables with union-of-candidates lookup and
dynamic insert/delete (standard multi-table LSH layered on the paper's
compact single-table regime).

Each table t hashes with a family drawn from ``fold_in(PRNGKey(seed), t)``,
so a MultiTableIndex with L=1 reproduces a single-table index built from
``fold_in(key, 0)`` exactly, and the candidate set grows monotonically with
L for a fixed seed — more tables can only add recall.

Features on one device are kept on the host too (``x_np``), hashed into
host bucket tables, and uploaded once for the re-rank.  Features that arrive
row-sharded over a mesh axis (a ``NamedSharding`` whose spec shards rows)
stay where they are: the index reads its mesh and axis from ``x.sharding``,
hashes each shard's rows on its own chip, keeps the codes there, scans them
shard by shard and re-ranks each candidate on the chip that owns its row.
Such an index answers scan queries and takes deletes: it builds no host
tables, a delete flags the row dead on its chip, and ``compact`` closes each
shard's live rows up on that shard.  Insert and the probe path need the
features on the host.

Ids are stable across mutations: ``insert`` assigns fresh ids (never
renumbers), ``delete`` tombstones rows out of every table, and ``compact``
(auto-triggered past ``IndexConfig.compact_threshold`` dead fraction, or
called directly after heavy delete churn) physically drops tombstoned rows
from ``codes``/``tables``/``x`` while a stable-id remap table keeps every
outstanding id resolving — results are always reported in stable-id space,
and internal row numbers never escape.
"""
from __future__ import annotations

import dataclasses
import time
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import functions as F
from repro.core import learning as L
from repro.core.indexer import IndexConfig, QueryResult
from repro.core.search import (DIST_SENTINEL, from_lanes, gather_rows,
                               hamming_topk_grouped, lane_rows, margin_batch,
                               margin_rerank_batch, rows_minor,
                               sharded_rerank, sharded_topk_lanes, to_lanes)
from repro.core.tables import SingleHashTable, keys_of
from repro.serving import batch_query as bq


@dataclasses.dataclass
class BatchQueryResult:
    ids: np.ndarray          # (B,) argmin-margin candidate per query (or -1)
    margins: np.ndarray      # (B,) f32
    nonempty: np.ndarray     # (B,) bool — any candidate survived the lookup?
    candidates: list[np.ndarray]  # per-query short-lists (union over tables)
    table_hits: np.ndarray   # (L,) per-table yield: probe path = bucket
                             # candidates found; scan path = scanned top-l
                             # slots (B·min(l, n_live), uniform by design)
    ids_topk: np.ndarray | None = None      # (B, l) when queried with l > 1
    margins_topk: np.ndarray | None = None  # (B, l), +inf past the valid set
    # replicated-shard serving (serving.cluster): fraction of the live rows
    # the answer actually scanned, and whether any shard had to be skipped
    # (all replicas down / past deadline).  Single-index paths always answer
    # over every live row, so the defaults make this a no-op for them.
    coverage: float = 1.0
    degraded: bool = False


def _fetch(x):
    """One blocking device-to-host read, under its own ``repro.fetch``
    span: an array, or a tuple of arrays whose copies start together."""
    with TraceAnnotation("repro.fetch"):
        return jax.device_get(x)


def _row_mesh(x):
    """(mesh, axis) of features row-sharded over one mesh axis, or (None,
    None) for features on one device (or replicated)."""
    sh = getattr(x, "sharding", None)
    if not isinstance(sh, NamedSharding) or len(sh.device_set) < 2:
        return None, None
    spec = tuple(sh.spec) + (None, None)
    axis = spec[0]
    if isinstance(axis, tuple):
        axis = axis[0] if len(axis) == 1 else axis
    if spec[1] is not None or isinstance(axis, tuple):
        raise ValueError(f"fit: features must be on one device or "
                         f"row-sharded over one mesh axis, got {sh.spec}")
    if axis is None:
        return None, None
    return sh.mesh, axis


_lanes = jax.jit(to_lanes, static_argnums=1)
_rows = jax.jit(from_lanes, static_argnums=1)


@lru_cache(maxsize=16)
def _compact_fn(mesh, axis: str, n_local: int, keep: int, minor: bool):
    """One program for ``MultiTableIndex._compact_shards``: each shard
    gathers the rows ``src`` names (keep of them, shard-local) from its own
    features and lane-dense codes."""
    def local(x, codes, src):
        codes = from_lanes(codes, n_local)[:, src]
        return (gather_rows(x, src, minor),
                to_lanes(codes, lane_rows(keep)))

    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(None, None, axis, None), P(axis)),
        out_specs=(P(axis, None), P(None, None, axis, None)),
        check_vma=False))


class MultiTableIndex:
    """Union-of-candidates index over L compact bilinear-hash tables."""

    def __init__(self, config: IndexConfig, tables: int | None = None):
        self.config = config
        self.num_tables = int(tables if tables is not None else config.tables)
        assert self.num_tables >= 1
        self.families: list = []
        self.tables: list[SingleHashTable] = []
        self.codes: list[np.ndarray] = []   # per-table (rows, W) uint32, host
        self.x_np: np.ndarray | None = None  # (rows, d) host copy
        self.active: np.ndarray | None = None  # (rows,) bool tombstone mask
        # stable-id machinery: rows are internal (compaction renumbers them);
        # every id that crosses the API boundary is a stable id.  ids_np maps
        # row -> stable id (strictly increasing, so row-order ties == id-order
        # ties); _row_of maps stable id -> current row, -1 once compacted away.
        self.ids_np: np.ndarray | None = None
        self._row_of: np.ndarray | None = None
        self._next_id = 0
        self.compactions = 0
        self.version = 0                    # bumped on insert/delete/compact
        # projection generation: bumped only when a refresh swap replaces
        # the hash families (serving.refresh) — the monolithic index never
        # moves it.  Version bumps strictly dominate generation bumps, so
        # version-keyed caches stay correct across a swap.
        self.generation = 0
        self.refreshes = 0
        self.fit_s = 0.0
        # observability: how often index state crosses the PCIe/ICI boundary
        # and how much compaction work ran.  The monolithic index re-uploads
        # its whole scan state after every mutation; the LSM subclass
        # (serving.lsm) exists to keep these flat under insert traffic —
        # the win is measured by these counters, not just asserted.
        self.device_uploads = 0        # host->device transfers of index state
        self.scan_state_rebuilds = 0   # stacked-code scan layouts rebuilt
        self.compaction_steps = 0      # bounded compaction work units
        self._x_dev = None
        self._codes_dev = None        # (L, n_live[_pad], W) stacked live codes
        self._live_rows: np.ndarray | None = None
        self._live_rows_dev = None
        # row-sharded features (fit reads them from x.sharding); None =
        # one device
        self.mesh = None
        self.axis = None
        self._n_local = 0             # rows a shard holds
        self._x_minor = False         # rows on the device's minor axis
        self._n_live = 0              # live rows the scan state holds
        self._pads = 0                # dead rows that hold no id: a shard's
                                      # block past its live rows after a
                                      # row-sharded compact
        self._active_dev = None       # row-sharded liveness flags; None =
                                      # every row live

    # -- build ---------------------------------------------------------------

    def table_key(self, t: int, learn_key=None):
        base = (jax.random.PRNGKey(self.config.seed)
                if learn_key is None else learn_key)
        return jax.random.fold_in(base, t)

    def _make_family(self, key, x):
        cfg = self.config
        d = x.shape[1]
        if cfg.method == "ah":
            return F.AHHash.create(key, d, cfg.bits)
        if cfg.method == "eh":
            return F.EHHash.create(key, d, cfg.bits,
                                   sample_dims=cfg.eh_sample_dims)
        if cfg.method == "bh":
            fam = F.SeededBHHash if cfg.seeded_projections else F.BHHash
            return fam.create(key, d, cfg.bits)
        if cfg.method == "lbh":
            m = min(cfg.lbh_sample, x.shape[0])
            sel = jax.random.choice(jax.random.fold_in(key, 1), x.shape[0],
                                    (m,), replace=False)
            res = L.learn_lbh(key, x[sel], cfg.bits, x_all=x,
                              steps=cfg.lbh_steps, lr=cfg.lbh_lr)
            return res.family
        raise ValueError(f"unknown method {self.config.method!r}")

    def fit(self, x, learn_key=None) -> "MultiTableIndex":
        t0 = time.perf_counter()
        x = jnp.asarray(x, jnp.float32)
        mesh, axis = _row_mesh(x)
        self.families = [self._make_family(self.table_key(t, learn_key), x)
                         for t in range(self.num_tables)]
        self.mesh = None
        self._invalidate()
        self.mesh, self.axis = mesh, axis
        n = x.shape[0]
        if self.mesh is None:
            codes_all = np.asarray(bq.hash_database_all(
                self.families, x, use_kernels=self.config.use_kernels))
            self.codes = [codes_all[t] for t in range(self.num_tables)]
            self.tables = [SingleHashTable(c, self.config.bits)
                           for c in self.codes]
            self.x_np = np.asarray(x)
            self._n_local = n
        else:
            self._n_local = n // self.mesh.shape[self.axis]
            self.codes, self.tables, self.x_np = [], [], None
            self._x_dev, self._x_minor = x, rows_minor(x)
            self._codes_dev = bq.hash_database_sharded(
                self.families, x, self.mesh, self.axis)
            self.device_uploads += 1
        self._n_live = n
        self._pads = 0
        self.active = np.ones(n, dtype=bool)
        if self.mesh is None:
            self.ids_np = np.arange(n, dtype=np.int64)
            self._row_of = np.arange(n, dtype=np.int64)
        else:           # stable ids == rows until the first compaction
            self.ids_np = self._row_of = None
        self._next_id = n
        self.compactions = 0
        self.version += 1
        self.fit_s = time.perf_counter() - t0
        return self

    def _invalidate(self, keep_x: bool = False) -> None:
        """Drop the device-resident caches derived from rows/codes.
        keep_x: the feature rows are unchanged (tombstone-only delete) —
        don't force a full (rows, d) re-upload on the next re-rank.
        Row-sharded features and their codes are the index itself and stay;
        only their liveness flags go."""
        self._active_dev = None
        if self.mesh is not None:
            return
        if not keep_x:
            self._x_dev = None
        self._codes_dev = None
        self._live_rows = None
        self._live_rows_dev = None

    def _require_fit(self, op: str) -> None:
        if self.active is None:
            raise RuntimeError(
                f"MultiTableIndex.{op} before fit(): build the index with "
                f"fit(x) before mutating or querying it")

    def _require_host_rows(self, op: str) -> None:
        self._require_fit(op)
        if self.mesh is not None:
            raise NotImplementedError(
                f"MultiTableIndex.{op}: the features are row-sharded over "
                f"mesh axis {self.axis!r}; this index answers scan queries "
                f"and takes deletes, and {op} needs them on the host")

    @property
    def n(self) -> int:
        """Live (non-deleted) row count."""
        return int(self.active.sum())

    @property
    def _dead(self) -> int:
        """Tombstoned rows: rows that hold an id but are no longer live."""
        return self.active.size - self._pads - int(self.active.sum())

    @property
    def x(self):
        if self._x_dev is None:
            self._x_dev = jnp.asarray(self.x_np)
            self._x_minor = rows_minor(self._x_dev)
            self.device_uploads += 1
        return self._x_dev

    # -- stable-id translation -----------------------------------------------

    def rows_to_ids(self, rows: np.ndarray) -> np.ndarray:
        """Internal row numbers -> stable external ids (-1 passes through).
        Identity until the first compaction."""
        rows = np.asarray(rows, dtype=np.int64)
        if self.ids_np is None:
            return np.where(rows >= 0, rows, -1)
        out = np.full(rows.shape, -1, dtype=np.int64)
        m = rows >= 0
        out[m] = self.ids_np[rows[m]]
        return out

    def ids_to_rows(self, ids: np.ndarray) -> np.ndarray:
        """Stable ids -> current rows.

        Never-assigned ids (negative, or >= the id high-water mark) and
        compacted-away ids raise KeyError — the range check runs before the
        ``_row_of`` gather so an out-of-range id can never surface as a raw
        numpy IndexError (or worse, a negative id silently wrapping to a
        valid row).  Tombstoned-but-not-yet-compacted ids still RESOLVE to
        their row: ``delete`` relies on that to find the row it is about to
        tombstone, and callers that need liveness check ``active[row]``.
        """
        self._require_fit("ids_to_rows")
        ids = np.asarray(ids, dtype=np.int64)
        n_ids = self._next_id if self._row_of is None else (
            self._row_of.shape[0])
        if ids.size and (ids.min() < 0 or ids.max() >= n_ids):
            raise KeyError(f"unknown ids (never assigned): "
                           f"{ids[(ids < 0) | (ids >= n_ids)][:8]}")
        if self._row_of is None:
            return ids.copy()
        rows = self._row_of[ids]
        if (rows < 0).any():
            raise KeyError(f"ids compacted away: {ids[rows < 0][:8]}")
        return rows

    def mask_to_rows(self, mask) -> np.ndarray | None:
        """Stable-id-space bool mask -> row-space mask (identity until the
        first compaction, where stable ids == rows)."""
        if mask is None:
            return None
        mask = np.asarray(mask, dtype=bool)
        return mask if self.ids_np is None else mask[self.ids_np]

    # -- dynamic updates -----------------------------------------------------

    def insert(self, x_new) -> np.ndarray:
        """Append rows to every table; returns the assigned stable ids."""
        self._require_host_rows("insert")
        x_new = np.atleast_2d(np.asarray(x_new, np.float32))
        if x_new.shape[0] == 0:
            return np.empty((0,), dtype=np.int64)
        new_codes = np.asarray(
            bq.hash_database_all(self.families, jnp.asarray(x_new),
                                 use_kernels=self.config.use_kernels))
        start = self.x_np.shape[0]
        rows = np.arange(start, start + x_new.shape[0], dtype=np.int64)
        ids = np.arange(self._next_id, self._next_id + x_new.shape[0],
                        dtype=np.int64)
        for t in range(self.num_tables):
            self.tables[t].insert(new_codes[t], rows)
            self.codes[t] = np.concatenate([self.codes[t], new_codes[t]])
        self.x_np = np.concatenate([self.x_np, x_new])
        self.active = np.concatenate(
            [self.active, np.ones(x_new.shape[0], dtype=bool)])
        self.ids_np = np.concatenate([self.ids_np, ids])
        self._row_of = np.concatenate([self._row_of, rows])
        self._next_id += x_new.shape[0]
        self._invalidate()
        self.version += 1
        return ids

    def delete(self, ids) -> None:
        """Tombstone rows out of every table (ids stay stable).  An empty
        delete is a no-op — it must NOT bump ``version`` (which would
        needlessly drop the service's query-code cache and the device scan
        state).  Past ``config.compact_threshold`` dead fraction the index
        compacts itself (see ``compact``)."""
        self._require_fit("delete")
        ids = np.atleast_1d(np.asarray(ids, dtype=np.int64))
        if ids.size == 0:
            return
        if np.unique(ids).size != ids.size:
            raise KeyError("duplicate ids in delete")
        rows = self.ids_to_rows(ids)
        if not self.active[rows].all():
            raise KeyError("delete of already-deleted or unknown id")
        for t in range(len(self.tables)):
            self.tables[t].delete(rows)
        self.active[rows] = False
        self._n_live -= rows.size
        self._invalidate(keep_x=True)
        self._put_active()
        self.version += 1
        thresh = self.config.compact_threshold
        held = self.active.size - self._pads
        if thresh is not None and self._dead > thresh * held:
            self.compact()

    def compact(self) -> np.ndarray:
        """Physically drop tombstoned rows: rebuild ``codes``/``tables``/
        ``x`` on the survivors and refresh the stable-id remap so every
        outstanding id keeps resolving.  Without this, delete churn grows
        the code tables (and the device scan state) forever.  Returns the
        surviving stable ids; no-op (no version bump) when nothing is dead.
        """
        self._require_fit("compact")
        if not self._dead:
            return self.rows_to_ids(np.flatnonzero(self.active))
        if self.mesh is not None:
            return self._compact_shards()
        live = np.flatnonzero(self.active)
        self.codes = [c[live] for c in self.codes]
        self.x_np = self.x_np[live]
        self.ids_np = self.ids_np[live]
        self.active = np.ones(live.size, dtype=bool)
        self.tables = [SingleHashTable(c, self.config.bits)
                       for c in self.codes]
        self._row_of = np.full(self._next_id, -1, dtype=np.int64)
        self._row_of[self.ids_np] = np.arange(live.size, dtype=np.int64)
        self._invalidate()
        self.version += 1
        self.compactions += 1
        self.compaction_steps += 1   # stop-the-world rebuild = one big step
        return self.ids_np.copy()

    def _compact_shards(self) -> np.ndarray:
        """``compact`` of row-sharded features: each shard closes its live
        rows up, in order, at the front of its block on its own chip, so no
        row crosses chips.  Every block keeps the fullest shard's live
        count; a shard with fewer pads its block with dead rows that hold
        no id.  Row order stays stable-id order, so ties still resolve to
        the lowest id."""
        shards, n_local = self.mesh.shape[self.axis], self._n_local
        live = self.active.reshape(shards, n_local)
        keep = max(int(live.sum(axis=1).max()), 1)
        src = np.zeros((shards, keep), np.int32)
        act = np.zeros((shards, keep), bool)
        for s in range(shards):
            rows = np.flatnonzero(live[s])
            src[s, :rows.size] = rows
            act[s, :rows.size] = True
        ids = self.rows_to_ids(
            (src + np.arange(shards)[:, None] * n_local)[act])
        src_dev = jax.device_put(src.reshape(-1),
                                 NamedSharding(self.mesh, P(self.axis)))
        self._x_dev, self._codes_dev = _compact_fn(
            self.mesh, self.axis, n_local, keep, self._x_minor)(
                self._x_dev, self._codes_dev, src_dev)
        self._x_minor = rows_minor(self._x_dev)
        self._n_local = keep
        self.active = act.reshape(-1)
        self._pads = self.active.size - ids.size
        self.ids_np = np.full(self.active.size, -1, dtype=np.int64)
        self.ids_np[self.active] = ids
        self._row_of = np.full(self._next_id, -1, dtype=np.int64)
        self._row_of[ids] = np.flatnonzero(self.active)
        self._put_active()
        self.version += 1
        self.compactions += 1
        self.compaction_steps += 1
        return ids.copy()

    def _put_active(self) -> None:
        """Row-sharded features: their liveness flags, sharded like the
        rows, for the scan to rank dead rows out (None while every row is
        live, so an index that never deletes scans without them)."""
        if self.mesh is None or self.active.all():
            self._active_dev = None
            return
        self._active_dev = jax.device_put(
            self.active, NamedSharding(self.mesh, P(self.axis)))
        self.device_uploads += 1

    # -- lookup / query ------------------------------------------------------

    def lookup_batch(self, w, qcodes: np.ndarray | None = None
                     ) -> tuple[list[np.ndarray], np.ndarray]:
        """Hash + multi-probe for B hyperplanes at once.

        qcodes: optional precomputed (L, B, W) query codes (the service
        computes them for its cache keys — no point hashing twice).
        Returns (per-query unioned candidate lists IN ROW SPACE — callers
        must translate with ``rows_to_ids`` before reporting, as the
        service does — and per-table hit counts)."""
        self._require_host_rows("lookup_batch")
        cfg = self.config
        w = np.atleast_2d(np.asarray(w, np.float32))
        if qcodes is None:
            qcodes = np.asarray(bq.hash_queries_all(self.families, w))
        hits = np.zeros(self.num_tables, dtype=np.int64)
        per_query: list[list[np.ndarray]] = [[] for _ in range(w.shape[0])]
        for t, table in enumerate(self.tables):
            keys = keys_of(qcodes[t])
            found = table.lookup_many(keys, cfg.radius, cfg.max_candidates,
                                      cfg.min_candidates)
            for b, cand in enumerate(found):
                per_query[b].append(cand)
                hits[t] += cand.size
        cands = [bq.union_candidates(per) for per in per_query]
        if cfg.max_candidates is not None:
            cands = [c[:cfg.max_candidates] for c in cands]
        return cands, hits

    def rerank_rows(self, w, cands: list[np.ndarray], l: int = 1,
                    mask_rows=None):
        """Exact-margin re-rank of B ragged ROW-space candidate lists
        (contract of ``batch_query.batched_rerank``).  This is the hook the
        LSM subclass overrides with a two-segment gather so the immutable
        base features never re-upload; every probe-path re-rank (here and
        in HashQueryService) routes through it."""
        return bq.batched_rerank(self.x, w, cands, l, mask_rows)

    def query_batch(self, w, mask=None, l: int = 1) -> BatchQueryResult:
        """Answer B hyperplane queries as one batch.

        mask: optional bool mask over stable-id space — restrict answers to
        these points (AL uses the unlabeled pool; identical to row space
        until the first compaction).  Bit-identical to B calls of `query`."""
        cands, hits = self.lookup_batch(w)
        w = np.atleast_2d(np.asarray(w, np.float32))
        ids, margins, nonempty = self.rerank_rows(w, cands, l,
                                                  self.mask_to_rows(mask))
        ids = self.rows_to_ids(ids)
        cands = [self.rows_to_ids(c) for c in cands]
        return BatchQueryResult(ids[:, 0], margins[:, 0], nonempty, cands,
                                hits, ids_topk=ids if l > 1 else None,
                                margins_topk=margins if l > 1 else None)

    def query(self, w) -> QueryResult:
        """Single-query path (same machinery, B=1)."""
        res = self.query_batch(np.asarray(w, np.float32)[None, :])
        return QueryResult(int(res.ids[0]), float(res.margins[0]),
                           res.candidates[0], bool(res.nonempty[0]))

    def _scan_state(self):
        """Device-resident stacked live codes for the fused scan, lane-dense
        (``core.search.to_lanes``): one (L, W, lane_rows(n_live) / 128, 128)
        array (tombstones compacted out, so deleted rows can never crowd
        live answers out of the top-l slots) plus the live-row map, rebuilt
        only when the index mutates.  The scan program then reads them as
        they lie.  Row-sharded features: the codes ``fit`` hashed on each
        shard, dead rows among them (``_active_dev`` flags them)."""
        if self._codes_dev is None:
            self.scan_state_rebuilds += 1
            self.device_uploads += 1
            self._live_rows = np.flatnonzero(self.active)
            self._n_live = self._live_rows.size
            stacked = np.stack([c[self._live_rows] for c in self.codes])
            self._codes_dev = _lanes(jnp.asarray(stacked),
                                     lane_rows(self._n_live))
            self._live_rows_dev = jnp.asarray(self._live_rows)
        return self._codes_dev, self._live_rows_dev

    def _put(self, a):
        """Host array -> device: replicated over the mesh of row-sharded
        features, else on the default device."""
        if self.mesh is None:
            return jnp.asarray(a)
        return jax.device_put(a, NamedSharding(self.mesh, P()))

    def query_scan_batch(self, w, l: int = 16, topk: int = 1, mask=None
                         ) -> BatchQueryResult:
        """Device-side batched scan: ONE fused Hamming kernel launch for all
        L tables and B queries, then union/dedup and exact margin re-rank.

        Over row-sharded features, the scan runs one local launch per shard
        (core.search.sharded_topk_lanes: O(L·B·l·shards) interconnect bytes
        for the candidate merge) and each shard re-ranks the candidates it
        owns (core.search.sharded_rerank); answers are bit-identical to the
        one-device index over the same rows.

        The L tables' live codes are stacked as a single lane-dense device
        array and L is folded into the query batch (L·B query rows); the
        grouped kernel matches each table's code rows against only that
        table's query rows, so launch count is independent of L.

        NOTE the parameter split: ``l`` is the per-table scan depth (the
        Hamming short-list size, as in the seed-era signature), NOT the
        number of answers — ``topk`` is.  query_batch(w, l=k) corresponds
        to query_scan_batch(w, topk=k), with ``l`` controlling recall.
        Deep scans (l in the hundreds) are cheap under the default
        histogram selection (``config.fused_select`` / REPRO_FUSED_SELECT
        = "hist": selection cost is independent of l per tile) — when
        recall matters more than rerank cost, raise ``l``, not ``tables``.
        ids_topk/margins_topk are set when topk > 1 and always have
        exactly topk columns (impossible slots: id -1 / margin +inf).
        mask: optional bool mask over stable-id space restricting answers,
        as in query_batch; it is read on the host at the deduplicated
        candidates only (B·L·l entries), never over all rows.  Returns a
        BatchQueryResult interchangeable with the host-table query_batch
        path (candidates come back sorted by id rather than in probe
        order); all reported ids are stable ids.

        Each stage runs under a host span on the profiler's clock:
        ``repro.hash`` (in ``batch_query.hash_queries_all``), then
        ``repro.scan``, ``repro.dedup``, ``repro.mask``, ``repro.rerank``,
        one ``repro.fetch`` per blocking device-to-host read (two: the
        candidates, then the answers) and ``repro.results`` for the host
        work after the reads.
        """
        self._require_fit("query_scan_batch")
        w = np.atleast_2d(np.asarray(w, np.float32))
        b = w.shape[0]
        codes_dev, live_rows_dev = self._scan_state()
        n_live = self._n_live
        if n_live == 0:
            ids_pad = np.full((b, topk), -1, np.int64)
            m_pad = np.full((b, topk), np.inf, np.float32)
            return BatchQueryResult(
                np.full(b, -1, np.int64), np.full(b, np.inf, np.float32),
                np.zeros(b, dtype=bool),
                [np.empty(0, np.int64) for _ in range(b)],
                np.zeros(self.num_tables, dtype=np.int64),
                ids_topk=ids_pad if topk > 1 else None,
                margins_topk=m_pad if topk > 1 else None)
        w_dev = self._put(w)            # one upload for hash and re-rank
        qcodes = bq.hash_queries_all(
            self.families, w_dev, use_kernels=self.config.use_kernels,
            mesh=self.mesh)
        _, idx = self._scan(codes_dev, qcodes, l, n_live)
        with TraceAnnotation("repro.dedup"):
            grows, packed = bq.dedup_packed(idx, live_rows_dev,
                                            self.active.size)
        packed = _fetch(packed)
        cand, hits = packed[:-1], packed[-1, :self.num_tables]
        # mask narrows answers/rerank, but (as in the probe path) NOT the
        # reported candidate short-lists — backends stay interchangeable.
        with TraceAnnotation("repro.mask"):
            valid = cand >= 0
            if mask is not None:
                ids = self.rows_to_ids(np.maximum(cand, 0))
                valid &= np.asarray(mask, dtype=bool)[ids]
            valid_dev = self._put(valid)
        with TraceAnnotation("repro.rerank"):
            if self.mesh is None:
                answer = margin_rerank_batch(self.x, w_dev, grows, valid_dev,
                                             topk, rows_minor=self._x_minor)
            else:
                answer = sharded_rerank(self._x_dev, w_dev, grows, valid_dev,
                                        topk, self.mesh, self.axis,
                                        rows_minor=self._x_minor)
        margins, top = _fetch(answer)
        with TraceAnnotation("repro.results"):
            top = top.astype(np.int64)
            top[~np.isfinite(margins)] = -1
            if margins.shape[1] < topk:   # topk > L*l candidates: pad
                padw = ((0, 0), (0, topk - margins.shape[1]))
                margins = np.pad(margins, padw, constant_values=np.inf)
                top = np.pad(top, padw, constant_values=-1)
            top = self.rows_to_ids(top)
            cands = [self.rows_to_ids(c[c >= 0]) for c in cand]
            return BatchQueryResult(
                top[:, 0], margins[:, 0], valid.any(axis=1), cands,
                hits.astype(np.int64),
                ids_topk=top if topk > 1 else None,
                margins_topk=margins if topk > 1 else None)

    def _scan(self, codes_dev, qcodes, l: int, n_live: int):
        """Per-table Hamming top-l of the stacked live codes, (dists, idx)
        each (L, B, l) on device, under the ``repro.scan`` span: shard by
        shard over the features' mesh, through the fused kernel, or in
        plain jnp."""
        select = self.config.fused_select       # None -> REPRO_FUSED_SELECT
        pack = self.config.cand_pack            # None -> REPRO_CAND_PACK
        with TraceAnnotation("repro.scan"):
            if self.mesh is not None:
                return sharded_topk_lanes(
                    codes_dev, qcodes, l, self.mesh, self.axis,
                    self._n_local, self._active_dev,
                    use_kernel=self.config.use_kernels, select=select,
                    pack=pack)
            if self.config.use_kernels:
                from repro.kernels import ops
                return ops.hamming_topk_lanes(codes_dev, qcodes, l, n_live,
                                              select=select, pack=pack)
            return hamming_topk_grouped(_rows(codes_dev, n_live), qcodes, l,
                                        select=select)

    # -- replicated-shard serving hooks (serving.cluster) --------------------
    #
    # The cluster router merges per-SHARD results at the Hamming level
    # (before any re-rank) so partial-shard unions keep the (dist, id) tie
    # contract — see core.search.merge_topk_shards.  These two hooks expose
    # exactly the pieces the router needs: the pre-merge per-table top-l in
    # stable-id space, and per-candidate margins with no selection.

    def scan_table_topk(self, w, l: int = 16
                        ) -> tuple[np.ndarray, np.ndarray]:
        """Per-table Hamming top-l surfaced PRE-merge, in stable-id space.

        Returns host arrays (dists (L, B, l) int32, ids (L, B, l) int64),
        each (table, query) list sorted ascending by (distance, stable id)
        with (DIST_SENTINEL, -1) sentinels in impossible slots — exactly
        the lists ``query_scan_batch`` deduplicates internally.  Stable ids
        ascend with rows, so the scan's (distance, live-row) order IS
        (distance, id) order and no re-sort is needed after translation.
        """
        self._require_fit("scan_table_topk")
        w = np.atleast_2d(np.asarray(w, np.float32))
        b = w.shape[0]
        codes_dev, _ = self._scan_state()
        n_live = self._n_live
        if n_live == 0:
            return (np.full((self.num_tables, b, l), DIST_SENTINEL,
                            np.int32),
                    np.full((self.num_tables, b, l), -1, np.int64))
        qcodes = bq.hash_queries_all(
            self.families, self._put(w), use_kernels=self.config.use_kernels,
            mesh=self.mesh)
        dists, idx = self._scan(codes_dev, qcodes, l, n_live)
        idx_np = np.asarray(idx, dtype=np.int64)
        grows = np.maximum(idx_np, 0)
        if self._live_rows is not None:
            grows = self._live_rows[grows]
        ids = self.rows_to_ids(np.where(idx_np >= 0, grows, -1))
        return np.asarray(dists, dtype=np.int32), ids

    def candidate_margins(self, w, cand_ids: np.ndarray) -> np.ndarray:
        """Exact margins for an externally-chosen candidate set, by id.

        cand_ids: (B, C) stable ids, -1 in pad slots.  Returns (B, C)
        float32 margins aligned to the candidate positions, +inf wherever
        the slot is padding or the id no longer resolves (compacted away
        mid-flight).  Values are bit-identical to what query_scan_batch's
        re-rank computes for the same rows (core.search.margin_batch shares
        the per-row margin expression), which is what lets the cluster
        router re-rank a cross-shard candidate union without losing the
        single-index answer contract.
        """
        self._require_fit("candidate_margins")
        w = np.atleast_2d(np.asarray(w, np.float32))
        cand_ids = np.asarray(cand_ids, dtype=np.int64)
        known = (cand_ids >= 0) & (cand_ids < self._next_id)
        rows = np.zeros(cand_ids.shape, dtype=np.int64)
        rows[known] = (cand_ids[known] if self._row_of is None
                       else self._row_of[cand_ids[known]])
        valid = known & (rows >= 0)
        rows[~valid] = 0
        m = margin_batch(self.x, jnp.asarray(w, jnp.float32),
                         jnp.asarray(rows), jnp.asarray(valid))
        return np.asarray(m, dtype=np.float32)

    def stats(self) -> dict:
        per_table = [t.stats() for t in self.tables]
        rows = self.active.size - self._pads if self.active is not None else 0
        return {
            "tables": self.num_tables,
            "n": self.n,
            "rows": rows,
            "dead_fraction": 1.0 - self.n / rows if rows else 0.0,
            "compactions": self.compactions,
            "bits": self.config.bits,
            "version": self.version,
            "generation": self.generation,
            "refreshes": self.refreshes,
            "device_uploads": self.device_uploads,
            "scan_state_rebuilds": self.scan_state_rebuilds,
            "compaction_steps": self.compaction_steps,
            "shards": 1 if self.mesh is None else self.mesh.shape[self.axis],
            "rows_per_shard": self._n_local,
            "per_table": per_table,
            "buckets_total": int(sum(s["buckets"] for s in per_table)),
        }
