"""LSM-style delta index: streaming ingest over an immutable base segment.

``MultiTableIndex`` treats the index as monolithic: every ``insert`` does a
full-array ``np.concatenate`` and bumps ``version``, which drops the cached
device scan state — the next scan query re-uploads the whole stacked
(L, n, W) code array — and ``compact()`` is a stop-the-world rebuild.  Fine
for read-mostly serving; fatal for streaming ingest, where inserts arrive
concurrently with query traffic.

``LSMMultiTableIndex`` restructures the same index into two segments over
one contiguous row space:

- **base** — rows ``[0, base_len)``, immutable: stacked codes uploaded to
  the device once per compaction cycle and served by the fused Pallas
  grouped scan exactly like the monolithic index; feature rows likewise
  device-resident.  Deletes never touch it — they tombstone (the ``active``
  mask) and are filtered at merge time.
- **delta** — rows ``[base_len, rows)``, mutable: append-only host buffers
  with geometric growth absorbing inserts (amortized O(1) per row, no
  concatenate), re-uploaded per mutation (small) and scanned per query as
  plain jnp while below ``IndexConfig.lsm_delta_fused_rows`` (past the knob
  it routes through the fused kernel like the base).

Queries scan both segments and merge candidates through the lexicographic
``(dist, id)`` contract (``core.search.merge_topk_segments``) — answers are
bit-identical to a fresh monolithic index built from the same surviving
rows, including tie order and l > n sentinels.  The invariant making that
cheap: row order always equals stable-id order (base rows keep their
relative order across compactions; delta ids are assigned later, hence
larger), so sorting by (distance, row) IS sorting by (distance, id).

Tombstones: deleted rows stay physically in place until compaction, so the
scan must keep them out of the top-l.  On a single device each segment's
liveness mask rides into the scan itself (the ``active=`` operand of
``hamming_topk_grouped`` / ``kernels.ops.hamming_topk_grouped``): dead and
shape-padding rows are set to the distance sentinel before selection, so
the scan is exactly ``l`` deep and the mask is a TRACED operand — inserts,
deletes and compaction swaps never change a jit trace key (device shapes
stay pinned to sticky power-of-two pad buckets).

Incremental compaction: past the delta/dead-fraction thresholds the index
freezes the current delta and folds base + frozen delta into a new base a
bounded number of source rows per step (``IndexConfig.lsm_step_rows``),
piggybacked on insert/delete/query calls (``lsm_auto``) or driven by
``start_compactor()``'s daemon thread; new inserts keep landing in the
still-live delta tail throughout.  Once the copy finishes, the new base is
uploaded to the device OFF the lock (the target region is immutable by
then), and one final bounded step swaps the segments atomically: pointer
flips plus O(live delta) fixups under the lock, with a liveness re-check so
rows deleted mid-compaction stay tombstoned in the new base.  Host probe
tables are keyed by stable id, so compaction never rebuilds or invalidates
them — only the service's version-keyed candidate cache drops, once per
swap.
"""
from __future__ import annotations

import threading
import time

import jax.numpy as jnp
import numpy as np

from repro.core.indexer import IndexConfig
from repro.core.search import (DIST_SENTINEL, hamming_topk_grouped,
                               margin_batch,
                               margin_batch_segmented, margin_rerank_batch,
                               margin_rerank_segmented, merge_topk_segments)
from repro.core.tables import SingleHashTable
from repro.serving import batch_query as bq
from repro.serving.multi_table import BatchQueryResult, MultiTableIndex

_MIN_CAP = 64   # floor for every power-of-two buffer/device-shape bucket


def _pow2_at_least(v: int, floor: int = 1) -> int:
    p = max(int(floor), 1)
    while p < v:
        p *= 2
    return p


class _Compaction:
    """In-flight incremental compaction: source snapshot + target buffers.

    ``src_*`` are references to the buffers as of ``begin_compaction`` —
    rows [0, src_len) (base + frozen delta) are immutable there, so the
    copy loop reads them without the lock being held between steps even if
    insert-growth swaps ``self._*_buf`` to larger arrays meanwhile.
    ``src_active`` may be stale after such a swap; that only makes the copy
    loop retain a row deleted mid-compaction — the atomic swap re-checks
    liveness against the CURRENT mask, so such rows land tombstoned.
    """
    __slots__ = ("src_codes", "src_x", "src_ids", "src_active", "src_len",
                 "tgt_codes", "tgt_x", "tgt_ids", "new_row_of",
                 "pos", "out", "uploading")

    def __init__(self, src_codes, src_x, src_ids, src_active, src_len,
                 tgt_codes, tgt_x, tgt_ids, new_row_of):
        self.src_codes = src_codes
        self.src_x = src_x
        self.src_ids = src_ids
        self.src_active = src_active
        self.src_len = src_len
        self.tgt_codes = tgt_codes
        self.tgt_x = tgt_x
        self.tgt_ids = tgt_ids
        self.new_row_of = new_row_of
        self.pos = 0        # next source row to examine
        self.out = 0        # rows copied into the target so far
        self.uploading = False


class LSMMultiTableIndex(MultiTableIndex):
    """MultiTableIndex with an immutable base + mutable delta (see module
    docstring).  Drop-in: same query/insert/delete/compact API, same
    stable-id contract, answers bit-identical on both backends."""

    # Lock discipline, machine-checked by repro.lint (static pass) and
    # assertable at runtime via repro.lint.runtime_lock_checks: each
    # attribute below may only be read or written while holding the mapped
    # lock.  Private helpers that rely on the caller's lock say so with a
    # "# lock held by caller" comment on their first line.
    _GUARDED_BY = {
        # segment geometry + growable host buffers
        "_rows": "_lock", "_base_len": "_lock", "_frozen_len": "_lock",
        "_codes_buf": "_lock", "_x_buf": "_lock", "_ids_buf": "_lock",
        "_active_buf": "_lock", "_row_of_buf": "_lock", "_bcap": "_lock",
        # segment versions
        "_base_version": "_lock", "_base_mask_version": "_lock",
        "_delta_version": "_lock",
        # device caches keyed by those versions
        "_base_codes_dev": "_lock", "_base_codes_key": "_lock",
        "_base_active_dev": "_lock", "_base_active_key": "_lock",
        "_base_x_dev": "_lock", "_base_x_key": "_lock",
        "_delta_codes_dev": "_lock", "_delta_x_dev": "_lock",
        "_delta_active_dev": "_lock", "_delta_key": "_lock",
        "_x_dev": "_lock", "_x_dev_key": "_lock",
        # compaction state + counters
        "_c": "_lock", "delta_uploads": "_lock",
        # refresh lifecycle: qcodes hashed off-lock must pair with the
        # generation whose device state they will scan — every consumer
        # snapshots (families, generation) and the code/table state under
        # ONE lock hold (see insert / query_scan_batch / service._answer)
        "families": "_lock", "tables": "_lock",
        "generation": "_lock", "refreshes": "_lock",
    }
    # _bcap: _upload_new_base reads it off-lock by design (only swaps move
    # it, and uploads are serialized by _Compaction.uploading) — the static
    # finding carries its reason in lint_baseline.json; runtime assertions
    # skip the attribute here.
    _RUNTIME_LOCK_EXEMPT = frozenset({"_bcap"})

    def __init__(self, config: IndexConfig, tables: int | None = None):
        super().__init__(config, tables)
        self._lock = threading.RLock()
        # delta device shapes never shrink below the compaction trigger
        # floor: every delta below lsm_delta_min shares ONE pad bucket, so a
        # full fill->compact cycle touches O(1) shape regimes instead of
        # O(log(delta_min)) of them (each regime is a fresh jit trace)
        self._delta_floor = _pow2_at_least(
            max(_MIN_CAP, int(config.lsm_delta_min)))
        # sticky base pad bucket (single-device layout): compaction swaps
        # never shrink it, so a swap that lands in the same bucket leaves
        # every scan/rerank trace key untouched — no post-swap recompiles
        self._bcap = _MIN_CAP
        # segment geometry over the unified row space: [0, base) immutable
        # base; [base, base+frozen) frozen delta (only while a compaction is
        # in flight); [base+frozen, rows) live delta absorbing inserts.
        self._rows = 0
        self._base_len = 0
        self._frozen_len = 0
        # growable host buffers; the parent-compat attributes (self.codes /
        # x_np / active / ids_np / _row_of) are zero-copy views of these,
        # refreshed after every geometry change (_refresh_views)
        self._codes_buf: np.ndarray | None = None   # (L, cap, W) uint32
        self._x_buf: np.ndarray | None = None       # (cap, d) f32
        self._ids_buf: np.ndarray | None = None     # (cap,) i64
        self._active_buf: np.ndarray | None = None  # (cap,) bool
        self._row_of_buf: np.ndarray | None = None  # (id_cap,) i64
        # segment versions: base changes only at a compaction swap; the base
        # mask on base-row deletes; the delta on every insert / delta delete
        self._base_version = 0
        self._base_mask_version = 0
        self._delta_version = 0
        # device caches, keyed by the versions above
        self._base_codes_dev = None
        self._base_codes_key = None
        self._base_active_dev = None
        self._base_active_key = None
        self._base_x_dev = None
        self._base_x_key = None
        self._delta_codes_dev = None
        self._delta_x_dev = None
        self._delta_active_dev = None
        self._delta_key = None
        self._x_dev_key = None          # full-copy compat `.x` property
        # compaction machinery
        self._c: _Compaction | None = None
        self._compactor: threading.Thread | None = None
        self._compactor_stop = threading.Event()
        self.delta_uploads = 0   # small per-insert transfers (NOT the base)

    # -- build ---------------------------------------------------------------

    def fit(self, x, learn_key=None) -> "LSMMultiTableIndex":
        t0 = time.perf_counter()
        x = jnp.asarray(x, jnp.float32)
        fams = [self._make_family(self.table_key(t, learn_key), x)
                for t in range(self.num_tables)]
        self._install(np.asarray(x), fams)
        self.fit_s = time.perf_counter() - t0
        return self

    def _hash_bucketed(self, families, x_np: np.ndarray) -> np.ndarray:
        """(L, cap, W) database codes with the row count padded up to its
        power-of-two bucket BEFORE hashing, so the jitted hash sees one
        shape per bucket — a refresh rebuild over a grown-but-same-bucket
        row count reuses the fit-time trace instead of minting a new one.
        Padding rows hash to whatever sgn(0)=+1 gives; callers only ever
        read [:n]."""
        n, d = x_np.shape
        cap = _pow2_at_least(n, _MIN_CAP)
        xp = np.zeros((cap, d), np.float32)
        xp[:n] = x_np
        return np.asarray(bq.hash_database_all(
            families, jnp.asarray(xp), use_kernels=self.config.use_kernels))

    def _install(self, x_np: np.ndarray, families, ids: np.ndarray | None = None,
                 next_id: int | None = None, bcap_floor: int = _MIN_CAP) -> None:
        """Build the full segment state from scratch: rows [0, n) become the
        immutable base, the delta starts empty.  ``fit`` calls this with
        fresh 0..n-1 ids; a refresh shadow (serving.refresh) passes the
        live rows' EXISTING stable ids (ascending, preserving the row-order
        == id-order invariant), the live index's id high-water mark, and
        its sticky base bucket so the swapped-in state keeps every scan
        trace key warm."""
        n, d = x_np.shape
        if ids is None:
            ids = np.arange(n, dtype=np.int64)
        else:
            ids = np.asarray(ids, dtype=np.int64)
            assert ids.shape == (n,)
            assert n == 0 or (np.diff(ids) > 0).all(), \
                "stable ids must ascend with rows"
        hi = int(next_id if next_id is not None
                 else (ids[-1] + 1 if n else 0))
        codes_all = self._hash_bucketed(families, x_np)
        ll, w = self.num_tables, codes_all.shape[2]
        with self._lock:
            cap = _pow2_at_least(n, _MIN_CAP)
            self._codes_buf = codes_all.copy()   # cap rows == hash bucket
            self._x_buf = np.zeros((cap, d), np.float32)
            self._x_buf[:n] = x_np
            self._ids_buf = np.zeros(cap, np.int64)
            self._ids_buf[:n] = ids
            self._active_buf = np.zeros(cap, bool)
            self._active_buf[:n] = True
            self._row_of_buf = np.full(_pow2_at_least(hi, _MIN_CAP), -1,
                                       np.int64)
            self._row_of_buf[ids] = np.arange(n)
            self._rows, self._base_len, self._frozen_len = n, n, 0
            self._bcap = _pow2_at_least(n, max(_MIN_CAP, int(bcap_floor)))
            self._next_id = hi
            self._c = None
            self.compactions = 0
            self.families = list(families)
            self._refresh_views()
            # host probe tables keyed by STABLE ID (== row at fit time, but
            # never renumbered after): compaction leaves them untouched
            self.tables = [SingleHashTable(codes_all[t, :n],
                                           self.config.bits, ids=ids)
                           for t in range(ll)]
            self._base_version += 1
            self._base_mask_version += 1
            self._delta_version += 1
            self.version += 1

    def _refresh_views(self) -> None:
        """Re-point the parent-compat attributes at the buffer prefixes.
        Views, not copies — writes like ``self.active[rows] = False`` land
        in the buffers, and inherited helpers (rows_to_ids / ids_to_rows /
        mask_to_rows / n / stats) work unchanged."""
        # lock held by caller
        r = self._rows
        self.codes = [self._codes_buf[t, :r] for t in range(self.num_tables)]
        self.x_np = self._x_buf[:r]
        self.active = self._active_buf[:r]
        self.ids_np = self._ids_buf[:r]
        self._row_of = self._row_of_buf[:self._next_id]

    def _grow_rows(self, need: int) -> None:
        # lock held by caller
        if need <= self._x_buf.shape[0]:
            return
        cap = _pow2_at_least(need, _MIN_CAP)
        r = self._rows
        codes = np.zeros((self.num_tables, cap, self._codes_buf.shape[2]),
                         np.uint32)
        codes[:, :r] = self._codes_buf[:, :r]
        x = np.zeros((cap, self._x_buf.shape[1]), np.float32)
        x[:r] = self._x_buf[:r]
        ids = np.zeros(cap, np.int64)
        ids[:r] = self._ids_buf[:r]
        act = np.zeros(cap, bool)
        act[:r] = self._active_buf[:r]
        self._codes_buf, self._x_buf = codes, x
        self._ids_buf, self._active_buf = ids, act

    def _grow_ids(self, need: int) -> None:
        # lock held by caller
        if need <= self._row_of_buf.shape[0]:
            return
        cap = _pow2_at_least(need, _MIN_CAP)
        row_of = np.full(cap, -1, np.int64)
        row_of[:self._next_id] = self._row_of_buf[:self._next_id]
        self._row_of_buf = row_of

    # -- compat: full-copy device x (NOT the serving path) -------------------

    @property
    def x(self):
        # The LSM mutators never call _invalidate (that is the point), so
        # the parent's cached _x_dev would go stale; key it by version.
        # Serving reranks go through rerank_rows' segmented gather instead.
        with self._lock:
            if self._x_dev is None or self._x_dev_key != self.version:
                self._x_dev = jnp.asarray(self.x_np)
                self._x_dev_key = self.version
                self.device_uploads += 1
            return self._x_dev

    # -- dynamic updates -----------------------------------------------------

    def insert(self, x_new) -> np.ndarray:
        """Append rows to the live delta; returns the assigned stable ids.
        O(rows inserted) amortized — no concatenate, and the base's device
        scan state is untouched (only the small delta re-uploads)."""
        self._require_fit("insert")
        x_new = np.atleast_2d(np.asarray(x_new, np.float32))
        k = x_new.shape[0]
        if k == 0:
            return np.empty((0,), dtype=np.int64)
        # hash OFF the lock, against a generation-stamped family snapshot: a
        # refresh swap between the hash and the append would otherwise file
        # old-generation codes under the new generation's tables.  On the
        # (rare) losing race, rehash with the new families and retry.
        while True:
            with self._lock:
                fams, gen = self.families, self.generation
            new_codes = np.asarray(
                bq.hash_database_all(fams, jnp.asarray(x_new),
                                     use_kernels=self.config.use_kernels))
            with self._lock:
                if self.generation == gen:
                    ids = self._append_rows(x_new, new_codes)
                    break
        self._maybe_compact()
        return ids

    def _append_rows(self, x_new: np.ndarray, new_codes: np.ndarray,
                     ids: np.ndarray | None = None) -> np.ndarray:
        # lock held by caller.  Append pre-hashed rows to the live delta;
        # ids defaults to fresh ones past the high-water mark (insert), the
        # refresh catch-up loop passes the EXISTING stable ids of rows it
        # mirrors into the shadow.
        k = x_new.shape[0]
        if k == 0:
            return np.empty((0,), dtype=np.int64)
        with self._lock:
            r0 = self._rows
            if ids is None:
                ids = np.arange(self._next_id, self._next_id + k,
                                dtype=np.int64)
            else:
                ids = np.asarray(ids, dtype=np.int64)
                assert int(ids[0]) >= self._next_id \
                    and bool((np.diff(ids) > 0).all()), \
                    "appended ids must keep row order == id order"
            self._grow_rows(r0 + k)
            self._grow_ids(int(ids[-1]) + 1)
            self._codes_buf[:, r0:r0 + k] = new_codes
            self._x_buf[r0:r0 + k] = x_new
            self._ids_buf[r0:r0 + k] = ids
            self._active_buf[r0:r0 + k] = True
            self._row_of_buf[ids] = np.arange(r0, r0 + k, dtype=np.int64)
            self._next_id = max(self._next_id, int(ids[-1]) + 1)
            self._rows = r0 + k
            self._refresh_views()
            for t in range(self.num_tables):
                self.tables[t].insert(new_codes[t], ids)
            self._delta_version += 1
            self.version += 1
        return ids

    def delete(self, ids) -> None:
        """Tombstone rows (base rows stay physically in place until the
        next compaction folds them out; the scan masks them to the
        distance sentinel inside selection)."""
        self._require_fit("delete")
        ids = np.atleast_1d(np.asarray(ids, dtype=np.int64))
        if ids.size == 0:
            return
        if np.unique(ids).size != ids.size:
            raise KeyError("duplicate ids in delete")
        with self._lock:
            rows = self.ids_to_rows(ids)
            if not self.active[rows].all():
                raise KeyError("delete of already-deleted or unknown id")
            for t in range(self.num_tables):
                self.tables[t].delete(ids)
            self.active[rows] = False
            if (rows < self._base_len).any():
                self._base_mask_version += 1
            if (rows >= self._base_len).any():
                self._delta_version += 1
            self.version += 1
        self._maybe_compact()

    # -- incremental compaction ----------------------------------------------

    def _should_begin(self) -> bool:
        # lock held by caller
        if self.x_np is None or self._rows == 0:
            return False
        cfg = self.config
        delta = self._rows - self._base_len
        if delta >= max(cfg.lsm_delta_min,
                        int(cfg.lsm_delta_threshold * max(self._base_len, 1))):
            return True
        thresh = cfg.compact_threshold
        if thresh is None:
            return False
        dead = self._rows - int(self._active_buf[:self._rows].sum())
        return dead > thresh * self._rows

    def begin_compaction(self) -> bool:
        """Freeze the delta and set up the fold of base + frozen delta into
        a new base.  Returns False when there is nothing to fold (no delta,
        no tombstones) or a compaction is already in flight."""
        with self._lock:
            if self._c is not None:
                return False
            src_len = self._rows
            if src_len == 0 or (self._base_len == src_len
                                and bool(self._active_buf[:src_len].all())):
                return False
            self._frozen_len = self._rows - self._base_len
            ll, w = self.num_tables, self._codes_buf.shape[2]
            d = self._x_buf.shape[1]
            # headroom past src_len: the live delta appended at swap time
            # usually fits without a grow-at-swap memcpy
            cap = _pow2_at_least(src_len + max(src_len // 4, _MIN_CAP),
                                 _MIN_CAP)
            self._c = _Compaction(
                src_codes=self._codes_buf, src_x=self._x_buf,
                src_ids=self._ids_buf, src_active=self._active_buf,
                src_len=src_len,
                tgt_codes=np.zeros((ll, cap, w), np.uint32),
                tgt_x=np.zeros((cap, d), np.float32),
                tgt_ids=np.zeros(cap, np.int64),
                new_row_of=np.full(max(self._next_id, 1), -1, np.int64))
            return True

    def compaction_step(self, max_rows: int | None = None) -> int:
        """Run one bounded unit of compaction work; returns the number of
        source rows examined (copy phase), 1 (upload / swap phase), or 0
        (nothing in flight, or another driver owns the upload).  The copy
        and swap phases hold the lock for O(step) work — that bound IS the
        pause a concurrent query can observe; the single O(n) device upload
        between them runs off-lock."""
        with self._lock:
            c = self._c
            if c is None:
                return 0
            if c.pos < c.src_len:
                step = int(max_rows if max_rows is not None
                           else self.config.lsm_step_rows)
                lo = c.pos
                hi = min(lo + max(step, 1), c.src_len)
                live = np.flatnonzero(c.src_active[lo:hi]) + lo
                k = live.size
                if k:
                    o = c.out
                    c.tgt_codes[:, o:o + k] = c.src_codes[:, live]
                    c.tgt_x[o:o + k] = c.src_x[live]
                    ids = c.src_ids[live]
                    c.tgt_ids[o:o + k] = ids
                    c.new_row_of[ids] = np.arange(o, o + k, dtype=np.int64)
                    c.out = o + k
                c.pos = hi
                self.compaction_steps += 1
                return hi - lo
            if c.uploading:
                return 0
            c.uploading = True
        # copy complete: rows [0, c.out) of the target are final, so the
        # new base can cross to the device without blocking mutators
        try:
            dev_codes, dev_x = self._upload_new_base(c)
        except BaseException:
            with self._lock:
                c.uploading = False
            raise
        with self._lock:
            if self._c is not c:
                # a refresh swap adopted a whole new segment state while the
                # upload ran — this compaction's target is stale; drop it
                return 0
            self._finish_swap(c, dev_codes, dev_x)
            self.compaction_steps += 1
        return 1

    def _upload_new_base(self, c: _Compaction):
        n_new = c.out
        # sticky bucket: pad to at least the current base bucket so a swap
        # landing in the same bucket leaves the scan trace keys untouched
        # (benign off-lock read — only swaps move _bcap, one at a time)
        bcap = max(self._bcap, _pow2_at_least(n_new, _MIN_CAP))
        ll, w = c.tgt_codes.shape[0], c.tgt_codes.shape[2]
        stacked = np.zeros((ll, bcap, w), np.uint32)
        stacked[:, :n_new] = c.tgt_codes[:, :n_new]
        xb = np.zeros((bcap, c.tgt_x.shape[1]), np.float32)
        xb[:n_new] = c.tgt_x[:n_new]
        return jnp.asarray(stacked), jnp.asarray(xb)

    def _finish_swap(self, c: _Compaction, dev_codes, dev_x) -> None:
        # lock held by caller.  O(live delta) copies + pointer flips.
        live_lo = self._base_len + self._frozen_len
        live_len = self._rows - live_lo
        n_new = c.out
        need = n_new + live_len
        if c.tgt_x.shape[0] < need:
            cap = _pow2_at_least(need, _MIN_CAP)
            codes = np.zeros((self.num_tables, cap, c.tgt_codes.shape[2]),
                             np.uint32)
            codes[:, :n_new] = c.tgt_codes[:, :n_new]
            x = np.zeros((cap, c.tgt_x.shape[1]), np.float32)
            x[:n_new] = c.tgt_x[:n_new]
            ids = np.zeros(cap, np.int64)
            ids[:n_new] = c.tgt_ids[:n_new]
            c.tgt_codes, c.tgt_x, c.tgt_ids = codes, x, ids
        # the live delta tail stays the delta, renumbered after the new base
        c.tgt_codes[:, n_new:need] = self._codes_buf[:, live_lo:self._rows]
        c.tgt_x[n_new:need] = self._x_buf[live_lo:self._rows]
        live_ids = self._ids_buf[live_lo:self._rows].copy()
        c.tgt_ids[n_new:need] = live_ids
        cap = c.tgt_x.shape[0]
        active = np.zeros(cap, bool)
        if n_new:
            # liveness re-check against the CURRENT mask: rows deleted while
            # the copy loop ran (possibly from a stale snapshot) stay
            # tombstoned in the new base and fold out next cycle
            old_rows = self._row_of[c.tgt_ids[:n_new]]
            active[:n_new] = self._active_buf[old_rows]
        active[n_new:need] = self._active_buf[live_lo:self._rows]
        row_of = c.new_row_of
        if row_of.shape[0] < self._next_id:
            grown = np.full(_pow2_at_least(self._next_id, _MIN_CAP), -1,
                            np.int64)
            grown[:row_of.shape[0]] = row_of
            row_of = grown
        row_of[live_ids] = np.arange(n_new, need, dtype=np.int64)
        # atomic swap: everything below is pointer assignment + version bumps
        self._codes_buf, self._x_buf = c.tgt_codes, c.tgt_x
        self._ids_buf, self._active_buf = c.tgt_ids, active
        self._row_of_buf = row_of
        self._rows, self._base_len, self._frozen_len = need, n_new, 0
        self._refresh_views()
        self._base_version += 1
        self._base_mask_version += 1
        self._delta_version += 1
        # the freshly uploaded single-device base layout is already current
        self._bcap = int(dev_codes.shape[1])
        self._base_codes_dev = dev_codes
        self._base_codes_key = (self._base_version, None)
        self._base_x_dev = dev_x
        self._base_x_key = self._base_version
        self.device_uploads += 2
        self.version += 1
        self.compactions += 1
        self._c = None

    # -- online refresh (serving.refresh drives this) ------------------------

    def _adopt_refresh(self, shadow: "LSMMultiTableIndex") -> None:
        # lock held by caller.  Atomic generation swap: adopt the shadow
        # index's entire segment state (buffers, families, tables, device
        # caches) by pointer flip.  The live index object's identity is
        # unchanged — services and threads holding a reference see the new
        # generation on their next locked read.  In-flight queries that
        # already snapshotted the old handles finish against the old
        # generation (the old buffers stay valid arrays).  Any in-flight
        # compaction is abandoned (_c = None; compaction_step re-checks).
        with shadow._lock:
            self._codes_buf = shadow._codes_buf
            self._x_buf = shadow._x_buf
            self._ids_buf = shadow._ids_buf
            self._active_buf = shadow._active_buf
            self._row_of_buf = shadow._row_of_buf
            self._rows = shadow._rows
            self._base_len = shadow._base_len
            self._frozen_len = 0
            self._bcap = shadow._bcap
            self._next_id = max(self._next_id, shadow._next_id)
            self.families = shadow.families
            self.tables = shadow.tables
            self._refresh_views()
            self._base_version += 1
            self._base_mask_version += 1
            self._delta_version += 1
            # adopt the shadow's warm single-device caches where current, so
            # a pre-warmed swap serves its first query without an upload
            if shadow._base_codes_key == (shadow._base_version, None):
                self._base_codes_dev = shadow._base_codes_dev
                self._base_codes_key = (self._base_version, None)
            else:
                self._base_codes_dev, self._base_codes_key = None, None
            if shadow._base_active_key == (shadow._base_version,
                                           shadow._base_mask_version):
                self._base_active_dev = shadow._base_active_dev
                self._base_active_key = (self._base_version,
                                         self._base_mask_version)
            else:
                self._base_active_dev, self._base_active_key = None, None
            if shadow._base_x_key == shadow._base_version:
                self._base_x_dev = shadow._base_x_dev
                self._base_x_key = self._base_version
            else:
                self._base_x_dev, self._base_x_key = None, None
            if (shadow._delta_key == shadow._delta_version
                    and shadow._rows > shadow._base_len):
                self._delta_codes_dev = shadow._delta_codes_dev
                self._delta_x_dev = shadow._delta_x_dev
                self._delta_active_dev = shadow._delta_active_dev
                self._delta_key = self._delta_version
            else:
                self._delta_codes_dev = self._delta_x_dev = None
                self._delta_active_dev = self._delta_key = None
            self._x_dev, self._x_dev_key = None, None
            self.device_uploads += shadow.device_uploads
            self.scan_state_rebuilds += shadow.scan_state_rebuilds
            self.delta_uploads += shadow.delta_uploads
        self._c = None
        self.version += 1
        self.generation += 1
        self.refreshes += 1

    def compact(self) -> np.ndarray:
        """Synchronous full compaction: begin + drive every incremental
        step + swap.  Same contract as the parent (returns surviving stable
        ids; no-op without a version bump when there is nothing to fold),
        but additionally folds the delta into the base."""
        self._require_fit("compact")
        with self._lock:
            started = self._c is not None or self.begin_compaction()
            if not started:
                return self.ids_np[self.active].copy()
        while True:
            with self._lock:
                if self._c is None:
                    break
            if self.compaction_step() == 0:
                time.sleep(1e-4)   # another driver owns the upload phase
        with self._lock:
            return self.ids_np[self.active].copy()

    def _maybe_compact(self) -> None:
        """Piggyback driver: begin past the thresholds, then pay one bounded
        step per index call (queries included) so ingest traffic amortizes
        its own compaction."""
        if not self.config.lsm_auto:
            return
        with self._lock:
            if self._c is None and self._should_begin():
                self.begin_compaction()
            active = self._c is not None
        if active:
            self.compaction_step()

    def start_compactor(self, interval_s: float = 0.002) -> None:
        """Drive incremental compaction from a daemon thread instead of
        (in addition to) piggybacking on index calls."""
        if self._compactor is not None:
            return
        self._compactor_stop.clear()

        def loop():
            while not self._compactor_stop.is_set():
                did = 0
                with self._lock:
                    if (self._c is None and self.x_np is not None
                            and self._should_begin()):
                        self.begin_compaction()
                    active = self._c is not None
                if active:
                    did = self.compaction_step()
                if not did:
                    self._compactor_stop.wait(interval_s)

        self._compactor = threading.Thread(target=loop, name="lsm-compactor",
                                           daemon=True)
        self._compactor.start()

    def stop_compactor(self) -> None:
        if self._compactor is None:
            return
        self._compactor_stop.set()
        self._compactor.join()
        self._compactor = None

    # -- device segment states -----------------------------------------------

    def _base_codes_state(self):
        # lock held by caller
        key = self._base_version
        if self._base_codes_key != key:
            bl = self._base_len
            stacked = np.zeros(
                (self.num_tables, self._bcap, self._codes_buf.shape[2]),
                np.uint32)
            stacked[:, :bl] = self._codes_buf[:, :bl]
            self._base_codes_dev = jnp.asarray(stacked)
            self._base_codes_key = key
            self.scan_state_rebuilds += 1
            self.device_uploads += 1
        return self._base_codes_dev

    def _base_active_state(self):
        # lock held by caller; (bcap,) bool, padding rows False
        key = (self._base_version, self._base_mask_version)
        if self._base_active_key != key:
            bl = self._base_len
            act = np.zeros(self._bcap, bool)
            act[:bl] = self._active_buf[:bl]
            self._base_active_dev = jnp.asarray(act)
            self._base_active_key = key
            self.device_uploads += 1
        return self._base_active_dev

    def _base_x_state(self):
        # lock held by caller; (bcap, d) f32, padding rows zero
        if self._base_x_key != self._base_version:
            bl = self._base_len
            xb = np.zeros((self._bcap, self._x_buf.shape[1]), np.float32)
            xb[:bl] = self._x_buf[:bl]
            self._base_x_dev = jnp.asarray(xb)
            self._base_x_key = self._base_version
            self.device_uploads += 1
        return self._base_x_dev

    def _delta_state(self):
        # lock held by caller; codes/x/active padded to a power-of-two row
        # bucket so per-insert shape churn retraces jit O(log n) times only
        if self._delta_key != self._delta_version:
            lo, hi = self._base_len, self._rows
            dlen = hi - lo
            dcap = _pow2_at_least(dlen, self._delta_floor)
            codes = np.zeros((self.num_tables, dcap,
                              self._codes_buf.shape[2]), np.uint32)
            codes[:, :dlen] = self._codes_buf[:, lo:hi]
            xb = np.zeros((dcap, self._x_buf.shape[1]), np.float32)
            xb[:dlen] = self._x_buf[lo:hi]
            act = np.zeros(dcap, bool)
            act[:dlen] = self._active_buf[lo:hi]
            self._delta_codes_dev = jnp.asarray(codes)
            self._delta_x_dev = jnp.asarray(xb)
            self._delta_active_dev = jnp.asarray(act)
            self._delta_key = self._delta_version
            self.delta_uploads += 1
            self.device_uploads += 1
        return (self._delta_codes_dev, self._delta_x_dev,
                self._delta_active_dev)

    # -- lookup / query ------------------------------------------------------

    def lookup_batch(self, w, qcodes: np.ndarray | None = None):
        """Probe path: the host tables are id-keyed (they survive
        compaction), so the parent lookup returns candidates in stable-id
        space — translate back to the ROW space the lookup contract
        promises.  Order-preserving: ids ascend with rows, so probe order
        and union first-occurrence order both map through unchanged."""
        with self._lock:
            cands, hits = super().lookup_batch(w, qcodes)
            cands = [self.ids_to_rows(c) if c.size else c.astype(np.int64)
                     for c in cands]
            return cands, hits

    def rerank_rows(self, w, cands: list[np.ndarray], l: int = 1,
                    mask_rows=None):
        """Segmented exact-margin re-rank: base rows gather from the
        device-resident immutable base features, delta rows from the small
        delta upload — the full (rows, d) array never re-uploads on insert.
        Bit-identical to the parent's monolithic gather."""
        ids, valid = bq.pad_candidates(cands)
        if mask_rows is not None:
            valid = valid & np.asarray(mask_rows, bool)[ids]
        nonempty = valid.any(axis=1)
        w = np.atleast_2d(np.asarray(w, np.float32))
        with self._lock:
            split = self._base_len
            delta_len = self._rows - split
            base_x = self._base_x_state()
            delta_x = self._delta_state()[1] if delta_len else None
        margins, top = self._rerank_dev(
            jnp.asarray(w), jnp.asarray(ids), jnp.asarray(valid), l,
            base_x, delta_x, split, delta_len)
        margins = np.asarray(margins)
        top = np.asarray(top).astype(np.int64)
        top[~np.isfinite(margins)] = -1
        return top, margins, nonempty

    def _rerank_dev(self, w_dev, rows_dev, valid_dev, l, base_x, delta_x,
                    split, delta_len):
        if delta_len == 0:
            return margin_rerank_batch(base_x, w_dev, rows_dev, valid_dev, l)
        if split == 0:
            return margin_rerank_batch(delta_x, w_dev, rows_dev, valid_dev, l)
        return margin_rerank_segmented(base_x, delta_x, jnp.int32(split),
                                       w_dev, rows_dev, valid_dev, l)

    def query_batch(self, w, mask=None, l: int = 1) -> BatchQueryResult:
        with self._lock:
            res = super().query_batch(w, mask, l)
        self._maybe_compact()
        return res

    def _scan_segment(self, codes_dev, qcodes, l: int, active_dev,
                      fused: bool, select, pack):
        """Scan one segment and return its top-l LIVE candidates,
        (G, B, l), lex-sorted, local row ids.  Tombstones AND pad rows are
        masked to the sentinel at distance level inside selection
        (active_dev is False for both), so the scan is exactly l deep and
        already filtered — one trace per (B, cap) pad bucket, immune to
        insert/delete/compaction churn (the mask is a traced operand, not a
        jit key)."""
        if fused:
            from repro.kernels import ops
            return ops.hamming_topk_grouped(codes_dev, qcodes, l,
                                            select=select,
                                            active=active_dev, pack=pack)
        return hamming_topk_grouped(codes_dev, qcodes, l, select=select,
                                    active=active_dev)

    def query_scan_batch(self, w, l: int = 16, topk: int = 1, mask=None
                         ) -> BatchQueryResult:
        """Two-segment fused scan (see parent for the l/topk contract).

        The base segment scans exactly like the monolithic index (fused
        kernel / jnp per config); the delta scans as
        plain jnp until it exceeds ``config.lsm_delta_fused_rows``; the two
        candidate lists merge through core.search.merge_topk_segments.
        All geometry and device handles are snapshotted under the lock, so
        a compaction swap concurrent with this call can only make the
        answer reflect the index state wholly before or wholly after the
        swap — never a mix.
        """
        self._require_fit("query_scan_batch")
        w = np.atleast_2d(np.asarray(w, np.float32))
        b = w.shape[0]
        cfg = self.config
        with self._lock:
            split = self._base_len
            rows = self._rows
            ids_view = self.ids_np          # old buffers stay valid views
            active_view = self._active_buf[:rows]
            n_live = int(active_view.sum())
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
            delta_len = rows - split
            base_codes = self._base_codes_state() if split else None
            base_active = (self._base_active_state()
                           if split else None)
            base_x = self._base_x_state()
            delta = self._delta_state() if delta_len else None
            fams = self.families    # snapshot WITH the device handles: a
            # refresh swap between this block and the hash below must not
            # pair new-generation qcodes with old-generation codes
        w_dev = jnp.asarray(w)          # one upload for hash and re-rank
        qcodes = bq.hash_queries_all(
            fams, w_dev, use_kernels=cfg.use_kernels)         # (L, B, W)
        select = cfg.fused_select
        pack = cfg.cand_pack
        d_m = i_m = None
        if base_codes is not None:
            d_b, i_b = self._scan_segment(
                base_codes, qcodes, l, base_active, cfg.use_kernels, select,
                pack)
            d_m, i_m = d_b, i_b
        if delta is not None:
            delta_codes, delta_x, delta_active = delta
            fused = cfg.use_kernels and delta_len >= cfg.lsm_delta_fused_rows
            d_d, i_d = self._scan_segment(
                delta_codes, qcodes, l, delta_active, fused, select, pack)
            # delta-local ids -> global rows (sentinels stay -1)
            i_d = jnp.where(i_d < 0, jnp.int32(-1),
                            i_d + jnp.int32(split))
            if d_m is None:
                d_m, i_m = d_d, i_d
            else:
                d_m, i_m = merge_topk_segments(d_m, i_m, d_d, i_d, l)
        else:
            delta_x = None
        # device-side union/dedup over global rows — row order == stable-id
        # order, so this is the monolithic scan's dedup program
        grows, uniq, hits = bq.dedup_candidates(i_m, rows=rows)
        mask_rows = None if mask is None else (
            np.asarray(mask, dtype=bool)[ids_view])
        valid = uniq if mask_rows is None else (
            bq.mask_candidates(uniq, grows, mask_rows))
        margins, top = self._rerank_dev(
            w_dev, grows, valid, topk, base_x, delta_x, split, delta_len)
        margins = np.asarray(margins)
        top = np.asarray(top).astype(np.int64)
        top[~np.isfinite(margins)] = -1
        if margins.shape[1] < topk:   # topk > L*l candidates: pad, not clip
            padw = ((0, 0), (0, topk - margins.shape[1]))
            margins = np.pad(margins, padw, constant_values=np.inf)
            top = np.pad(top, padw, constant_values=-1)
        live = top >= 0
        top_ids = np.full(top.shape, -1, np.int64)
        top_ids[live] = ids_view[top[live]]
        hits = np.asarray(hits, dtype=np.int64)
        grows_np, valid_np = np.asarray(grows), np.asarray(valid)
        uniq_np = np.asarray(uniq)
        cands = [ids_view[grows_np[i, uniq_np[i]]] for i in range(b)]
        self._maybe_compact()
        return BatchQueryResult(
            top_ids[:, 0], margins[:, 0], valid_np.any(axis=1), cands, hits,
            ids_topk=top_ids if topk > 1 else None,
            margins_topk=margins if topk > 1 else None)

    # -- replicated-shard serving hooks (serving.cluster) --------------------

    def scan_table_topk(self, w, l: int = 16
                        ) -> tuple[np.ndarray, np.ndarray]:
        """Two-segment override of the parent hook: scan base + delta and
        merge through merge_topk_segments BEFORE translating to stable ids,
        so the returned per-table lists carry the identical (dist, id)
        order a monolithic scan over the live rows would produce.  All
        geometry/handles snapshot under one lock hold, as in
        query_scan_batch."""
        self._require_fit("scan_table_topk")
        w = np.atleast_2d(np.asarray(w, np.float32))
        b = w.shape[0]
        cfg = self.config
        with self._lock:
            split = self._base_len
            rows = self._rows
            ids_view = self.ids_np
            active_view = self._active_buf[:rows]
            n_live = int(active_view.sum())
            if n_live == 0:
                return (np.full((self.num_tables, b, l), DIST_SENTINEL,
                                np.int32),
                        np.full((self.num_tables, b, l), -1, np.int64))
            delta_len = rows - split
            base_codes = self._base_codes_state() if split else None
            base_active = self._base_active_state() if split else None
            delta = self._delta_state() if delta_len else None
            fams = self.families
        qcodes = bq.hash_queries_all(fams, w, use_kernels=cfg.use_kernels)
        select = cfg.fused_select
        pack = cfg.cand_pack
        d_m = i_m = None
        if base_codes is not None:
            d_m, i_m = self._scan_segment(
                base_codes, qcodes, l, base_active, cfg.use_kernels, select,
                pack)
        if delta is not None:
            delta_codes, _, delta_active = delta
            fused = cfg.use_kernels and delta_len >= cfg.lsm_delta_fused_rows
            d_d, i_d = self._scan_segment(
                delta_codes, qcodes, l, delta_active, fused, select, pack)
            i_d = jnp.where(i_d < 0, jnp.int32(-1), i_d + jnp.int32(split))
            if d_m is None:
                d_m, i_m = d_d, i_d
            else:
                d_m, i_m = merge_topk_segments(d_m, i_m, d_d, i_d, l)
        i_np = np.asarray(i_m, dtype=np.int64)
        ids = np.where(i_np >= 0, ids_view[np.clip(i_np, 0, rows - 1)], -1)
        return np.asarray(d_m, dtype=np.int32), ids

    def candidate_margins(self, w, cand_ids: np.ndarray) -> np.ndarray:
        """Segmented override: margins gather from the device-resident base
        features plus the small delta upload (core.search.
        margin_batch_segmented), bit-identical to the parent's monolithic
        gather.  Unresolvable ids (pad slots, or rows compacted away
        between the router's scan and this call) come back +inf."""
        self._require_fit("candidate_margins")
        w = np.atleast_2d(np.asarray(w, np.float32))
        cand_ids = np.asarray(cand_ids, dtype=np.int64)
        with self._lock:
            split = self._base_len
            delta_len = self._rows - split
            base_x = self._base_x_state()
            delta_x = self._delta_state()[1] if delta_len else None
            next_id = self._next_id
            row_of = self._row_of          # old buffers stay valid views
        known = (cand_ids >= 0) & (cand_ids < next_id)
        rows = np.zeros(cand_ids.shape, dtype=np.int64)
        rows[known] = row_of[cand_ids[known]]
        valid = known & (rows >= 0)
        rows[~valid] = 0
        w_dev = jnp.asarray(w, jnp.float32)
        rows_dev, valid_dev = jnp.asarray(rows), jnp.asarray(valid)
        if delta_len == 0:
            m = margin_batch(base_x, w_dev, rows_dev, valid_dev)
        elif split == 0:
            m = margin_batch(delta_x, w_dev, rows_dev, valid_dev)
        else:
            m = margin_batch_segmented(base_x, delta_x, jnp.int32(split),
                                       w_dev, rows_dev, valid_dev)
        return np.asarray(m, dtype=np.float32)

    # -- counters ------------------------------------------------------------

    def stats(self) -> dict:
        with self._lock:
            st = super().stats()
            st.update({
                "backend": "lsm",
                "base_rows": self._base_len,
                "delta_rows": self._rows - self._base_len,
                "frozen_rows": self._frozen_len,
                "compaction_active": self._c is not None,
                "delta_uploads": self.delta_uploads,
            })
        return st
