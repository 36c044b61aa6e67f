"""Micro-batching query service over a MultiTableIndex.

Mirrors the Engine idiom of serve/engine.py: callers enqueue work
(``submit``) and the service answers everything pending as a single batched
device pass (``flush``), or hand it a whole batch at once (``query_batch``)
and it chunks by ``max_batch``.

The LRU cache sits at the query-*code* level: two hyperplanes that hash to
the same L codes probe the same buckets, so the cached value is the unioned
candidate list (host dict-probe work — the serial part of the pipeline).
The exact-margin re-rank always runs, because margins depend on w itself,
not just its code.  The cache is dropped whenever the index mutates
(``index.version``) and bypassed when a row mask is given (mask-dependent
results must not be shared).

Two interchangeable backends (``mode``):

- ``"probe"`` (default) — host hash-table multi-probe + candidate cache,
  the paper's lookup path.
- ``"scan"`` — the device-resident fused top-k Hamming scan
  (``MultiTableIndex.query_scan_batch``): one kernel launch for all L
  tables and the whole micro-batch, no host tables and no candidate cache.
  Over an index fitted on row-sharded features, the scan runs one local
  launch per shard and each shard re-ranks the rows it owns, answers
  bit-identical to the single-device scan.

Scan depth (``scan_l``) trades recall for rerank cost.  Under the default
histogram selection (``IndexConfig.fused_select`` / REPRO_FUSED_SELECT =
"hist") the kernel's selection cost is independent of l per code tile, so
deep scans — scan_l in the hundreds — cost little more than shallow ones
and buy most of the recall back on coarse (low-bit) codes; only the
re-rank gather grows with l.  Under the legacy "argmin" selection, kernel
time grows linearly with scan_l — keep it shallow there.
"""
from __future__ import annotations

import contextlib
import time
from collections import OrderedDict, deque

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.indexer import QueryResult
from repro.serving import batch_query as bq
from repro.serving.multi_table import MultiTableIndex
from repro.serving.refresh import RefreshManager


class HashQueryService:
    """Batched front end with micro-batching, candidate cache and counters."""

    def __init__(self, index: MultiTableIndex, max_batch: int | None = None,
                 cache_size: int = 1024, mode: str = "probe",
                 scan_l: int = 16):
        assert mode in ("probe", "scan"), mode
        self.index = index
        self.mode = mode
        self.scan_l = int(scan_l)
        self.max_batch = int(max_batch if max_batch is not None
                             else index.config.batch)
        assert self.max_batch >= 1
        self.cache_size = int(cache_size)
        self._cache: OrderedDict[bytes, np.ndarray] = OrderedDict()
        self._cache_version = index.version
        self._pending: list[np.ndarray] = []
        # counters
        self.requests = 0
        self.batches = 0
        self.cache_hits = 0
        self.busy_s = 0.0
        self.latencies_s: deque[float] = deque(maxlen=65536)
        self.inserts = 0
        self.inserted_rows = 0
        self.deletes = 0
        self.deleted_rows = 0
        # degraded-answer observability (scan answers from a
        # ShardReplicaRouter carry coverage/degraded; monolithic indexes
        # always report full coverage)
        self.degraded_batches = 0
        self.last_coverage = 1.0
        # online refresh (serving.refresh): available when the index
        # supports the generation swap (the LSM index); created eagerly so
        # concurrent first triggers can't race a lazy constructor
        self.refresher = (RefreshManager(index)
                          if hasattr(index, "_adopt_refresh") else None)
        self._refresh_mark = 0   # inserted_rows at the last auto trigger

    def _index_lock(self):
        """The index's mutation lock when it has one (the LSM index runs a
        compactor that swaps row storage under live traffic — probe answers
        must see one consistent row space across lookup + re-rank + id
        translation); a no-op for the plain MultiTableIndex."""
        return getattr(self.index, "_lock", None) or contextlib.nullcontext()

    # -- writes --------------------------------------------------------------

    def insert(self, x_new) -> np.ndarray:
        """Forward a streaming insert to the index; returns the assigned
        stable ids.  The candidate cache self-invalidates on the version
        bump (``_cache_get``), so no explicit flush is needed here."""
        ids = self.index.insert(x_new)
        self.inserts += 1
        self.inserted_rows += int(ids.size)
        self._maybe_refresh()
        return ids

    # -- online refresh ------------------------------------------------------

    def refresh(self, wait: bool = True, warm_batches: tuple = ()) -> bool:
        """Re-learn the hash families from the accumulated rows and swap
        the rebuilt index in (serving.refresh.RefreshManager; requires the
        LSM index).  wait=False runs it on a background worker, off the
        query path.  Returns False when a refresh is already in flight.
        warm_batches: batch sizes to pre-compile the new generation's scan
        traces with before the swap (defaults to this service's max_batch
        bucket for scan mode)."""
        if self.refresher is None:
            raise RuntimeError(
                "refresh() requires an index with generation-swap support "
                "(serving.lsm.LSMMultiTableIndex)")
        if not warm_batches and self.mode == "scan":
            warm_batches = (self.max_batch,)
        return self.refresher.refresh(wait=wait, warm_batches=warm_batches,
                                      warm_l=self.scan_l)

    def _maybe_refresh(self) -> None:
        """Auto policy: start a background refresh once
        ``config.refresh_ingest_rows`` rows arrived since the last trigger."""
        thresh = self.index.config.refresh_ingest_rows
        if (self.refresher is None or thresh is None
                or self.inserted_rows - self._refresh_mark < thresh):
            return
        self._refresh_mark = self.inserted_rows
        self.refresh(wait=False)

    def delete(self, ids) -> None:
        """Forward a streaming delete (tombstone) to the index."""
        ids = np.atleast_1d(np.asarray(ids, dtype=np.int64))
        self.index.delete(ids)
        self.deletes += 1
        self.deleted_rows += int(ids.size)

    # -- micro-batching ------------------------------------------------------

    def submit(self, w) -> int:
        """Enqueue one hyperplane query; returns its ticket (flush order)."""
        self._pending.append(np.asarray(w, np.float32).reshape(-1))
        return len(self._pending) - 1

    @property
    def pending(self) -> int:
        return len(self._pending)

    def flush(self) -> list[QueryResult]:
        """Answer everything pending as one batch, in submit order."""
        if not self._pending:
            return []
        ws = np.stack(self._pending)
        self._pending = []
        return self.query_batch(ws)

    def query(self, w) -> QueryResult:
        ticket = self.submit(w)
        return self.flush()[ticket]

    # -- batched path --------------------------------------------------------

    def query_batch(self, ws, mask=None) -> list[QueryResult]:
        """Answer B queries, chunked by ``max_batch``; results in order.
        The whole call runs under the ``repro.query`` host span."""
        with TraceAnnotation("repro.query"):
            ws = np.atleast_2d(np.asarray(ws, np.float32))
            out: list[QueryResult] = []
            for s in range(0, ws.shape[0], self.max_batch):
                out.extend(self._answer(ws[s:s + self.max_batch], mask))
            return out

    def _cache_get(self, key: bytes) -> np.ndarray | None:
        if self._cache_version != self.index.version:
            self._cache.clear()
            self._cache_version = self.index.version
            return None
        cand = self._cache.get(key)
        if cand is not None:
            self._cache.move_to_end(key)
        return cand

    def _cache_put(self, key: bytes, cand: np.ndarray) -> None:
        self._cache[key] = cand
        self._cache.move_to_end(key)
        while len(self._cache) > self.cache_size:
            self._cache.popitem(last=False)

    def _answer(self, ws: np.ndarray, mask) -> list[QueryResult]:
        if self.refresher is not None \
                and self.index.config.refresh_traffic_sample:
            self.refresher.note_queries(ws)
        if self.mode == "scan":
            return self._answer_scan(ws, mask)
        t_start = time.perf_counter()
        b = ws.shape[0]
        use_cache = mask is None and self.cache_size > 0

        # one consistent row space AND hash generation for qcode + cache
        # probe + lookup + re-rank + id translation: cached candidate lists
        # are row-space, so a compaction swap mid-answer would misattribute
        # them — and a refresh swap between hashing and probing would pair
        # old-generation qcodes with new-generation tables (_index_lock)
        with self._index_lock():
            qcodes = np.asarray(bq.hash_queries_all(
                self.index.families, ws,
                use_kernels=self.index.config.use_kernels))
            keys = [qcodes[:, i, :].tobytes() for i in range(b)]
            cands: list[np.ndarray | None] = [None] * b
            miss_rows = []
            for i, key in enumerate(keys):
                hit = self._cache_get(key) if use_cache else None
                if hit is None:
                    miss_rows.append(i)
                else:
                    cands[i] = hit
                    self.cache_hits += 1
            if miss_rows:
                found, _ = self.index.lookup_batch(
                    ws[miss_rows], qcodes=qcodes[:, miss_rows, :])
                for i, cand in zip(miss_rows, found):
                    cands[i] = cand
                    if use_cache:
                        self._cache_put(keys[i], cand)

            ids, margins, nonempty = self.index.rerank_rows(
                ws, cands, 1, self.index.mask_to_rows(mask))
            ids = self.index.rows_to_ids(ids)
            cands = [self.index.rows_to_ids(c) for c in cands]

        elapsed = time.perf_counter() - t_start
        self.requests += b
        self.batches += 1
        self.busy_s += elapsed
        self.latencies_s.append(elapsed)
        return [QueryResult(int(ids[i, 0]), float(margins[i, 0]), cands[i],
                            bool(nonempty[i]))
                for i in range(b)]

    def _answer_scan(self, ws: np.ndarray, mask) -> list[QueryResult]:
        """Fused-scan backend: one grouped Hamming kernel launch per
        micro-batch covering every table; no candidate cache (the scan is
        device-bound — there is no host probe work to save)."""
        t_start = time.perf_counter()
        b = ws.shape[0]
        res = self.index.query_scan_batch(ws, l=self.scan_l, mask=mask)
        elapsed = time.perf_counter() - t_start
        self.last_coverage = float(getattr(res, "coverage", 1.0))
        if getattr(res, "degraded", False):
            self.degraded_batches += 1
        self.requests += b
        self.batches += 1
        self.busy_s += elapsed
        self.latencies_s.append(elapsed)
        with TraceAnnotation("repro.results"):
            return [QueryResult(int(res.ids[i]), float(res.margins[i]),
                                res.candidates[i], bool(res.nonempty[i]))
                    for i in range(b)]

    # -- counters ------------------------------------------------------------

    def stats(self) -> dict:
        lat = np.asarray(self.latencies_s) if self.latencies_s else np.zeros(1)
        return {
            "requests": self.requests,
            "batches": self.batches,
            "mean_batch": self.requests / max(self.batches, 1),
            "cache_hits": self.cache_hits,
            "cache_hit_rate": self.cache_hits / max(self.requests, 1),
            "cache_entries": len(self._cache),
            "qps": self.requests / max(self.busy_s, 1e-12),
            "mean_batch_latency_ms": 1e3 * float(lat.mean()),
            "p95_batch_latency_ms": 1e3 * float(np.quantile(lat, 0.95)),
            "index_version": self.index.version,
            "inserts": self.inserts,
            "inserted_rows": self.inserted_rows,
            "deletes": self.deletes,
            "deleted_rows": self.deleted_rows,
            "degraded_batches": self.degraded_batches,
            "last_coverage": self.last_coverage,
            # index-side observability: transfer and compaction work done
            # under this service's traffic (serving.lsm exists to keep the
            # first two flat under insert streams — see multi_table counters)
            "index_device_uploads": self.index.device_uploads,
            "index_scan_state_rebuilds": self.index.scan_state_rebuilds,
            "index_compaction_steps": self.index.compaction_steps,
            "index_compactions": self.index.compactions,
            "refresh": (None if self.refresher is None
                        else self.refresher.stats()),
        }
