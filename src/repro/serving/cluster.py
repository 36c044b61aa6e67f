"""Replicated-shard serving: R-way replicated row shards behind one router.

``ShardReplicaRouter`` is the robustness tier between the single-process
sharded scan (an index fitted on row-sharded features) and a true
multi-host deployment: the row space is split round-robin over S shards,
each shard is served by R replica ``LSMMultiTableIndex`` instances built
from the SAME ``IndexConfig`` (same seed ⇒ identical hash families
everywhere, which is what makes replicas — and a fresh reference index —
interchangeable bit for bit), and every replica interaction crosses one
seam (``_guarded_call``) where a ``serving.faults.FaultPlan`` can inject
deterministic chaos and where a ``jax.distributed`` host boundary can slot
in later without touching the query protocol.

Query protocol (the degraded-answer contract):

1. **Scan, per shard** — one healthy replica per shard (rotated per query
   to spread load) returns its per-table Hamming top-l PRE-merge in
   stable-id space (``scan_table_topk``).  Per-shard calls run in
   parallel under a deadline; a timeout or failure retries the sibling
   replica after a backoff (the failover ladder).  Shards whose replicas
   are all down/late are simply left out.
2. **Merge at the Hamming level** — shard-local ids are mapped to global
   ids and the per-table lists merge lexicographically by (dist, gid)
   (core.search.merge_topk_shards).  Any covered-rows global top-l row is
   necessarily in its own shard's local top-l, so the merged list is
   bit-identical to a single scan over the covered rows — ties and l > n
   sentinels included.  Merging *answers* instead would break this (each
   shard's candidate union is a superset whose extra members can displace
   the true argmin).
3. **Re-rank the merged union** — each covered shard computes exact
   margins for the candidates it owns (``candidate_margins``; same margin
   expression as every other rerank path, so values are bit-identical no
   matter which index computes them), and the router selects the top-k by
   ascending (margin, gid) — the same tie order ``lax.top_k`` realises.

The result is a normal ``BatchQueryResult`` plus ``coverage`` (fraction
of live rows actually scanned) and ``degraded`` (coverage < 1).  A fully
covered answer is bit-identical to a monolithic index over all rows; a
partial answer is bit-identical to a fresh index built over only the
covered shards' rows.  When every shard is down the router answers with
coverage 0.0 and all-(-1) ids — it never raises on the query path.

Health: a replica that fails (or times out) ``fail_threshold`` times is
taken out of rotation; every query then probes downed replicas through
the same fault seam, and ``readmit_probes`` consecutive probe successes
re-admit it (hysteresis, so a flapping replica can't thrash).  A replica
that missed writes while down first catches up through the refresh
shadow-build path (``_install`` a shadow from the router's own row log +
``_adopt_refresh`` pointer swap — exactly how serving.refresh swaps a
re-learned generation in), so re-admission is atomic and the recovered
replica serves bit-identical answers.

Writes: the router owns the logical row log (per-shard feature rows,
global↔local id maps, liveness); ``insert``/``delete`` append/tombstone
there first and then push to every current replica, so a write succeeds
logically even with a whole shard down — the replicas repair from router
truth at re-admission.  Stable ids the router hands out are GLOBAL;
replica-local stable ids equal positions in the shard's append-only row
log, which ascend with global ids, preserving the (dist, id) tie
contract across the mapping.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as _FutTimeout

import numpy as np

from repro.core.indexer import IndexConfig
from repro.core.search import DIST_SENTINEL, merge_topk_shards
from repro.serving.faults import FaultPlan
from repro.serving.lsm import _MIN_CAP, LSMMultiTableIndex, _pow2_at_least
from repro.serving.multi_table import BatchQueryResult


class ShardCallTimeout(RuntimeError):
    """A replica call ran past the router's per-shard deadline."""


class ShardUnavailableError(RuntimeError):
    """Every replica of a shard failed the call ladder."""


class _ReplicaHealth:
    __slots__ = ("alive", "fails", "probe_ok", "applied")

    def __init__(self):
        self.alive = True
        self.fails = 0        # consecutive call failures while alive
        self.probe_ok = 0     # consecutive probe successes while down
        self.applied = 0      # writes applied (vs the shard's write count)


class ShardReplicaRouter:
    """Front end over S shards × R replicas of ``LSMMultiTableIndex``.

    Duck-types the scan-mode index surface ``HashQueryService`` /
    ``AsyncHashQueryService`` consume (query_scan_batch / insert / delete
    / config / version / stats / churn counters), so the services spread
    their flushes across healthy replicas without knowing the cluster
    exists.  Probe mode (lookup_batch) is not served here.
    """

    # Lock discipline, machine-checked by repro.lint: the replica table,
    # the health map, the router-owned row log, and every counter below
    # may only be touched while holding ``_mu``.  Replica *objects* are
    # internally locked (LSMMultiTableIndex._lock) — the router snapshots
    # handles under _mu and calls them with _mu released, so slow device
    # work never sits on the router's critical path (and ladder worker
    # threads, which take _mu to note health, can never deadlock against
    # a query holding it).
    _GUARDED_BY = {
        "_replicas": "_mu", "_health": "_mu",
        "_gids": "_mu", "_shard_x": "_mu", "_shard_active": "_mu",
        "_shard_of_buf": "_mu", "_local_of_buf": "_mu", "_next_id": "_mu",
        "_writes": "_mu", "_inflight": "_mu", "_rotation": "_mu",
        "version": "_mu", "queries": "_mu", "degraded_answers": "_mu",
        "last_coverage": "_mu", "failovers": "_mu", "timeouts": "_mu",
        "replica_downs": "_mu", "readmits": "_mu", "catchups": "_mu",
        "write_skips": "_mu",
    }

    def __init__(self, config: IndexConfig, shards: int = 2,
                 replicas: int = 2, deadline_ms: float = 250.0,
                 backoff_ms: float = 1.0, fail_threshold: int = 1,
                 readmit_probes: int = 2,
                 fault_plan: FaultPlan | None = None):
        assert shards >= 1 and replicas >= 1
        self.config = config
        self.shards = int(shards)
        self.replicas = int(replicas)
        self.deadline_s = float(deadline_ms) * 1e-3
        self.backoff_ms = float(backoff_ms)
        self.fail_threshold = max(1, int(fail_threshold))
        self.readmit_probes = max(1, int(readmit_probes))
        self.fault_plan = fault_plan      # immutable after construction
        self._mu = threading.RLock()
        self._replicas = [[LSMMultiTableIndex(config)
                           for _ in range(self.replicas)]
                          for _ in range(self.shards)]
        self._health = [[_ReplicaHealth() for _ in range(self.replicas)]
                        for _ in range(self.shards)]
        # router-owned logical row log, per shard: feature rows, liveness,
        # and the local→global id map (append-only, strictly increasing —
        # the monotone map that carries the (dist, id) tie order through)
        self._gids = [np.empty(0, np.int64) for _ in range(self.shards)]
        self._shard_x = [None for _ in range(self.shards)]
        self._shard_active = [np.empty(0, bool) for _ in range(self.shards)]
        # global id → (owner shard, shard-local id)
        self._shard_of_buf = np.empty(0, np.int64)
        self._local_of_buf = np.empty(0, np.int64)
        self._next_id = 0
        self._writes = [0] * self.shards     # per-shard write-op count
        self._inflight = [0] * self.shards   # write pushes in flight
        self._rotation = [0] * self.shards   # flush-spreading counter
        self.version = 0
        # observability
        self.queries = 0
        self.degraded_answers = 0
        self.last_coverage = 1.0
        self.failovers = 0
        self.timeouts = 0
        self.replica_downs = 0
        self.readmits = 0
        self.catchups = 0
        self.write_skips = 0     # replica writes skipped (replica down)
        # two pools: shard ladders run on _shard_pool, each attempt runs on
        # _call_pool so the ladder thread can enforce the deadline with
        # future.result(timeout) (a late attempt is abandoned, not joined)
        self._call_pool = ThreadPoolExecutor(
            max_workers=self.shards * self.replicas + 2,
            thread_name_prefix="cluster-call")
        self._shard_pool = ThreadPoolExecutor(
            max_workers=self.shards, thread_name_prefix="cluster-shard")

    # -- build / writes ------------------------------------------------------

    def fit(self, x) -> "ShardReplicaRouter":
        """Round-robin split the rows over shards (global row i → shard
        i mod S) and fit every replica of each shard on its shard's rows.
        Global ids are 0..n-1; shard-local ids ascend with global ids by
        construction."""
        x = np.atleast_2d(np.asarray(x, np.float32))
        n = x.shape[0]
        parts = [np.arange(s, n, self.shards) for s in range(self.shards)]
        with self._mu:
            reps = [list(row) for row in self._replicas]
        # fit replicas with _mu released: learning/hashing is the slow part
        # and nothing serves traffic before fit returns
        for s, rows in enumerate(parts):
            for rep in reps[s]:
                rep.fit(x[rows])
        with self._mu:
            self._gids = [p.astype(np.int64) for p in parts]
            self._shard_x = [x[p].copy() for p in parts]
            self._shard_active = [np.ones(p.size, bool) for p in parts]
            self._shard_of_buf = np.full(_pow2_at_least(max(n, 1), _MIN_CAP),
                                         -1, np.int64)
            self._local_of_buf = np.full(self._shard_of_buf.shape[0], -1,
                                         np.int64)
            self._shard_of_buf[:n] = np.arange(n) % self.shards
            for s, p in enumerate(parts):
                self._local_of_buf[p] = np.arange(p.size)
            self._next_id = n
            self._writes = [0] * self.shards
            for row in self._health:
                for h in row:
                    h.alive, h.fails, h.probe_ok, h.applied = True, 0, 0, 0
            self.version += 1
        return self

    def _grow_id_maps(self, need: int) -> None:
        # _mu lock held by caller
        if need <= self._shard_of_buf.shape[0]:
            return
        cap = _pow2_at_least(need, _MIN_CAP)
        so = np.full(cap, -1, np.int64)
        so[:self._next_id] = self._shard_of_buf[:self._next_id]
        lo = np.full(cap, -1, np.int64)
        lo[:self._next_id] = self._local_of_buf[:self._next_id]
        self._shard_of_buf, self._local_of_buf = so, lo

    def insert(self, x_new) -> np.ndarray:
        """Append rows (round-robin by global id).  Always succeeds
        logically — the router's row log is the source of truth; replicas
        that are down (or fail the push) miss the write and repair from
        the log at re-admission.  Returns the assigned GLOBAL ids."""
        x_new = np.atleast_2d(np.asarray(x_new, np.float32))
        k = x_new.shape[0]
        if k == 0:
            return np.empty((0,), dtype=np.int64)
        pushes = []
        with self._mu:
            gids = np.arange(self._next_id, self._next_id + k,
                             dtype=np.int64)
            self._grow_id_maps(self._next_id + k)
            owner = gids % self.shards
            self._shard_of_buf[gids] = owner
            self._next_id += k
            for s in range(self.shards):
                sel = np.flatnonzero(owner == s)
                if sel.size == 0:
                    continue
                local0 = self._gids[s].size
                self._local_of_buf[gids[sel]] = np.arange(
                    local0, local0 + sel.size)
                self._gids[s] = np.concatenate([self._gids[s], gids[sel]])
                self._shard_x[s] = np.concatenate(
                    [self._shard_x[s], x_new[sel]])
                self._shard_active[s] = np.concatenate(
                    [self._shard_active[s], np.ones(sel.size, bool)])
                targets = self._current_replicas(s)
                skipped = self.replicas - len(targets)
                if skipped:
                    self.write_skips += skipped
                self._writes[s] += 1
                self._inflight[s] += 1
                pushes.append((s, x_new[sel].copy(), targets))
            self.version += 1
        for s, xs, targets in pushes:
            try:
                for r, rep in targets:
                    self._push_write(s, r, rep,
                                     lambda rep=rep, xs=xs: rep.insert(xs))
            finally:
                with self._mu:
                    self._inflight[s] -= 1
        return gids

    def delete(self, ids) -> None:
        """Tombstone rows by GLOBAL id.  Validates against the router's
        own row log (unknown / already-deleted ids raise KeyError exactly
        like the single-index contract — a bad id is the caller's bug,
        never a replica-health event), then pushes to current replicas
        best-effort."""
        ids = np.atleast_1d(np.asarray(ids, dtype=np.int64))
        if ids.size == 0:
            return
        if np.unique(ids).size != ids.size:
            raise KeyError("duplicate ids in delete")
        pushes = []
        with self._mu:
            if ids.min() < 0 or ids.max() >= self._next_id:
                raise KeyError(f"unknown ids (never assigned): "
                               f"{ids[(ids < 0) | (ids >= self._next_id)][:8]}")
            owner = self._shard_of_buf[ids]
            local = self._local_of_buf[ids]
            for s in range(self.shards):
                sel = local[owner == s]
                if sel.size and not self._shard_active[s][sel].all():
                    raise KeyError("delete of already-deleted id")
            for s in range(self.shards):
                sel = local[owner == s]
                if sel.size == 0:
                    continue
                self._shard_active[s][sel] = False
                targets = self._current_replicas(s)
                skipped = self.replicas - len(targets)
                if skipped:
                    self.write_skips += skipped
                self._writes[s] += 1
                self._inflight[s] += 1
                pushes.append((s, sel.copy(), targets))
            self.version += 1
        for s, sel, targets in pushes:
            try:
                for r, rep in targets:
                    self._push_write(s, r, rep,
                                     lambda rep=rep, sel=sel: rep.delete(sel))
            finally:
                with self._mu:
                    self._inflight[s] -= 1

    def _current_replicas(self, s: int) -> list:
        # _mu lock held by caller: alive replicas that applied every write
        out = []
        for r in range(self.replicas):
            h = self._health[s][r]
            if h.alive and h.applied == self._writes[s]:
                out.append((r, self._replicas[s][r]))
        return out

    def _push_write(self, s: int, r: int, rep, fn) -> None:
        """One replica write through the fault seam; a failure demotes the
        replica (it is now behind the log regardless of the cause)."""
        try:
            self._guarded_call(s, r, "write", fn)
        except Exception:
            self._note_failure(s, r, force_down=True)
            return
        with self._mu:
            self._health[s][r].applied += 1
            self._health[s][r].fails = 0

    # -- the fault/distribution seam -----------------------------------------

    def _guarded_call(self, s: int, r: int, op: str, fn):
        """EVERY replica interaction funnels through here — the seam the
        FaultPlan hooks, and where a remote-host transport would slot in."""
        if self.fault_plan is not None:
            self.fault_plan.on_call(s, r, op)
        return fn()

    def _note_failure(self, s: int, r: int, force_down: bool = False,
                      timeout: bool = False) -> None:
        with self._mu:
            h = self._health[s][r]
            h.fails += 1
            h.probe_ok = 0
            if timeout:
                self.timeouts += 1
            if h.alive and (force_down or h.fails >= self.fail_threshold):
                h.alive = False
                self.replica_downs += 1

    def _note_success(self, s: int, r: int) -> None:
        with self._mu:
            self._health[s][r].fails = 0

    def _attempt(self, s: int, r: int, op: str, fn):
        """One deadline-bounded replica call.  Runs on _call_pool so this
        (ladder) thread can abandon a late attempt; the stray worker
        finishes eventually and its result is discarded."""
        fut = self._call_pool.submit(self._guarded_call, s, r, op, fn)
        try:
            out = fut.result(timeout=self.deadline_s)
        except _FutTimeout:
            self._note_failure(s, r, timeout=True)
            raise ShardCallTimeout(
                f"shard {s} replica {r} {op} past "
                f"{self.deadline_s * 1e3:.0f} ms deadline") from None
        except Exception:
            self._note_failure(s, r)
            raise
        self._note_success(s, r)
        return out

    def _ladder_order(self, s: int, prefer: int | None) -> list:
        # _mu lock held by caller: serving replicas rotated for load
        # spread; `prefer` (the replica that served this query's scan)
        # goes first so phase 2 reuses its warm state when possible
        cur = self._current_replicas(s)
        if not cur:
            return []
        rot = self._rotation[s] % len(cur)
        order = cur[rot:] + cur[:rot]
        if prefer is not None:
            order.sort(key=lambda t: t[0] != prefer)
        return order

    def _shard_ladder(self, s: int, op: str, fn_of_rep,
                      prefer: int | None = None):
        """retry → sibling replica → ShardUnavailableError: the failover
        ladder.  Each rung is one deadline-bounded attempt; rungs after
        the first back off exponentially and count as failovers."""
        with self._mu:
            order = self._ladder_order(s, prefer)
        last: Exception | None = None
        for k, (r, rep) in enumerate(order):
            if k:
                with self._mu:
                    self.failovers += 1
                if self.backoff_ms:
                    time.sleep(self.backoff_ms * 1e-3 * (2 ** (k - 1)))
            try:
                return r, self._attempt(s, r, op,
                                        lambda rep=rep: fn_of_rep(rep))
            except Exception as e:
                last = e
        raise ShardUnavailableError(
            f"shard {s}: all replicas failed {op}") from last

    # -- health probes + hysteresis ------------------------------------------

    def _probe_down_replicas(self) -> None:
        """Probe every downed replica through the fault seam; after
        ``readmit_probes`` consecutive successes, catch the replica up
        from the router's row log (if it missed writes) and re-admit it.
        Piggybacked on every query — recovery needs no extra driver."""
        with self._mu:
            targets = [(s, r, self._replicas[s][r])
                       for s in range(self.shards)
                       for r in range(self.replicas)
                       if not self._health[s][r].alive]
        for s, r, rep in targets:
            try:
                self._guarded_call(s, r, "probe", lambda rep=rep: rep.version)
            except Exception:
                with self._mu:
                    self._health[s][r].probe_ok = 0
                continue
            with self._mu:
                h = self._health[s][r]
                h.probe_ok += 1
                # defer re-admission while a write push is in flight: the
                # catch-up snapshot could otherwise double-apply the write
                ready = (h.probe_ok >= self.readmit_probes
                         and self._inflight[s] == 0)
                stale = h.applied != self._writes[s]
                writes_at = self._writes[s]
            if not ready:
                continue
            if stale:
                if not self._catchup_replica(s, r, rep, writes_at):
                    continue        # raced a write; retry next probe round
            with self._mu:
                h = self._health[s][r]
                h.alive, h.fails, h.probe_ok = True, 0, 0
                h.applied = writes_at
                self.readmits += 1

    def _catchup_replica(self, s: int, r: int, rep, writes_at: int) -> bool:
        """Rebuild a stale replica from the router's row log via the
        refresh shadow-build path: ``_install`` a shadow index over the
        shard's live rows (families copied from a current sibling when one
        exists, else re-derived from config.seed — identical for seeded
        methods) and ``_adopt_refresh`` it in under the replica's lock,
        exactly how serving.refresh swaps a re-learned generation in.
        Returns False if a write raced the snapshot (caller retries)."""
        with self._mu:
            live_local = np.flatnonzero(self._shard_active[s])
            x_live = self._shard_x[s][live_local].copy()
            d = self._shard_x[s].shape[1]
            n_s = self._gids[s].size
            sibs = self._current_replicas(s)
        sib = next((rr_rep for rr, rr_rep in sibs), None)
        shadow = LSMMultiTableIndex(self.config)
        if sib is not None:
            with sib._lock:
                fams = list(sib.families)
                bcap = sib._bcap
        else:
            import jax.numpy as jnp
            xj = jnp.asarray(x_live if x_live.size
                             else np.zeros((1, d), np.float32))
            fams = [shadow._make_family(shadow.table_key(t), xj)
                    for t in range(shadow.num_tables)]
            bcap = _MIN_CAP
        shadow._install(x_live, fams, ids=live_local, next_id=n_s,
                        bcap_floor=bcap)
        with self._mu:
            if self._writes[s] != writes_at or self._inflight[s]:
                return False
            with rep._lock:
                rep._adopt_refresh(shadow)
            self.catchups += 1
        return True

    # -- queries -------------------------------------------------------------

    def _scan_covered_shard(self, s: int, w: np.ndarray, l: int,
                            gids: np.ndarray):
        """Phase-1 ladder for one shard: per-table (dist, local-id) top-l
        from a healthy replica, mapped to GLOBAL ids.  Runs on
        _shard_pool, so shards scan (and fail over) concurrently."""
        r, (d, ids) = self._shard_ladder(
            s, "scan",
            lambda rep: rep.scan_table_topk(w, l))
        known = (ids >= 0) & (ids < gids.size)
        g = np.where(known, gids[np.clip(ids, 0, gids.size - 1)], -1)
        # rows newer than this query's snapshot (concurrent insert racing
        # the scan) drop to sentinels rather than mis-mapping
        d = np.where(known | (ids < 0), d, DIST_SENTINEL).astype(np.int32)
        return r, d, g

    def query_scan_batch(self, w, l: int = 16, topk: int = 1, mask=None
                         ) -> BatchQueryResult:
        """Cluster-wide scan answer (see module docstring for the
        protocol).  Never raises on replica failure — lost shards shrink
        ``coverage`` and set ``degraded`` instead.  ``mask`` is a bool
        mask over GLOBAL stable-id space, as in the single-index paths."""
        w = np.atleast_2d(np.asarray(w, np.float32))
        b = w.shape[0]
        self._probe_down_replicas()
        with self._mu:
            if self._shard_x[0] is None:
                raise RuntimeError("ShardReplicaRouter.query_scan_batch "
                                   "before fit()")
            gids_snap = list(self._gids)
            live = [int(a.sum()) for a in self._shard_active]
            shard_of = self._shard_of_buf
            local_of = self._local_of_buf
            n_id = self._next_id
            self._rotation = [c + 1 for c in self._rotation]
            self.queries += 1
        total_live = sum(live)
        hits = np.zeros(self.config.tables, dtype=np.int64)
        if total_live == 0:
            return self._finish(b, topk, np.full((b, topk), -1, np.int64),
                                np.full((b, topk), np.inf, np.float32),
                                np.zeros(b, bool),
                                [np.empty(0, np.int64) for _ in range(b)],
                                hits, 1.0)
        # phase 1: parallel per-shard scans with failover ladders
        want = [s for s in range(self.shards) if live[s] > 0]
        futs = {s: self._shard_pool.submit(
                    self._scan_covered_shard, s, w, l, gids_snap[s])
                for s in want}
        scans: dict[int, tuple] = {}
        served: dict[int, int] = {}
        for s, fut in futs.items():
            try:
                r, d, g = fut.result()
            except ShardUnavailableError:
                continue
            scans[s] = (d, g)
            served[s] = r
        # phases 2+3, re-run with a shard dropped if its re-rank fails too
        covered = sorted(scans)
        while covered:
            d_m, g_m = merge_topk_shards([scans[s][0] for s in covered],
                                         [scans[s][1] for s in covered], l)
            flat = np.sort(g_m.transpose(1, 0, 2).reshape(b, -1), axis=1)
            uniq = flat >= 0
            uniq[:, 1:] &= flat[:, 1:] != flat[:, :-1]
            cwidth = _pow2_at_least(max(1, int(uniq.sum(axis=1).max())),
                                    _MIN_CAP)   # bounded retrace buckets
            cand = np.full((b, cwidth), -1, np.int64)
            for i in range(b):
                sel = flat[i, uniq[i]]
                cand[i, :sel.size] = sel
            known = (cand >= 0) & (cand < n_id)
            owner = np.where(known, shard_of[np.clip(cand, 0, n_id - 1)], -1)
            margins = np.full((b, cwidth), np.inf, np.float32)
            failed = []
            for s in covered:
                mine = owner == s
                if not mine.any():
                    continue
                local = np.where(mine,
                                 local_of[np.clip(cand, 0, n_id - 1)], -1)
                try:
                    _, m_s = self._shard_ladder(
                        s, "margins",
                        lambda rep, local=local: rep.candidate_margins(
                            w, local),
                        prefer=served.get(s))
                except ShardUnavailableError:
                    failed.append(s)
                    continue
                put = mine & np.isfinite(m_s)
                margins[put] = m_s[put]
            if not failed:
                break
            covered = [s for s in covered if s not in failed]
        if not covered:
            return self._finish(b, topk, np.full((b, topk), -1, np.int64),
                                np.full((b, topk), np.inf, np.float32),
                                np.zeros(b, bool),
                                [np.empty(0, np.int64) for _ in range(b)],
                                hits, 0.0)
        # phase 3: global top-k by ascending (margin, gid) — the exact tie
        # order lax.top_k realises over an ascending-by-id candidate axis
        mask_arr = None if mask is None else np.asarray(mask, dtype=bool)
        sel_valid = (cand >= 0) & np.isfinite(margins)
        if mask_arr is not None:
            in_mask = np.zeros_like(sel_valid)
            ok = (cand >= 0) & (cand < mask_arr.size)
            in_mask[ok] = mask_arr[cand[ok]]
            sel_valid &= in_mask
        ids_topk = np.full((b, topk), -1, np.int64)
        margins_topk = np.full((b, topk), np.inf, np.float32)
        for i in range(b):
            mm = np.where(sel_valid[i], margins[i], np.inf)
            order = np.lexsort((cand[i], mm))[:topk]
            mt = mm[order]
            ids_topk[i, :order.size] = np.where(np.isfinite(mt),
                                                cand[i][order], -1)
            margins_topk[i, :order.size] = mt
        cands = [cand[i][cand[i] >= 0] for i in range(b)]
        hits = (g_m >= 0).sum(axis=(1, 2)).astype(np.int64)
        coverage = sum(live[s] for s in covered) / total_live
        return self._finish(b, topk, ids_topk, margins_topk,
                            sel_valid.any(axis=1), cands, hits, coverage)

    def _finish(self, b, topk, ids_topk, margins_topk, nonempty, cands,
                hits, coverage) -> BatchQueryResult:
        degraded = coverage < 1.0
        with self._mu:
            self.last_coverage = float(coverage)
            if degraded:
                self.degraded_answers += 1
        return BatchQueryResult(
            ids_topk[:, 0], margins_topk[:, 0], nonempty, cands, hits,
            ids_topk=ids_topk if topk > 1 else None,
            margins_topk=margins_topk if topk > 1 else None,
            coverage=float(coverage), degraded=degraded)

    # -- service-compat surface ----------------------------------------------

    def lookup_batch(self, w, qcodes=None):
        raise NotImplementedError(
            "ShardReplicaRouter serves scan mode only — use "
            "HashQueryService(router, mode='scan')")

    @property
    def n(self) -> int:
        with self._mu:
            return int(sum(int(a.sum()) for a in self._shard_active))

    def _replica_sum(self, attr: str) -> int:
        with self._mu:
            reps = [rep for row in self._replicas for rep in row]
        return int(sum(getattr(rep, attr) for rep in reps))

    @property
    def device_uploads(self) -> int:
        return self._replica_sum("device_uploads")

    @property
    def scan_state_rebuilds(self) -> int:
        return self._replica_sum("scan_state_rebuilds")

    @property
    def compaction_steps(self) -> int:
        return self._replica_sum("compaction_steps")

    @property
    def compactions(self) -> int:
        return self._replica_sum("compactions")

    def health(self) -> list[list[dict]]:
        with self._mu:
            return [[{"alive": h.alive, "fails": h.fails,
                      "probe_ok": h.probe_ok, "applied": h.applied,
                      "writes": self._writes[s]}
                     for h in self._health[s]]
                    for s in range(self.shards)]

    def stats(self) -> dict:
        with self._mu:
            rows = int(sum(g.size for g in self._gids))
            n = int(sum(int(a.sum()) for a in self._shard_active))
            alive = sum(h.alive for row in self._health for h in row)
            out = {
                "backend": "cluster",
                "shards": self.shards,
                "replicas": self.replicas,
                "replicas_alive": int(alive),
                "n": n,
                "rows": rows,
                "version": self.version,
                "queries": self.queries,
                "degraded_answers": self.degraded_answers,
                "last_coverage": self.last_coverage,
                "failovers": self.failovers,
                "timeouts": self.timeouts,
                "replica_downs": self.replica_downs,
                "readmits": self.readmits,
                "catchups": self.catchups,
                "write_skips": self.write_skips,
                "writes": list(self._writes),
            }
        out["health"] = self.health()
        out["device_uploads"] = self.device_uploads
        if self.fault_plan is not None:
            out["faults"] = self.fault_plan.stats()
        return out
