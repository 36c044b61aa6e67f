"""Pass 4 — runtime sentinels: TraceCounter and lock assertions.

``TraceCounter`` turns the "never retraces across insert / delete /
compaction / swap" comments into asserted regression tests: it snapshots
the jit trace-cache sizes of registered entrypoints (and the entry
counts of ``lru_cache``'d jit factories) and asserts a code window added
none.  A retrace here is exactly the PR 3 bug class — a 9→444 QPS cliff
that no correctness test sees.

``runtime_lock_checks`` is the opt-in runtime mode of the
lock-discipline pass: inside the context, reads/writes of
``_GUARDED_BY`` attributes on the given classes assert the mapped lock
is held.  RLock/Condition expose real ownership (``_is_owned``); a
plain ``threading.Lock`` only exposes ``locked()`` (held by *someone*),
the best available there.  Attrs in a class's ``_RUNTIME_LOCK_EXEMPT``
are skipped (documented benign racy reads — the static pass still
covers them via the baseline file, with reasons).
"""
from __future__ import annotations

import contextlib
import threading


def _cache_count(fn) -> int:
    """Trace count of a jitted callable, or entry count of an lru_cache'd
    jit factory (a new entry == a newly built + traced closure)."""
    if hasattr(fn, "_cache_size"):
        return fn._cache_size()
    if hasattr(fn, "cache_info"):
        return fn.cache_info().currsize
    raise TypeError(f"{fn!r} exposes neither _cache_size (jax.jit) nor "
                    f"cache_info (lru_cache)")


class TraceCounter:
    """Snapshot/assert helper over named jit entrypoints.

    >>> tc = TraceCounter(scan_trace_targets())
    >>> ...warmup traffic...
    >>> with tc.assert_no_retrace():
    ...     ...steady-state traffic...
    """

    def __init__(self, targets: dict):
        self.targets = dict(targets)

    def snapshot(self) -> dict:
        return {name: _cache_count(fn) for name, fn in self.targets.items()}

    def deltas(self, before: dict) -> dict:
        now = self.snapshot()
        return {name: now[name] - before.get(name, 0) for name in now
                if now[name] != before.get(name, 0)}

    @contextlib.contextmanager
    def assert_no_retrace(self):
        before = self.snapshot()
        yield self
        grew = self.deltas(before)
        assert not grew, (
            f"jit entrypoints retraced during a window that must be "
            f"trace-stable: {grew} (new traces per entrypoint). A retrace "
            f"here means a value that should be a traced operand (or a "
            f"properly keyed static) changed identity — the PR 3 QPS-cliff "
            f"bug class.")


def scan_trace_targets() -> dict:
    """The jit entrypoints the serving scan path goes through —
    query_scan_batch (LSM base+delta), query hashing, dedup, mask, rerank,
    and the lru'd sharded-scan factories."""
    from repro.core import search
    from repro.kernels import ops
    from repro.serving import batch_query as bq

    return {
        "ops._topk_grouped_impl": ops._topk_grouped_impl,
        "ops._topk_grouped_rows": ops._topk_grouped_rows,
        "search.hamming_topk_grouped_hist": search.hamming_topk_grouped_hist,
        "search._grouped_topk_lax": search._grouped_topk_lax,
        "search.merge_topk_segments": search.merge_topk_segments,
        "search.margin_rerank_batch": search.margin_rerank_batch,
        "search.margin_rerank_segmented": search.margin_rerank_segmented,
        "search._sharded_fn": search._sharded_fn,
        "search._sharded_scan_fn": search._sharded_scan_fn,
        "search._sharded_rerank_fn": search._sharded_rerank_fn,
        "bq._bh_query_codes": bq._bh_query_codes,
        "bq._bh_db_codes": bq._bh_db_codes,
        "bq._seeded_query_codes": bq._seeded_query_codes,
        "bq.dedup_candidates": bq.dedup_candidates,
        "bq.dedup_packed": bq.dedup_packed,
        "bq.mask_candidates": bq.mask_candidates,
        "ops.bilinear_hash_seeded_grouped": ops.bilinear_hash_seeded_grouped,
    }


# ---------------------------------------------------------------------------
# runtime lock assertions
# ---------------------------------------------------------------------------

def _lock_is_held(lock) -> bool:
    if hasattr(lock, "_is_owned"):      # RLock, Condition
        return lock._is_owned()
    return lock.locked()                # plain Lock: held by someone


@contextlib.contextmanager
def runtime_lock_checks(*classes):
    """Enforce each class's ``_GUARDED_BY`` map with runtime lock-ownership
    assertions on instance attribute access.  Instances are only checked
    once fully constructed (``__init__`` runs unarmed)."""
    saved = []
    for cls in classes:
        guarded = dict(cls._GUARDED_BY)
        exempt = set(getattr(cls, "_RUNTIME_LOCK_EXEMPT", ()))
        orig_get = cls.__getattribute__
        orig_set = cls.__setattr__
        orig_init = cls.__init__
        saved.append((cls, orig_get, orig_set, orig_init))

        def make(cls, guarded, exempt, orig_get, orig_set, orig_init):
            def _assert_held(self, name, verb):
                if name not in guarded or name in exempt:
                    return
                try:
                    armed = orig_get(self, "_lint_lock_armed")
                except AttributeError:
                    return
                if not armed:
                    return
                lock = orig_get(self, guarded[name])
                if not _lock_is_held(lock):
                    raise AssertionError(
                        f"unlocked {verb} of {cls.__name__}.{name} "
                        f"(GUARDED_BY {guarded[name]}) in thread "
                        f"{threading.current_thread().name}")

            def __getattribute__(self, name):
                _assert_held(self, name, "read")
                return orig_get(self, name)

            def __setattr__(self, name, value):
                _assert_held(self, name, "write")
                orig_set(self, name, value)

            def __init__(self, *a, **kw):
                orig_init(self, *a, **kw)
                object.__setattr__(self, "_lint_lock_armed", True)

            return __getattribute__, __setattr__, __init__

        g, s, i = make(cls, guarded, exempt, orig_get, orig_set, orig_init)
        cls.__getattribute__ = g
        cls.__setattr__ = s
        cls.__init__ = i
    try:
        yield
    finally:
        for cls, orig_get, orig_set, orig_init in saved:
            cls.__getattribute__ = orig_get
            cls.__setattr__ = orig_set
            cls.__init__ = orig_init
