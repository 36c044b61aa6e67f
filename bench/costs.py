"""Bytes the algorithm has to move, counted from shapes alone, so the count
stays the same whatever implements a layer (a relayout, padding or a
second pass that an implementation adds is its own cost, not the
algorithm's)."""
from __future__ import annotations


def code_words(bits: int) -> int:
    return (bits + 31) // 32


def scan_bytes(n: int, bits: int, tables: int, batch: int, l: int,
               masked: bool) -> int:
    """One top-l Hamming scan of ``batch`` queries over ``tables`` tables
    of n packed codes: every code read once, the mask once (one byte a
    row), the query codes read, and l (distance, row) pairs of 4 bytes
    each written per query and table."""
    w = code_words(bits)
    return (tables * n * w * 4 + (n if masked else 0)
            + tables * batch * w * 4 + tables * batch * l * 8)


def rerank_bytes(rows: int, d: int, batch: int) -> int:
    """Exact-margin re-rank: each re-ranked feature row read once, and the
    batch's hyperplanes."""
    return (rows + batch) * d * 4
