"""Plain reference for the point-to-hyperplane answer, and the comparison
that decides a run's ``correct``.

It imports nothing of the program.  From the configuration alone it
re-derives each table's hash projections (the seeded counter-based
Gaussian generator, copied here), hashes the corpus and each query with
the stated operand rounding, ranks every row by Hamming distance, and
recomputes margins in float64 on the host.

What one answer is compared on (one table, ``index.tables == 1``):

- ``topl_bad``: candidate rows that cannot be the top-l by (distance, row)
  under the reference codes.  A code bit counts as certain only where its
  two projections lie farther from zero than the float32 accumulation of
  the stated rounding can move them; a distance with uncertain bits is an
  interval, and the program's set is accepted if some resolution of the
  intervals makes it the top-l.  Exact otherwise, ties included.
- ``pick_bad``: a pick that is not an unmasked candidate, or an answer that
  says "no candidate" while one exists (and the reverse).
- ``gap_units``: float64 margin of the pick minus the least float64 margin
  over the unmasked candidates, in units of 2^-24 · sum|x_i w_i| / ||w||
  summed over the two rows (what float32 rounding can account for).
- ``margin_units``: the reported float32 margin against the pick's float64
  margin, in the same unit of the pick's row.

``Control`` is the same pipeline put in the program's place at the next
lower precision (float8 e4m3 hash operands, bfloat16 re-rank operands); its
answers have to fail the comparison.

This module is every configuration's reference unless the configuration
names ``"reference": "<name>"``; the harness then loads
``bench/references/<name>.py`` in its place.  Such a file, like this one,
imports nothing of the program and defines:

- ``Reference(x, cfg, index_seed)``: built once after the window, over the
  corpus ``x`` exactly as the generator laid it out (on one device or
  sharded), the configuration's dict and the index's seed.
- ``Reference.topl_bad(ws, candidates)``: per query (rows of ``ws``, float32
  host array), how many of the program's candidates (a list of int arrays
  of row ids) cannot be its top-l; an int64 host array.
- ``Reference.margins(w, rows)``: the float64 margins ``|w.x|/||w||`` of the
  rows named (a sorted int64 host array of valid row ids) and each row's
  float32-rounding unit ``2^-24 * sum|x_i w_i| / ||w||``, as two float64 host
  arrays.  ``compare`` reads margins only through it, so a reference never
  needs the corpus on the host.
- optionally ``Control(x, cfg, index_seed)``: the reference one precision
  lower in the program's place (``query_batch(ws, mask)`` and ``stats()``,
  as ``HashQueryService``); ``--control 1`` refuses a reference without it.

``compare`` below stays the harness's: it decides ``correct`` for every
reference.
"""
from __future__ import annotations

from functools import partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
U32 = 2.0 ** -24          # float32 unit roundoff
ROW_BITS = 21             # rows < 2^21 in a (distance, row) sort key

_GOLD = 0x9E3779B9
_FNV = 0x01000193


def _fmix32(h):
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> jnp.uint32(16))


def seeded_gaussian(seed, tag: int, rows, cols):
    """N(0, 1) float32 at absolute (row, col): murmur3 finalizer chain over
    the indices, then one Box-Muller branch."""
    s = _fmix32(jnp.uint32(seed) + jnp.uint32(tag) * jnp.uint32(_GOLD))
    h = _fmix32(s ^ (rows.astype(jnp.uint32) * jnp.uint32(_FNV)))
    h = _fmix32(h ^ cols.astype(jnp.uint32))
    b1 = _fmix32(h ^ jnp.uint32(0x632BE59B))
    b2 = _fmix32(h ^ jnp.uint32(0x2545F491))
    u1 = ((b1 >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)
          + jnp.float32(0.5)) * jnp.float32(2.0 ** -24)
    u2 = ((b2 >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)
          + jnp.float32(0.5)) * jnp.float32(2.0 ** -24)
    r = jnp.sqrt(jnp.float32(-2.0) * jnp.log(u1))
    return (r * jnp.cos(jnp.float32(2.0 * jnp.pi) * u2)).astype(jnp.float32)


def table_seed(index_seed: int, table: int) -> int:
    """32-bit generator seed of one table: the uint32 drawn from
    fold_in(PRNGKey(index_seed), table)."""
    key = jax.random.fold_in(jax.random.PRNGKey(index_seed), table)
    return int(jax.random.bits(key, (), jnp.uint32))


@jax.jit
def _projections(seed, rows, cols):
    return seeded_gaussian(seed, 0, rows, cols), \
        seeded_gaussian(seed, 1, rows, cols)


def projections(index_seed: int, d: int, k: int):
    rows = jnp.arange(d, dtype=jnp.int32)[:, None]
    cols = jnp.arange(k, dtype=jnp.int32)[None, :]
    return _projections(jnp.uint32(table_seed(index_seed, 0)), rows, cols)


def _round(a, dtype: str):
    return a if dtype == "float32" else \
        a.astype(jnp.dtype(dtype)).astype(jnp.float32)


def _near_midpoint(u):
    """Elements whose float32 value lies within 16 float32 ulps of a
    bfloat16 rounding midpoint: another float32 rounding of the same
    Gaussian may round to the other bfloat16 neighbour."""
    low = jax.lax.bitcast_convert_type(u, jnp.uint32) & jnp.uint32(0xFFFF)
    return jnp.abs(low.astype(jnp.int32) - 0x8000) <= 16


@partial(jax.jit, static_argnames=("rounding", "bits"))
def _hash_block(x, u, v, *, rounding: str, bits: int):
    """Database-style sign bits of rows x, packed, and the packed mask of
    bits whose sign the stated float32 accumulation cannot pin down."""
    xr = _round(x, rounding)
    ax = jnp.abs(xr)
    terms = jnp.sum(xr != 0, axis=1, keepdims=True).astype(jnp.float32)
    out = []
    for p in (u, v):
        pr = _round(p, rounding)
        s = jnp.dot(xr, pr, precision=HI)
        # |program sum - exact| and |this sum - exact| are each at most
        # terms·u·sum|terms|; a generator value near a bfloat16 midpoint
        # may round one ulp (2^-8 relative) the other way in the program
        bound = 2.0 * terms * U32 * jnp.dot(ax, jnp.abs(pr), precision=HI)
        if rounding == "bfloat16":
            amb = jnp.where(_near_midpoint(p), jnp.abs(pr) * 2.0 ** -8, 0.0)
            bound = bound + jnp.dot(ax, amb, precision=HI)
        out.append((s, bound))
    (su, bu), (sv, bv) = out
    bit = (su >= 0) == (sv >= 0)
    unsure = (jnp.abs(su) <= bu) | (jnp.abs(sv) <= bv)
    w = jnp.uint32(1) << jnp.arange(bits, dtype=jnp.uint32)
    pack = lambda b: jnp.sum(jnp.where(b, w, jnp.uint32(0)), axis=1,
                             dtype=jnp.uint32)
    return pack(bit), pack(unsure)


def hash_rows(x, u, v, rounding: str):
    """(codes (n,) uint32, unsure (n,) uint32) in blocks of about 2^25
    values."""
    bits = u.shape[1]
    block = max(256, (1 << 25) // x.shape[1])
    if bits > 32:
        raise ValueError("the reference packs one 32-bit word per code")
    codes, unsure = [], []
    for s in range(0, x.shape[0], block):
        c, m = _hash_block(x[s:s + block], u, v, rounding=rounding,
                           bits=bits)
        codes.append(c)
        unsure.append(m)
    return jnp.concatenate(codes), jnp.concatenate(unsure)


def _popcount(x):
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((x * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


@partial(jax.jit, static_argnames=("l",))
def _topl_favoring(codes, unsure, q, qu, in_s, l: int):
    """Top-l rows by (distance, row) where each distance interval is
    resolved in favour of the rows in_s marks: low end inside, high end
    outside.  Returns (B, l) rows."""
    un = unsure[None, :] | qu[:, None]
    diff = (codes[None, :] ^ q[:, None]) & ~un
    lo = _popcount(diff)
    dist = jnp.where(in_s, lo, lo + _popcount(un))
    rows = jnp.arange(codes.shape[0], dtype=jnp.int32)
    key = (dist << ROW_BITS) + rows[None, :]
    _, top = jax.lax.top_k(-key, l)
    return top


class Reference:
    """The configuration's hash, built once over the corpus on the device."""

    def __init__(self, x, cfg: dict, index_seed: int,
                 rounding: str | None = None):
        idx = cfg["index"]
        if idx["tables"] != 1:
            raise ValueError("the reference compares one table")
        if x.shape[0] >= 1 << ROW_BITS:
            raise ValueError("row ids must fit the sort key")
        self.x = x
        self.bits = idx["bits"]
        self.l = min(idx["scan_l"], x.shape[0])
        self.rounding = rounding or cfg["precision"]["hash_operands"]
        self.u, self.v = projections(index_seed, x.shape[1], self.bits)
        self.codes, self.unsure = hash_rows(x, self.u, self.v, self.rounding)

    def query_codes(self, ws):
        """Query-side codes are the complement of the database-style bits
        within the code width (h(P_w) = -h(w)); the unsure mask is shared."""
        c, m = hash_rows(jnp.asarray(ws, jnp.float32), self.u, self.v,
                         self.rounding)
        full = jnp.uint32((1 << self.bits) - 1 if self.bits < 32
                          else 0xFFFFFFFF)
        return c ^ full, m

    def topl_bad(self, ws, cands: list[np.ndarray], block: int = 16):
        """Per query: how many of its candidates no resolution of the
        unsure bits puts in the top-l (a list of the wrong length counts
        the difference too)."""
        n = self.codes.shape[0]
        bad = np.zeros(len(cands), np.int64)
        for s in range(0, len(cands), block):
            q, qu = self.query_codes(ws[s:s + block])
            in_s = np.zeros((q.shape[0], n), bool)
            for i, c in enumerate(cands[s:s + block]):
                c = np.asarray(c, np.int64)
                ok = (c >= 0) & (c < n)
                in_s[i, c[ok]] = True
                bad[s + i] += abs(self.l - len(c)) + int((~ok).sum()) \
                    + (len(c) - len(np.unique(c)))
            top = np.asarray(_topl_favoring(self.codes, self.unsure, q, qu,
                                            jnp.asarray(in_s), self.l))
            for i in range(q.shape[0]):
                bad[s + i] += self.l - int(in_s[i, top[i]].sum())
        return bad

    def margins(self, w, rows: np.ndarray):
        """``margins64`` of ``rows``, gathered from the corpus on the device
        (padded to a power of two, so a few gather shapes serve every
        answer) and read back as they are."""
        pad = np.zeros(max(8, 1 << (len(rows) - 1).bit_length()), np.int32)
        pad[:len(rows)] = rows
        return margins64(np.asarray(_take(self.x, pad))[:len(rows)], w)


@jax.jit
def _take(x, rows):
    return x[rows]


def margins64(xs: np.ndarray, w: np.ndarray):
    """(float64 margins |w.x|/||w||, float32-rounding unit) of the float32
    rows xs."""
    xs = xs.astype(np.float64)
    w64 = np.asarray(w, np.float64)
    nw = max(np.linalg.norm(w64), 1e-12)
    return np.abs(xs @ w64) / nw, U32 * (np.abs(xs) @ np.abs(w64)) / nw


def compare(answers: list, ref, n: int) -> dict:
    """The compared numbers over a list of answers, against a reference as
    the module docstring describes, over a corpus of ``n`` rows.  Each
    answer has ``w``, ``mask`` (bool over rows, or None), and the program's
    ``index``, ``margin``, ``nonempty`` and ``candidates``."""
    ws = np.stack([a.w for a in answers]).astype(np.float32)
    bad = ref.topl_bad(ws, [a.candidates for a in answers])
    pick_bad, gap, merr = 0, 0.0, 0.0
    for a in answers:
        c = np.unique(np.asarray(a.candidates, np.int64))
        c = c[(c >= 0) & (c < n)]
        valid = c if a.mask is None else c[a.mask[c]]
        if valid.size == 0:
            pick_bad += int(bool(a.nonempty))
            continue
        if not a.nonempty or a.index not in set(valid.tolist()):
            pick_bad += 1
            continue
        m, unit = ref.margins(a.w, valid)
        at = int(np.flatnonzero(valid == a.index)[0])
        best = int(np.argmin(m))
        gap = max(gap, (m[at] - m[best]) / (unit[at] + unit[best]))
        merr = max(merr, abs(float(a.margin) - m[at]) / unit[at])
    return {"topl_bad": int(bad.sum()), "pick_bad": pick_bad,
            "gap_units": float(gap), "margin_units": float(merr),
            "checked": len(answers)}


@jax.jit
def _control_rerank(x, ws, top, valid):
    xs = x[top].astype(jnp.bfloat16).astype(jnp.float32)
    wr = ws.astype(jnp.bfloat16).astype(jnp.float32)
    m = jnp.abs(jnp.sum(xs * wr[:, None, :], axis=-1))
    m = m / jnp.maximum(jnp.linalg.norm(ws, axis=1, keepdims=True), 1e-12)
    m = jnp.where(valid, m, jnp.inf)
    at = jnp.argmin(m, axis=1)
    return jnp.take_along_axis(top, at[:, None], 1)[:, 0], \
        jnp.take_along_axis(m, at[:, None], 1)[:, 0]


class Control:
    """The reference in the program's place, one precision lower: hash
    operands rounded to float8 e4m3 and re-rank operands to bfloat16.
    Answers like ``HashQueryService.query_batch``."""

    def __init__(self, x, cfg: dict, index_seed: int):
        self.x = x
        self.ref = Reference(x, cfg, index_seed, rounding="float8_e4m3fn")
        self.no_unsure = jnp.zeros_like(self.ref.unsure)

    def query_batch(self, ws, mask=None):
        ws = np.atleast_2d(np.asarray(ws, np.float32))
        q, _ = self.ref.query_codes(ws)
        in_s = jnp.zeros((q.shape[0], self.x.shape[0]), bool)
        top = _topl_favoring(self.ref.codes, self.no_unsure, q,
                             jnp.zeros_like(q), in_s, self.ref.l)
        valid = jnp.ones(top.shape, bool) if mask is None else \
            jnp.asarray(np.asarray(mask, bool))[top]
        pick, m = _control_rerank(self.x, jnp.asarray(ws), top, valid)
        top, pick, m = np.asarray(top), np.asarray(pick), np.asarray(m)
        valid = np.asarray(valid)
        return [SimpleNamespace(index=int(pick[i]), margin=float(m[i]),
                                nonempty=bool(valid[i].any()),
                                candidates=np.sort(top[i]).astype(np.int64))
                for i in range(len(ws))]

    def stats(self) -> dict:
        return {}
