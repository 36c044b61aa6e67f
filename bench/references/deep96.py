"""Plain reference for a corpus of any size, row-sharded over a mesh or on
one device: the ``deep96`` configuration's.

It imports nothing of the program.  It keeps ``reference.py``'s interface
and semantics (see that docstring) and reuses its size-independent parts:
the seeded projections, the interval hash of a block of rows, the popcount
and ``margins64``; ``compare`` stays ``reference.py``'s.  What changes with
size:

- the corpus is hashed on each shard from its own rows, a block a launch;
- ``topl_bad`` takes the program's candidate lists, never a dense (B, n)
  mask.  The favoured top-l that ``reference.py`` computes (candidates at
  the low ends of their distance intervals, every other row at its high
  end) is the top-l of the candidates at their low ends merged with the
  best l + |candidates| rows at their high ends.  Those are found a block
  of rows at a time on every shard, under (distance, row-in-block) keys
  of 32 bits, and merged on the host under 64-bit (distance, row) keys;
- rows are gathered on the chip that owns them, each a column of x.T
  sliced where it lies (a gather of rows would first copy the whole shard
  into rows-major order).
"""
from __future__ import annotations

from functools import lru_cache
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

import reference as base

HASH_BLOCK = 1 << 18      # rows a launch in the hash
SCAN_BLOCK = 1 << 21      # rows a launch in the distance pass
QUERY_BLOCK = 64          # queries a launch in the distance pass
KEY_BITS = 25             # row-in-block bits of a 32-bit (distance, row) key


def _mesh_of(x):
    """(mesh, axis) x is row-sharded over; a one-device mesh otherwise."""
    sh = x.sharding
    if isinstance(sh, NamedSharding) and len(sh.device_set) > 1:
        spec = tuple(sh.spec) + (None,)
        if spec[0] is not None:
            return sh.mesh, spec[0]
    return Mesh(np.asarray(sorted(x.devices(), key=lambda d: d.id)[:1]),
                ("rows",)), "rows"


def _blocks(n_local: int, blk: int):
    """(start, size) of the row blocks of a shard: whole blocks, then the
    tail."""
    blk = min(blk, n_local)
    out = [(s, blk) for s in range(0, n_local - blk + 1, blk)]
    if n_local % blk:
        out.append((n_local - n_local % blk, n_local % blk))
    return out


class Reference:
    """The configuration's hash, built once over the corpus where it lies."""

    def __init__(self, x, cfg: dict, index_seed: int,
                 rounding: str | None = None):
        idx = cfg["index"]
        if idx["tables"] != 1:
            raise ValueError("the reference compares one table")
        if idx["bits"] > 32:
            raise ValueError("the reference packs one 32-bit word per code")
        self.x = x
        self.mesh, self.axis = _mesh_of(x)
        self.shards = self.mesh.shape[self.axis]
        self.n = x.shape[0]
        if self.n % self.shards:
            raise ValueError("rows must divide over the shards")
        self.n_local = self.n // self.shards
        self.bits = idx["bits"]
        self.l = min(idx["scan_l"], self.n)
        self.rounding = rounding or cfg["precision"]["hash_operands"]
        self.u, self.v = base.projections(index_seed, x.shape[1], self.bits)
        self.codes, self.unsure = self._hash()

    # -- the corpus's codes -------------------------------------------------

    def _hash(self):
        rows = NamedSharding(self.mesh, P(self.axis))
        zeros = jax.jit(lambda: (jnp.zeros(self.n, jnp.uint32),) * 2,
                        out_shardings=(rows, rows))
        codes, unsure = zeros()
        for start, size in _blocks(self.n_local, HASH_BLOCK):
            codes, unsure = _hash_step(self.mesh, self.axis, size,
                                       self.rounding, self.bits)(
                self.x, codes, unsure, self.u, self.v, start)
        return codes, unsure

    def query_codes(self, ws):
        """As ``reference.Reference.query_codes``."""
        c, m = base.hash_rows(jnp.asarray(ws, jnp.float32), self.u, self.v,
                              self.rounding)
        full = jnp.uint32((1 << self.bits) - 1 if self.bits < 32
                          else 0xFFFFFFFF)
        return c ^ full, m

    # -- distances ----------------------------------------------------------

    def best_high(self, q, qu, k: int, unsure=None) -> np.ndarray:
        """(B, k) int64 (distance << 40 | row) keys of the k best rows by
        (high end of the distance interval, row), for query codes q with
        unsure bits qu; ``unsure`` overrides the rows' unsure bits."""
        unsure = self.unsure if unsure is None else unsure
        keys = []
        for start, size in _blocks(self.n_local, SCAN_BLOCK):
            kk = min(k, size)
            got = np.asarray(_best_step(self.mesh, self.axis, size, kk)(
                self.codes, unsure, q, qu, start)).astype(np.int64)
            shard = np.arange(self.shards, dtype=np.int64)[:, None, None]
            row = shard * self.n_local + start + (got & ((1 << KEY_BITS) - 1))
            keys.append(((got >> KEY_BITS) << 40 | row).transpose(1, 0, 2)
                        .reshape(got.shape[1], -1))
        keys = np.sort(np.concatenate(keys, axis=1), axis=1)
        return keys[:, :k]

    def low_high(self, q, qu, rows: np.ndarray):
        """(low, high) ends of the distance intervals of ``rows`` (B, m),
        int64 host arrays."""
        c = self._take_codes(rows.reshape(-1)).reshape(2, *rows.shape)
        un = c[1] | np.asarray(qu)[:, None]
        diff = (c[0] ^ np.asarray(q)[:, None]) & ~un
        lo = _popcount64(diff)
        return lo, lo + _popcount64(un)

    def _take_codes(self, rows: np.ndarray) -> np.ndarray:
        m = max(8, 1 << (max(len(rows), 1) - 1).bit_length())
        pad = np.zeros(m, np.int32)
        pad[:len(rows)] = rows
        got = _take_codes_fn(self.mesh, self.axis, self.n_local)(
            self.codes, self.unsure, jnp.asarray(pad))
        return np.asarray(got)[:, :len(rows)].astype(np.uint32)

    def topl_bad(self, ws, cands: list, block: int = QUERY_BLOCK):
        """Per query: how many of its candidates no resolution of the
        unsure bits puts in the top-l (a list of the wrong length counts
        the difference too), as ``reference.Reference.topl_bad``."""
        bad = np.zeros(len(cands), np.int64)
        for s in range(0, len(cands), block):
            part = cands[s:s + block]
            q, qu = self.query_codes(ws[s:s + block])
            sets = []
            for i, c in enumerate(part):
                c = np.asarray(c, np.int64)
                ok = (c >= 0) & (c < self.n)
                bad[s + i] += abs(self.l - len(c)) + int((~ok).sum()) \
                    + (len(c) - len(np.unique(c)))
                sets.append(np.unique(c[ok]))
            width = max(1, max(len(c) for c in sets))
            best = self.best_high(q, qu, self.l + width)
            srows = np.zeros((len(part), width), np.int64)
            for i, c in enumerate(sets):
                srows[i, :len(c)] = c
            lo, _ = self.low_high(q, qu, srows)
            for i, c in enumerate(sets):
                keys = [int(d) << 40 | int(r) for d, r in
                        zip(lo[i, :len(c)], c)]
                mine = set(c.tolist())
                keys += [int(k) for k in best[i]
                         if int(k) & ((1 << 40) - 1) not in mine]
                top = sorted(keys)[:self.l]
                bad[s + i] += self.l - sum(
                    (k & ((1 << 40) - 1)) in mine for k in top)
        return bad

    # -- margins ------------------------------------------------------------

    def rows_of(self, rows: np.ndarray) -> np.ndarray:
        """The float32 rows named, gathered on the chips that own them."""
        m = max(8, 1 << (max(len(rows), 1) - 1).bit_length())
        pad = np.zeros(m, np.int32)
        pad[:len(rows)] = rows
        got = _take_rows_fn(self.mesh, self.axis, self.n_local)(
            self.x, jnp.asarray(pad))
        return np.ascontiguousarray(np.asarray(got).T[:len(rows)])

    def margins(self, w, rows: np.ndarray):
        """``margins64`` of ``rows``, gathered where they lie."""
        return base.margins64(self.rows_of(np.asarray(rows)), w)


def _popcount64(a):
    a = np.asarray(a, np.uint64)
    return np.unpackbits(a.view(np.uint8).reshape(*a.shape, 8),
                         axis=-1).sum(axis=-1).astype(np.int64)


@lru_cache(maxsize=16)
def _hash_step(mesh, axis: str, size: int, rounding: str, bits: int):
    def local(x, codes, unsure, u, v, start):
        c, m = base._hash_block(jax.lax.dynamic_slice_in_dim(x, start, size),
                                u, v, rounding=rounding, bits=bits)
        return (jax.lax.dynamic_update_slice_in_dim(codes, c, start, 0),
                jax.lax.dynamic_update_slice_in_dim(unsure, m, start, 0))

    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(axis), P(), P(), P()),
        out_specs=(P(axis), P(axis)), check_vma=False),
        donate_argnums=(1, 2))


@lru_cache(maxsize=16)
def _best_step(mesh, axis: str, size: int, k: int):
    """Per shard, the k smallest (high distance << KEY_BITS | row-in-block)
    keys of its rows [start, start + size): (shards, B, k) int32."""
    def local(codes, unsure, q, qu, start):
        c = jax.lax.dynamic_slice_in_dim(codes, start, size)
        un = jax.lax.dynamic_slice_in_dim(unsure, start, size)[None, :] \
            | qu[:, None]
        hi = base._popcount((c[None, :] ^ q[:, None]) & ~un) \
            + base._popcount(un)
        key = (hi << KEY_BITS) + jnp.arange(size, dtype=jnp.int32)[None, :]
        neg, _ = jax.lax.top_k(-key, k)
        return (-neg)[None]

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(axis), P(axis), P(), P(), P()),
        out_specs=P(axis), check_vma=False))


def _owned(rows, n_local: int, axis: str):
    local = rows - jax.lax.axis_index(axis) * n_local
    return (local >= 0) & (local < n_local), jnp.clip(local, 0, n_local - 1)


@lru_cache(maxsize=16)
def _take_codes_fn(mesh, axis: str, n_local: int):
    """(2, m) codes and unsure bits of m rows, from their owners."""
    def local(codes, unsure, rows):
        own, at = _owned(rows, n_local, axis)
        got = jnp.stack([codes[at], unsure[at]])
        return jax.lax.psum(jnp.where(own[None], got, jnp.uint32(0)), axis)

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(axis), P(axis), P()), out_specs=P(),
        check_vma=False))


@lru_cache(maxsize=16)
def _take_rows_fn(mesh, axis: str, n_local: int):
    """(d, m) float32 columns of x.T at m rows, from their owners."""
    def local(x, rows):
        own, at = _owned(rows, n_local, axis)
        xt = x.T

        def one(i, out):
            col = jax.lax.dynamic_slice(xt, (0, at[i]), (xt.shape[0], 1))
            return jax.lax.dynamic_update_slice(out, col, (0, i))

        out = jax.lax.fori_loop(0, rows.shape[0], one,
                                jnp.zeros((xt.shape[0], rows.shape[0]),
                                          x.dtype))
        return jax.lax.psum(jnp.where(own[None], out, 0.0), axis)

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(axis, None), P()), out_specs=P(),
        check_vma=False))


@jax.jit
def _control_pick(xs, ws, valid):
    """bfloat16-operand margins of the gathered (B, l, d) rows, and the
    least per query: (pick position, margin)."""
    xr = xs.astype(jnp.bfloat16).astype(jnp.float32)
    wr = ws.astype(jnp.bfloat16).astype(jnp.float32)
    m = jnp.abs(jnp.sum(xr * wr[:, None, :], axis=-1))
    m = m / jnp.maximum(jnp.linalg.norm(ws, axis=1, keepdims=True), 1e-12)
    m = jnp.where(valid, m, jnp.inf)
    at = jnp.argmin(m, axis=1)
    return at, jnp.take_along_axis(m, at[:, None], 1)[:, 0]


class Control:
    """The reference in the program's place, one precision lower: hash
    operands rounded to float8 e4m3 and re-rank operands to bfloat16, the
    top-l by plain Hamming distance.  Answers like
    ``HashQueryService.query_batch``."""

    def __init__(self, x, cfg: dict, index_seed: int):
        self.x = x
        self.ref = Reference(x, cfg, index_seed, rounding="float8_e4m3fn")
        self.no_unsure = jax.jit(
            jnp.zeros_like, out_shardings=self.ref.unsure.sharding)(
            self.ref.unsure)

    def query_batch(self, ws, mask=None):
        ws = np.atleast_2d(np.asarray(ws, np.float32))
        ref = self.ref
        q, _ = ref.query_codes(ws)
        best = ref.best_high(q, jnp.zeros_like(q), ref.l,
                             unsure=self.no_unsure)
        top = best & ((1 << 40) - 1)
        valid = np.ones(top.shape, bool) if mask is None else \
            np.asarray(mask, bool)[top]
        xs = ref.rows_of(top.reshape(-1)).reshape(*top.shape, -1)
        at, m = _control_pick(jnp.asarray(xs), jnp.asarray(ws),
                              jnp.asarray(valid))
        at, m = np.asarray(at), np.asarray(m)
        return [SimpleNamespace(index=int(top[i, at[i]]), margin=float(m[i]),
                                nonempty=bool(valid[i].any()),
                                candidates=np.sort(top[i]).astype(np.int64))
                for i in range(len(ws))]

    def stats(self) -> dict:
        return {}
