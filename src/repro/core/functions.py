"""Randomized hyperplane hash-function families: AH, EH, BH (paper §3).

All families share a convention:

- ``hash_database(X)``: codes for database *points* (rows of X).
- ``hash_query(W)``:    codes for hyperplane *normals* (rows of W), with the
  query-side sign conventions of the paper (AH: [sgn(u.w), sgn(-v.w)];
  EH/BH: h(P_w) = -h(w)).

Sign codes are int8 in {-1, +1}; ``sgn(0) = +1`` throughout (measure-zero
under the Gaussian draws, but it keeps packing deterministic).

BH-Hash (the paper's contribution, eq. 6/7):
    h(z) = sgn(u^T z z^T v) = sgn((u.z)(v.z))
i.e. the XNOR of the two AH bits — one bit per (u, v) pair instead of two.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.utils.bits import pack_signs, flip_packed


def _sgn(x):
    """sign with sgn(0) = +1, as int8."""
    return jnp.where(x >= 0, 1, -1).astype(jnp.int8)


# ---------------------------------------------------------------------------
# Seed-generated projections: deterministic counter-based N(0, 1)
# ---------------------------------------------------------------------------
#
# The bilinear factorization makes the projections cheap enough to regenerate
# on the fly: instead of streaming materialized (d, k) factors from HBM on
# every hash launch, the Pallas kernel re-derives U/V values in-register from
# a 32-bit per-table seed.  The generator is COUNTER-based (a murmur3-style
# finalizer chain over the absolute (row, col) indices, then Box-Muller):
# the value at (seed, tag, row, col) never depends on tiling, padding,
# backend, or evaluation order, so the kernel and the pure-jnp oracle below
# are bit-identical by construction.  This is deliberately NOT the hardware
# TPU PRNG (pltpu.prng_random_bits): the hardware stream cannot be reproduced
# by a jnp oracle, and the repo's parity contract (every CI leg bit-identical
# in interpret mode) is load-bearing for the serving tests.

_GOLD = 0x9E3779B9       # 2^32 / golden ratio — per-matrix seed spacing
_FNV = 0x01000193        # FNV prime — decorrelates the row counter pre-mix


def _fmix32(h):
    """murmur3 32-bit finalizer: a full-avalanche mix on uint32 lanes
    (every elementwise op here exists on the TPU VPU and in interpret)."""
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> jnp.uint32(16))
    return h


def seeded_gaussian(seed, tag: int, rows, cols):
    """Deterministic N(0, 1) f32 values at absolute (row, col) positions.

    seed: uint32 scalar (python int or traced); tag: which matrix of the
    family (0 = U, 1 = V); rows/cols: broadcastable int32 index arrays.
    Two decorrelated uniform streams feed one Box-Muller branch; uniforms
    are mapped to (0, 1) as (bits>>8 + 0.5) * 2^-24, so log never sees 0.
    """
    s = _fmix32(jnp.uint32(seed) + jnp.uint32(tag) * jnp.uint32(_GOLD))
    h = _fmix32(s ^ (rows.astype(jnp.uint32) * jnp.uint32(_FNV)))
    h = _fmix32(h ^ cols.astype(jnp.uint32))
    b1 = _fmix32(h ^ jnp.uint32(0x632BE59B))
    b2 = _fmix32(h ^ jnp.uint32(0x2545F491))
    # the top 24 bits fit int32, and Mosaic converts only signed ints
    u1 = ((b1 >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)
          + jnp.float32(0.5)) * jnp.float32(2.0 ** -24)
    u2 = ((b2 >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)
          + jnp.float32(0.5)) * jnp.float32(2.0 ** -24)
    r = jnp.sqrt(jnp.float32(-2.0) * jnp.log(u1))
    return (r * jnp.cos(jnp.float32(2.0 * jnp.pi) * u2)).astype(jnp.float32)


def seeded_projections(seed, d: int, k: int):
    """Pure-jnp oracle of the in-kernel generator: the (d, k) U, V factors a
    seed denotes.  kernels.bilinear_hash.bilinear_hash_seeded_kernel computes
    exactly these values tile-by-tile from the same arithmetic, so
    ``ops.bilinear_hash(x, *seeded_projections(s, d, k))`` is bit-identical
    to ``ops.bilinear_hash_seeded(x, s, k)``."""
    rows = jnp.arange(d, dtype=jnp.int32)[:, None]
    cols = jnp.arange(k, dtype=jnp.int32)[None, :]
    return (seeded_gaussian(seed, 0, rows, cols),
            seeded_gaussian(seed, 1, rows, cols))


def seed_from_key(key) -> int:
    """Collapse a jax PRNG key to the 32-bit table seed the kernel consumes.
    Deterministic in the key, so two indexes built from the same key (e.g.
    HyperplaneIndex and MultiTableIndex table 0) derive the same family."""
    return int(jax.random.bits(key, (), jnp.uint32))


# ---------------------------------------------------------------------------
# BH-Hash (bilinear, eq. 6)
# ---------------------------------------------------------------------------

def sample_bilinear_projections(key, d: int, k: int, dtype=jnp.float32):
    """k i.i.d. pairs (u_j, v_j) ~ N(0, I_d), returned as (d, k) matrices."""
    ku, kv = jax.random.split(key)
    u = jax.random.normal(ku, (d, k), dtype)
    v = jax.random.normal(kv, (d, k), dtype)
    return u, v


def bilinear_signs(x, u, v):
    """sgn((X u_j)(X v_j)) for each point/bit.  x: (n, d); u, v: (d, k)."""
    return _sgn((x @ u) * (x @ v))


@dataclasses.dataclass(frozen=True)
class BHHash:
    """Randomized Bilinear-Hyperplane Hash family B (eq. 7)."""

    u: jax.Array  # (d, k)
    v: jax.Array  # (d, k)

    @classmethod
    def create(cls, key, d: int, k: int, dtype=jnp.float32) -> "BHHash":
        return cls(*sample_bilinear_projections(key, d, k, dtype))

    @property
    def k(self) -> int:
        return self.u.shape[1]

    def signs_database(self, x):
        return bilinear_signs(x, self.u, self.v)

    def signs_query(self, w):
        return -bilinear_signs(w, self.u, self.v)  # h(P_w) = -h(w)

    def hash_database(self, x):
        return pack_signs(self.signs_database(x))

    def hash_query(self, w):
        return pack_signs(self.signs_query(w))


@dataclasses.dataclass(frozen=True)
class SeededBHHash(BHHash):
    """BH family whose projections are seed-generated, not sampled.

    Same evaluation contract as BHHash — u/v are materialized here once at
    creation (they are small: 2·d·k floats) so every pure-jnp path, the
    probe tables, and the stacked batch-query hashing work unchanged.  The
    point of the seed is the KERNEL path: ``ops.bilinear_hash_seeded`` /
    the grouped serving hash regenerate U, V in-register from ``seed`` and
    never read projection weights from HBM, so hashing L tables streams
    only the points and the packed codes (see kernels/README.md).  Parity:
    ``u, v == seeded_projections(seed, d, k)`` exactly, and the kernel
    computes those same values tile-by-tile.
    """

    seed: int = 0

    @classmethod
    def create(cls, key, d: int, k: int, dtype=jnp.float32) -> "SeededBHHash":
        seed = seed_from_key(key)
        u, v = seeded_projections(seed, d, k)
        return cls(u.astype(dtype), v.astype(dtype), seed)


# ---------------------------------------------------------------------------
# AH-Hash (Jain et al. 2010; eq. 2) — baseline
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AHHash:
    """Angle-Hyperplane Hash: two bits per (u, v) pair.

    k here is the *total* number of bits and must be even; there are k/2
    (u, v) pairs.  The paper uses 2x the bits of BH/EH for fairness.
    """

    u: jax.Array  # (d, k//2)
    v: jax.Array  # (d, k//2)

    @classmethod
    def create(cls, key, d: int, k: int, dtype=jnp.float32) -> "AHHash":
        assert k % 2 == 0, "AH-Hash emits bit pairs; k must be even"
        return cls(*sample_bilinear_projections(key, d, k // 2, dtype))

    @property
    def k(self) -> int:
        return 2 * self.u.shape[1]

    def _interleave(self, a, b):
        # [sgn(u1.z), sgn(v1.z), sgn(u2.z), ...] per the 2-bit structure
        n, h = a.shape
        return jnp.stack([a, b], axis=-1).reshape(n, 2 * h)

    def signs_database(self, z):
        return self._interleave(_sgn(z @ self.u), _sgn(z @ self.v))

    def signs_query(self, w):
        return self._interleave(_sgn(w @ self.u), _sgn(-(w @ self.v)))

    def hash_database(self, z):
        return pack_signs(self.signs_database(z))

    def hash_query(self, w):
        return pack_signs(self.signs_query(w))


# ---------------------------------------------------------------------------
# EH-Hash (Jain et al. 2010; eq. 4) — baseline
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EHHash:
    """Embedding-Hyperplane Hash: sgn(U . vec(z z^T)).

    We keep each of the k projections as a d x d matrix M_j and evaluate
    z^T M_j z, which is the same inner product without materializing the
    d^2 embedding.  ``sample_dims`` implements the paper's dimension-sampling
    speed-up (project onto a random subset of coordinates first).
    """

    mats: jax.Array  # (k, d, d)
    dims: jax.Array | None = None  # optional (d_sub,) sampled coordinates

    @classmethod
    def create(cls, key, d: int, k: int, sample_dims: int | None = None,
               dtype=jnp.float32) -> "EHHash":
        km, kd = jax.random.split(key)
        d_eff = sample_dims or d
        mats = jax.random.normal(km, (k, d_eff, d_eff), dtype)
        dims = None
        if sample_dims is not None:
            dims = jax.random.choice(kd, d, (sample_dims,), replace=False)
        return cls(mats, dims)

    @property
    def k(self) -> int:
        return self.mats.shape[0]

    def _project(self, z):
        return z if self.dims is None else z[:, self.dims]

    def _scores(self, z):
        z = self._project(z)
        return jnp.einsum("nd,kde,ne->nk", z, self.mats, z)

    def signs_database(self, z):
        return _sgn(self._scores(z))

    def signs_query(self, w):
        return _sgn(-self._scores(w))

    def hash_database(self, z):
        return pack_signs(self.signs_database(z))

    def hash_query(self, w):
        return pack_signs(self.signs_query(w))


# ---------------------------------------------------------------------------
# Learned bilinear hash (LBH) — same bilinear form, learned projections.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LBHHash(BHHash):
    """Compact learned bilinear hashing (paper §4).

    Identical evaluation path to BHHash — only the projections differ
    (they are learned by repro.core.learning.learn_lbh).
    """


FAMILIES = {"ah": AHHash, "eh": EHHash, "bh": BHHash, "lbh": LBHHash}


def query_lookup_code(family, w):
    """Packed code to *look up* in a table built from hash_database codes.

    Searching points near the hyperplane = points whose database code is at
    maximal Hamming distance from code(w) = minimal distance from the
    query-side code (which already includes the sign flip).
    """
    return family.hash_query(w)


def flip_database_code(packed, k: int):
    """Equivalent formulation used in the paper's step (1): bitwise NOT of
    H(w) computed database-style."""
    return flip_packed(packed, k)
