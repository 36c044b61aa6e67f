"""Pallas TPU kernel: fused bilinear hashing (the paper's hot loop).

codes = pack( sgn((X U) .* (X V)) )          X: (n, d), U, V: (d, k)

One pass produces packed uint32 codes directly:
  - the two projections run as MXU matmuls over (BN, BD) x (BD, BK) VMEM
    tiles with f32 accumulation in VMEM scratch across the d-reduction grid
    axis (innermost, "arbitrary" semantics);
  - on the last d-step the elementwise product, sign, and 32-way bit pack
    happen in-register, writing only (BN, BK/32) uint32 to HBM.

HBM traffic is n*d + 2*d*k + n*k/8 bytes — the two (n, k) f32 projection
intermediates that a composed XLA graph would round-trip never materialize.
MXU alignment: BN, BK multiples of 128 (lane dim), BD multiple of 128; the
ops.py wrapper pads inputs so edge tiles stay full.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.functions import seeded_gaussian

WORD = 32


def _kernel(x_ref, u_ref, v_ref, out_ref, acc_u, acc_v, *, n_d_steps: int):
    dstep = pl.program_id(2)

    @pl.when(dstep == 0)
    def _init():
        acc_u[...] = jnp.zeros_like(acc_u)
        acc_v[...] = jnp.zeros_like(acc_v)

    x = x_ref[...]
    acc_u[...] += jnp.dot(x, u_ref[...], preferred_element_type=jnp.float32)
    acc_v[...] += jnp.dot(x, v_ref[...], preferred_element_type=jnp.float32)

    @pl.when(dstep == n_d_steps - 1)
    def _finalize():
        out_ref[...] = _pack_sign_bits(acc_u[...] * acc_v[...])


@functools.partial(
    jax.jit,
    static_argnames=("block_n", "block_k", "block_d", "interpret"))
def bilinear_hash_kernel(x, u, v, *, block_n: int = 256, block_k: int = 128,
                         block_d: int = 512, interpret: bool = False):
    """Raw kernel call.  Preconditions (ops.py enforces by padding):
    n % block_n == 0, d % block_d == 0, k % block_k == 0, block_k % 32 == 0.
    Returns packed codes (n, k // 32) uint32."""
    n, d = x.shape
    k = u.shape[1]
    grid = (n // block_n, k // block_k, d // block_d)
    return pl.pallas_call(
        functools.partial(_kernel, n_d_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_d), lambda i, j, s: (i, s)),
            pl.BlockSpec((block_d, block_k), lambda i, j, s: (s, j)),
            pl.BlockSpec((block_d, block_k), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((block_n, block_k // WORD),
                               lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, k // WORD), jnp.uint32),
        scratch_shapes=[
            pltpu.VMEM((block_n, block_k), jnp.float32),
            pltpu.VMEM((block_n, block_k), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, u, v)


def _pack_sign_bits(prod):
    """Sign bits of a (BN, BK) tile packed 32 to a word: (BN, BK/32) uint32.

    Two MXU products with power-of-two weights build each word's low and
    high 16 bits.  Every operand (a 0/1 bit, a weight 2^i) is exact in
    bf16 and every sum is below 2^16, so the packing is exact on every
    backend; the halves are joined in int32 and bitcast, since Mosaic has
    no unsigned lane reduction."""
    bits = (prod >= 0).astype(jnp.bfloat16)        # sgn(0) = +1
    bk = bits.shape[1]
    shape = (bk, bk // WORD)
    j = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    word = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    bit = j % WORD
    mine = j // WORD == word

    def half(lo_bit):
        weight = jnp.where(mine & (bit >= lo_bit) & (bit < lo_bit + 16),
                           jnp.int32(1) << (bit - lo_bit), 0)
        return jnp.dot(bits, weight.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32).astype(jnp.int32)

    packed = half(0) | (half(16) << 16)
    return jax.lax.bitcast_convert_type(packed, jnp.uint32)


def _seeded_kernel(seed_ref, x_ref, out_ref, acc_u, acc_v, *,
                   n_d_steps: int, block_d: int, block_k: int):
    """Grid step of the seed-generated hash: identical tiling, accumulation
    order and finalize as ``_kernel``, except the (BD, BK) U/V tiles are
    regenerated in-register from this group's seed instead of being streamed
    from HBM.  The generator is indexed by ABSOLUTE (row, col) — the tile's
    values equal the matching slice of core.functions.seeded_projections, so
    the packed codes are bit-identical to the materialized kernel fed the
    oracle's U, V (pad rows of x are zero, so the garbage gaussians generated
    past the true d contribute exactly 0.0 to every accumulator lane)."""
    j, s = pl.program_id(2), pl.program_id(3)

    @pl.when(s == 0)
    def _init():
        acc_u[...] = jnp.zeros_like(acc_u)
        acc_v[...] = jnp.zeros_like(acc_v)

    seed = seed_ref[0, 0]
    rows = (jax.lax.broadcasted_iota(jnp.int32, (block_d, block_k), 0)
            + s * block_d)
    cols = (jax.lax.broadcasted_iota(jnp.int32, (block_d, block_k), 1)
            + j * block_k)
    u = seeded_gaussian(seed, 0, rows, cols)
    v = seeded_gaussian(seed, 1, rows, cols)
    x = x_ref[...]
    acc_u[...] += jnp.dot(x, u, preferred_element_type=jnp.float32)
    acc_v[...] += jnp.dot(x, v, preferred_element_type=jnp.float32)

    @pl.when(s == n_d_steps - 1)
    def _finalize():
        out_ref[0] = _pack_sign_bits(acc_u[...] * acc_v[...])


@functools.partial(
    jax.jit,
    static_argnames=("k", "block_n", "block_k", "block_d", "interpret"))
def bilinear_hash_seeded_kernel(x, seeds, *, k: int, block_n: int = 256,
                                block_k: int = 128, block_d: int = 512,
                                interpret: bool = False):
    """Grouped seed-generated hash: codes for G tables in ONE launch with
    zero projection-weight HBM reads.

    x: (n, d) f32 shared by all tables; seeds: (G, 1) uint32 per-table
    seeds.  Preconditions as ``bilinear_hash_kernel`` (ops.py pads).
    Returns (G, n, k // 32) uint32 — group g bit-identical to
    ``bilinear_hash_kernel(x, *seeded_projections(seeds[g], d, k))``.
    HBM traffic is G·(n·d·4 + n·k/8) + x re-reads — the 2·d·k·4·G weight
    stream of the materialized path never exists (hash_traffic_model in
    ops.py counts both)."""
    n, d = x.shape
    g = seeds.shape[0]
    grid = (g, n // block_n, k // block_k, d // block_d)
    return pl.pallas_call(
        functools.partial(_seeded_kernel, n_d_steps=grid[3],
                          block_d=block_d, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda t, i, j, s: (t, 0)),
            pl.BlockSpec((block_n, block_d), lambda t, i, j, s: (i, s)),
        ],
        out_specs=pl.BlockSpec((1, block_n, block_k // WORD),
                               lambda t, i, j, s: (t, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, n, k // WORD), jnp.uint32),
        scratch_shapes=[
            pltpu.VMEM((block_n, block_k), jnp.float32),
            pltpu.VMEM((block_n, block_k), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(seeds, x)
