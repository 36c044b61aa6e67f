"""Pallas TPU kernel: fused LBH surrogate-gradient chain (paper eq. 16-18).

Given p = X u, q = X v (MXU matmuls, left to XLA) and the residue R, the
gradient of g~(u,v) = -b~^T R b~ needs the elementwise chain

    b = tanh(p*q/2);  s = (R b) * (1 - b^2);  out = (s*q, s*p)

after which  grad_u = -X^T (s*q),  grad_v = -X^T (s*p)  (MXU again).
The kernel fuses the R matvec with the surrounding elementwise ops so the
five m-vectors (b, Rb, s, s*q, s*p) never round-trip HBM: R streams through
VMEM once (m^2 * 4 bytes — the unavoidable term), everything else stays
in registers.  Rows of R are tiled on the grid; p/q are small enough
(m <= ~8k) to sit whole in VMEM for every tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(p_ref, q_ref, pt_ref, qt_ref, r_ref, sq_ref, sp_ref):
    b = jnp.tanh(0.5 * p_ref[...] * q_ref[...])        # (1, m)
    # (R b) for this tile of rows, as a (1, BM) row: contract m.
    rb = jax.lax.dot_general(b, r_ref[...], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    p_tile, q_tile = pt_ref[...], qt_ref[...]          # (1, BM)
    b_tile = jnp.tanh(0.5 * p_tile * q_tile)
    s = rb * (1.0 - b_tile * b_tile)
    sq_ref[...] = s * q_tile
    sp_ref[...] = s * p_tile


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def lbh_chain_kernel(p, q, r, *, block_m: int = 512, interpret: bool = False):
    """p, q: (m,) f32; r: (m, m) f32 with m % block_m == 0.
    Returns (s*q, s*p), each (m,) f32."""
    m = p.shape[0]
    grid = (m // block_m,)
    sq, sp = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, m), lambda i: (0, 0)),
            pl.BlockSpec((1, m), lambda i: (0, 0)),
            pl.BlockSpec((1, block_m), lambda i: (0, i)),
            pl.BlockSpec((1, block_m), lambda i: (0, i)),
            pl.BlockSpec((block_m, m), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_m), lambda i: (0, i)),
            pl.BlockSpec((1, block_m), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, m), jnp.float32),
            jax.ShapeDtypeStruct((1, m), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(p[None, :], q[None, :], p[None, :], q[None, :], r)
    return sq[0], sp[0]
