"""Ahead-of-time compiles of the main-path Pallas kernels for a described
TPU v5e, at the Tiny-1M deployment's real widths (n = 1,060,000 rows,
d = 385, k = 20 bits, C = 10 queries).

Nothing runs: the TPU compiler builds each kernel for a chip that is
described, not attached, and refuses what the chip would refuse (tiling,
unsupported primitives, VMEM over budget) — faults the interpret-mode parity
tests cannot see.  The topology is described inside a fixture, so a host
that cannot describe it skips these tests instead of failing to collect.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import hamming, ops

N, D, K, C = 1_060_000, 385, 20, 10
SCAN_L = 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # can never be read back here; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _scan_shapes(w: int, masked: bool):
    """The operand shapes ops.hamming_topk_grouped hands the fused kernels
    for one table and a C-query batch: lane-dense codes."""
    b = -(-C // ops.SUBLANE) * ops.SUBLANE
    bn = ops._block_rows(N, 4096, b)
    n_pad = -(-N // bn) * bn
    shapes = [((1, w, n_pad // 128, 128), jnp.uint32),
              ((1, b, w), jnp.uint32)]
    if masked:
        shapes.append(((1, n_pad), jnp.int32))
    return bn, shapes


def _hist(dma=False):
    def kernel(bn, codes, queries, active=None):
        return hamming.hamming_topk_hist_kernel(
            codes, queries, SCAN_L, N, active=active, block_n=bn, dma=dma)
    return kernel


def _argmin(bn, codes, queries, active=None):
    return hamming.hamming_topk_fused_kernel(codes, queries, SCAN_L, N,
                                             active=active, block_n=bn)


SCANS = {
    "hist-w1": (_hist(), 1, False),
    "hist-w1-masked": (_hist(), 1, True),
    "hist-w4": (_hist(), 4, False),
    "hist-w4-masked": (_hist(), 4, True),
    "argmin-w1-masked": (_argmin, 1, True),
    "hist-dma-w1-masked": (_hist(dma=True), 1, True),
}

HASHES = {
    "seeded-d385-k20": (
        lambda x, seeds: ops.bilinear_hash_seeded_grouped(
            x, seeds, K, interpret=False),
        [((N, D), jnp.float32), ((1,), jnp.uint32)]),
    "materialized-d385-k20": (
        lambda x, u, v: ops.bilinear_hash(x, u, v, interpret=False),
        [((N, D), jnp.float32), ((D, K), jnp.float32),
         ((D, K), jnp.float32)]),
    "materialized-d64-k128": (
        lambda x, u, v: ops.bilinear_hash(x, u, v, interpret=False),
        [((N, 64), jnp.float32), ((64, 128), jnp.float32),
         ((64, 128), jnp.float32)]),
}


def _compiled_text(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("case", sorted(HASHES))
def test_hash_kernel_compiles_for_v5e(case, one_chip):
    fn, shapes = HASHES[case]
    assert "tpu_custom_call" in _compiled_text(fn, shapes, one_chip)


@pytest.mark.parametrize("case", sorted(SCANS))
def test_scan_kernel_compiles_for_v5e(case, one_chip):
    kernel, w, masked = SCANS[case]
    bn, shapes = _scan_shapes(w, masked)
    text = _compiled_text(lambda *a: kernel(bn, *a), shapes, one_chip)
    assert "tpu_custom_call" in text
