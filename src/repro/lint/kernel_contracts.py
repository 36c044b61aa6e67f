"""Pass 2 — Pallas kernel contract checker.

Abstractly evaluates every registered kernel entrypoint over its declared
supported (block_n, W, B, l, cand_pack) space: the entrypoint's wrapper
body runs eagerly on stub operands with ``pl.pallas_call`` intercepted,
so the exact grid / BlockSpec / scratch / out_shape the kernel would
launch with are captured *without* compiling or executing the kernel.
Each captured launch is checked against the TPU tiling contract
(see /opt guides + kernels/README.md invariants table):

- ``index-map-arity`` — every BlockSpec index map takes exactly
  ``len(grid)`` arguments.
- ``block-shape-divides`` — block dims divide the (padded) operand dims:
  the repo's contract is full blocks only, padding handled by ops.py.
- ``block-out-of-bounds`` — the corner grid step's block must stay
  inside the array.
- ``sublane-misaligned`` / ``lane-misaligned`` — the trailing two block
  dims obey the (8, 128) f32/i32 tile quantum: sublane % 8 (or the full
  dim, or 1 for degenerate row blocks), lane % 128 or the full dim.
- ``vmem-over-budget`` — double-buffered operand blocks plus scratch
  must fit ``VMEM_BUDGET_BYTES`` (16 MB/core).
- ``sentinel-collision`` / ``sentinel-over-strict`` — the static
  companion to ``cand_encoding``'s runtime ValueErrors: for every
  (pack, W, block_n) point, a real distance (≤ 32·W) or block-local id
  (≤ block_n − 1) must never collide with the pack's sentinel encoding;
  the entrypoint must refuse exactly the illegal points.  The legality
  predicate here is computed independently so a regression in
  ``cand_encoding`` itself is caught.

Sweep points are cheap (no kernel runs), so the space errs on the broad
side; it includes the uint8 ceiling (W = 7 → 224 < 255 legal,
W = 8 → 256 illegal) and a bigger-than-VMEM code table.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math

import numpy as np

from repro.lint.findings import Finding, SEVERITY_REPORT

# 16 MB/core budget; mirrored by kernels.hamming.VMEM_BUDGET_BYTES (the
# runtime constant the traffic models use) — keep in sync.
VMEM_BUDGET_BYTES = 16 * 2 ** 20
SUBLANE = 8
LANE = 128

# Independent sentinel ceilings (do NOT import from kernels.hamming: the
# whole point is to catch a regression there).  A narrow pack is legal iff
# the largest real distance 32·W sits strictly below the distance sentinel
# and block-local ids fit the int16 id channel.
_PACK_DIST_SENTINEL = {"16": 2 ** 15 - 1, "8": 2 ** 8 - 1}
_PACK_ID_MAX = 2 ** 15 - 1


def pack_is_legal(pack: str, w: int, block_n: int) -> bool:
    if pack == "none":
        return True
    return 32 * w < _PACK_DIST_SENTINEL[pack] and \
        block_n - 1 <= _PACK_ID_MAX


@dataclasses.dataclass
class Launch:
    grid: tuple
    in_specs: list
    out_specs: list
    out_shape: list
    scratch_shapes: list
    operands: tuple


@dataclasses.dataclass
class Case:
    case_id: str
    kwargs: dict
    make_operands: object           # () -> tuple of jnp arrays
    legal: bool = True              # sentinel legality expectation


@dataclasses.dataclass
class KernelContract:
    name: str                       # e.g. "kernels/hamming.py:hamming_topk_hist_kernel"
    fn: object                      # the (jitted) entrypoint
    cases: object                   # () -> iterable of Case


def _aslist(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


@contextlib.contextmanager
def record_launches():
    """Patch pl.pallas_call so wrapper bodies run eagerly and every launch
    is captured instead of compiled."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    captured = []
    real = pl.pallas_call

    def fake_pallas_call(kernel, *, grid=None, in_specs=None, out_specs=None,
                         out_shape=None, scratch_shapes=None, **kw):
        def runner(*operands):
            captured.append(Launch(
                grid=tuple(grid) if grid is not None else (),
                in_specs=_aslist(in_specs), out_specs=_aslist(out_specs),
                out_shape=_aslist(out_shape),
                scratch_shapes=_aslist(scratch_shapes), operands=operands))
            outs = [jnp.zeros(s.shape, s.dtype) for s in _aslist(out_shape)]
            if isinstance(out_shape, (list, tuple)):
                return type(out_shape)(outs)
            return outs[0]
        return runner

    pl.pallas_call = fake_pallas_call
    try:
        yield captured
    finally:
        pl.pallas_call = real


def _unjit(fn):
    return getattr(fn, "__wrapped__", fn)


def check_launch(launch: Launch, where: str, case_id: str) -> list:
    findings = []

    def finding(rule, msg, key):
        findings.append(Finding(
            "kernel_contract", rule, where.split(":")[0],
            where.split(":")[1], key=f"{case_id}:{key}", message=msg))

    vmem = 0
    pairs = list(zip(launch.in_specs, launch.operands)) + \
        list(zip(launch.out_specs, launch.out_shape))
    corner = tuple(g - 1 for g in launch.grid)
    for which, (spec, arr) in enumerate(pairs):
        block = getattr(spec, "block_shape", None)
        if block is None:
            continue            # memory_space=ANY / manual DMA operand
        block = tuple(block)
        shape = tuple(arr.shape)
        itemsize = arr.dtype.itemsize
        vmem += 2 * math.prod(block) * itemsize     # pipeline double buffer
        index_map = getattr(spec, "index_map", None)
        idx = None
        if index_map is not None:
            try:
                idx = index_map(*corner)
            except TypeError:
                finding("index-map-arity",
                        f"[{case_id}] operand {which}: index map arity != "
                        f"grid rank {len(launch.grid)}", f"arity:{which}")
        if len(block) != len(shape):
            finding("block-rank-mismatch",
                    f"[{case_id}] operand {which}: block rank {len(block)} "
                    f"vs array rank {len(shape)}", f"rank:{which}")
            continue
        for d, (bs, dim) in enumerate(zip(block, shape)):
            if bs is None:
                continue
            if dim % bs != 0:
                finding("block-shape-divides",
                        f"[{case_id}] operand {which} dim {d}: block {bs} "
                        f"does not divide padded dim {dim} (partial blocks "
                        f"violate the full-block contract; pad in the "
                        f"wrapper)", f"div:{which}:{d}")
        if idx is not None and len(idx) == len(block):
            for d, (bs, dim) in enumerate(zip(block, shape)):
                if bs is None:
                    continue
                if (int(idx[d]) + 1) * bs > dim + (-dim) % bs:
                    finding("block-out-of-bounds",
                            f"[{case_id}] operand {which} dim {d}: corner "
                            f"grid step maps block {idx[d]} past dim {dim}",
                            f"oob:{which}:{d}")
        if len(block) >= 2:
            sub, lane = block[-2], block[-1]
            sub_full, lane_full = shape[-2], shape[-1]
            if sub is not None and not (
                    sub % SUBLANE == 0 or sub == sub_full or sub == 1):
                finding("sublane-misaligned",
                        f"[{case_id}] operand {which}: sublane block dim "
                        f"{sub} is not a multiple of {SUBLANE} nor the full "
                        f"dim {sub_full} — illegal (8, 128) tiling",
                        f"sublane:{which}")
            if lane is not None and not (
                    lane % LANE == 0 or lane == lane_full):
                finding("lane-misaligned",
                        f"[{case_id}] operand {which}: lane block dim "
                        f"{lane} is not a multiple of {LANE} nor the full "
                        f"dim {lane_full} — illegal (8, 128) tiling",
                        f"lane:{which}")

    for s in launch.scratch_shapes:
        shape = getattr(s, "shape", None)
        dtype = getattr(s, "dtype", None)
        try:
            itemsize = None if dtype is None else np.dtype(dtype).itemsize
        except TypeError:
            itemsize = None         # semaphore dtypes ('dma_sem', …)
        if shape is not None and itemsize is not None:
            vmem += math.prod(tuple(shape)) * itemsize
        else:
            vmem += 4               # semaphores: count a word, negligible
    if vmem > VMEM_BUDGET_BYTES:
        finding("vmem-over-budget",
                f"[{case_id}] working set {vmem / 2**20:.1f} MB (2x blocks "
                f"+ scratch) exceeds the {VMEM_BUDGET_BYTES // 2**20} MB "
                f"VMEM budget", "vmem")
    return findings


def check_contract(contract: KernelContract) -> list:
    findings = []
    for case in contract.cases():
        operands = case.make_operands()
        raised = None
        with record_launches() as launches:
            try:
                _unjit(contract.fn)(*operands, **case.kwargs)
            except ValueError as e:
                raised = e
        if not case.legal:
            if raised is None:
                findings.append(Finding(
                    "kernel_contract", "sentinel-collision",
                    contract.name.split(":")[0], contract.name.split(":")[1],
                    key=f"{case.case_id}:collide",
                    message=f"[{case.case_id}] illegal pack point was "
                            f"accepted: a real distance or block-local id "
                            f"collides with the narrow sentinel encoding "
                            f"(cand_encoding must refuse it)"))
            continue
        if raised is not None:
            findings.append(Finding(
                "kernel_contract", "sentinel-over-strict",
                contract.name.split(":")[0], contract.name.split(":")[1],
                key=f"{case.case_id}:strict",
                message=f"[{case.case_id}] legal sweep point refused at "
                        f"launch build time: {raised}"))
            continue
        for launch in launches:
            findings.extend(check_launch(launch, contract.name,
                                         case.case_id))
    return findings


# ---------------------------------------------------------------------------
# Registry: the repo's kernel entrypoints and their supported spaces.
# ---------------------------------------------------------------------------

def default_registry() -> list:
    import jax.numpy as jnp
    from repro.kernels import bilinear_hash as bh
    from repro.kernels import hamming as hk
    from repro.kernels import lbh_grad as lbh

    def z(shape, dtype=jnp.uint32):
        return jnp.zeros(shape, dtype)

    def distance_cases():
        for block_n in (256, 2048):
            for w in (1, 8):
                yield Case(
                    f"bn{block_n}-w{w}", dict(block_n=block_n, interpret=True),
                    lambda bn=block_n, w=w: (z((2 * bn, w)), z((w,))))

    def batch_cases():
        for block_n in (256, 2048):
            for w, b in ((1, 8), (8, 3), (8, 128)):
                yield Case(
                    f"bn{block_n}-w{w}-b{b}",
                    dict(block_n=block_n, interpret=True),
                    lambda bn=block_n, w=w, b=b: (z((2 * bn, w)), z((b, w))))

    def topk_cases(dma_values=(False,)):
        # (block_n, W, B, l, pack) space: includes the uint8 ceiling
        # (w=7 legal, w=8 illegal for pack="8"), the int16 id ceiling
        # (block_n 8192 fine, int16 ids hold block-local rows < 32768),
        # grouped launches, a live-rows mask, and a bigger-than-VMEM table.
        # Codes are lane-dense (G, W, n_pad / 128, 128), so a block that
        # does not cover the table is a multiple of 1024 rows.
        for pack in ("none", "16", "8"):
            for w in (1, 7, 8):
                for block_n, g, b, l in ((1024, 1, 8, 8), (2048, 4, 32, 128),
                                         (8192, 2, 128, 512)):
                    for dma in dma_values:
                        for masked in (False, True):
                            kw = dict(block_n=block_n, interpret=True,
                                      pack=pack)
                            if dma_values != (False,):
                                kw["dma"] = dma
                            n_pad = 2 * block_n
                            args = [z((g, w, n_pad // 128, 128)),
                                    z((g, b, w)), min(l, block_n),
                                    n_pad - 3]
                            if masked:
                                kw["active"] = z((1, n_pad), jnp.int32)
                            yield Case(
                                f"bn{block_n}-w{w}-b{b}-l{l}-{pack}"
                                f"{'-dma' if dma else ''}"
                                f"{'-mask' if masked else ''}",
                                kw, lambda a=tuple(args): a,
                                legal=pack_is_legal(pack, w, block_n))

    def bilinear_cases():
        # contract: one k-block per launch (k == block_k) — the packed out
        # lane (k // 32) is sub-128, legal only as the full dim.
        for block_n, k, block_d, n_mult, d_mult in (
                (256, 128, 512, 1, 1), (256, 128, 512, 2, 2),
                (256, 256, 512, 2, 1), (1024, 128, 512, 1, 2)):
            yield Case(
                f"bn{block_n}-k{k}-bd{block_d}-n{n_mult}-d{d_mult}",
                dict(block_n=block_n, block_k=k, block_d=block_d,
                     interpret=True),
                lambda bn=block_n, k=k, bd=block_d, nm=n_mult, dm=d_mult: (
                    z((nm * bn, dm * bd), jnp.float32),
                    z((dm * bd, k), jnp.float32),
                    z((dm * bd, k), jnp.float32)))

    def seeded_cases():
        for g, block_n, k, block_d in ((1, 256, 128, 512), (4, 256, 256, 512),
                                       (7, 1024, 128, 1024)):
            yield Case(
                f"g{g}-bn{block_n}-k{k}-bd{block_d}",
                dict(k=k, block_n=block_n, block_k=k, block_d=block_d,
                     interpret=True),
                lambda g=g, bn=block_n, k=k, bd=block_d: (
                    z((2 * bn, bd), jnp.float32), z((g, 1))))

    def lbh_cases():
        for m, block_m in ((1024, 256), (2048, 512)):
            yield Case(
                f"m{m}-bm{block_m}", dict(block_m=block_m, interpret=True),
                lambda m=m: (z((m,), jnp.float32), z((m,), jnp.float32),
                             z((m, m), jnp.float32)))

    return [
        KernelContract("src/repro/kernels/hamming.py:hamming_distance_kernel",
                       hk.hamming_distance_kernel, distance_cases),
        KernelContract(
            "src/repro/kernels/hamming.py:hamming_distance_batch_kernel",
            hk.hamming_distance_batch_kernel, batch_cases),
        KernelContract(
            "src/repro/kernels/hamming.py:hamming_topk_fused_kernel",
            hk.hamming_topk_fused_kernel, lambda: topk_cases((False,))),
        KernelContract(
            "src/repro/kernels/hamming.py:hamming_topk_hist_kernel",
            hk.hamming_topk_hist_kernel, lambda: topk_cases((False, True))),
        KernelContract(
            "src/repro/kernels/bilinear_hash.py:bilinear_hash_kernel",
            bh.bilinear_hash_kernel, bilinear_cases),
        KernelContract(
            "src/repro/kernels/bilinear_hash.py:bilinear_hash_seeded_kernel",
            bh.bilinear_hash_seeded_kernel, seeded_cases),
        KernelContract("src/repro/kernels/lbh_grad.py:lbh_chain_kernel",
                       lbh.lbh_chain_kernel, lbh_cases),
    ]


def run(modules=None, registry=None) -> tuple[list, dict]:
    """Run contract checks; with ``modules`` also report kernel
    entrypoints (functions calling pl.pallas_call) missing a contract."""
    registry = default_registry() if registry is None else registry
    findings = []
    for contract in registry:
        findings.extend(check_contract(contract))

    covered = {c.name.split(":")[1] for c in registry}
    if modules:
        import ast
        for src in modules:
            if "/kernels/" not in src.rel:
                continue
            for node in src.tree.body:
                if not isinstance(node, ast.FunctionDef):
                    continue
                calls_pallas = any(
                    isinstance(n, ast.Attribute) and n.attr == "pallas_call"
                    for n in ast.walk(node))
                if calls_pallas and node.name not in covered:
                    findings.append(Finding(
                        "kernel_contract", "unregistered-kernel", src.rel,
                        node.name, line=node.lineno,
                        severity=SEVERITY_REPORT, key=node.name,
                        message=f"kernel entrypoint {node.name} launches "
                                f"pallas_call but has no contract in "
                                f"repro.lint.kernel_contracts.default_registry"))
    meta = {"contracts": sorted(c.name for c in registry)}
    return findings, meta
