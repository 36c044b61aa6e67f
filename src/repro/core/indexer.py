"""High-level index API tying together hash families, learning, tables and
device-side scans — plus the activation indexer that attaches the paper's
technique to any model-zoo backbone.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import functions as F
from repro.core import learning as L
from repro.core.search import env_use_kernels, hamming_topk, margin_rerank
from repro.core.tables import SingleHashTable


@dataclasses.dataclass
class IndexConfig:
    method: str = "lbh"            # ah | eh | bh | lbh
    bits: int = 20                 # total bits (AH uses bit pairs; keep even)
    radius: int = 4                # Hamming-ball probe radius
    seed: int = 0
    rerank: bool = True            # exact-margin re-rank of candidates
    max_candidates: int = 4096
    # escalate the probe radius until at least this many candidates are in
    # hand (None = fixed radius, the seed behaviour); restores re-rank
    # quality when the radius-`radius` ball around the query key is sparse
    min_candidates: int | None = 64
    # serving knobs (serving.MultiTableIndex / HashQueryService)
    tables: int = 1                # number of independent hash tables L
    batch: int = 32                # micro-batch size for the query service
    # auto-compact the multi-table index once this fraction of rows is
    # tombstoned (None = never; delete churn then grows tables forever)
    compact_threshold: float | None = 0.5
    # LSM delta-index knobs (serving.lsm.LSMMultiTableIndex): streaming
    # ingest splits the index into an immutable device-resident base plus
    # a small mutable delta absorbing inserts, folded back incrementally.
    lsm_step_rows: int = 4096          # max source rows folded per
                                       # incremental compaction step (the
                                       # bounded-pause unit)
    lsm_delta_threshold: float = 0.5   # begin folding once the delta
                                       # exceeds this fraction of the base…
    lsm_delta_min: int = 1024          # …and at least this many rows
                                       # (avoids thrashing tiny indexes)
    lsm_delta_fused_rows: int = 4096   # delta scans stay pure-jnp below
                                       # this many rows; past it they route
                                       # through the fused kernel like the
                                       # base (see kernels/README.md)
    lsm_auto: bool = True              # piggyback compaction begin/step on
                                       # insert/delete/query calls (False =
                                       # only compact()/start_compactor())
    # LBH learning
    lbh_sample: int = 1000
    lbh_steps: int = 150
    lbh_lr: float = 0.03
    # EH dimension-sampling trick (paper §5.2); None = exact d^2 embedding
    eh_sample_dims: int | None = None
    # route hashing/scans through the Pallas kernels; the default follows
    # the platform (on where the backend is a TPU) and the REPRO_USE_KERNELS
    # env var overrides it (CI runs a leg with each value)
    use_kernels: bool = dataclasses.field(default_factory=env_use_kernels)
    # fused-scan selection algorithm: "hist" (counting-sort select, cheap
    # at any scan depth l) or "argmin" (legacy l-round masked argmin — the
    # escape hatch).  None honours the REPRO_FUSED_SELECT env var (default
    # hist).  Bit-identical results either way; deep scans (l in the
    # hundreds, for recall) are only cheap under "hist".
    fused_select: str | None = None
    # method="bh": derive the random bilinear factors from a 32-bit
    # per-table seed (functions.SeededBHHash) so the kernel path hashes
    # with ZERO projection-weight HBM reads — growing the table count L is
    # then free on the hash side (see ops.hash_traffic_model).  False
    # restores the classic jax.random.normal sampling.  Learned factors
    # (method="lbh") always stay materialized.
    seeded_projections: bool = True
    # fused-scan candidate emission width: "16" (int16 pairs, half the
    # candidate HBM/interconnect bytes), "8" (uint8 distances, k <= 224),
    # or "none" (int32 escape hatch).  None honours REPRO_CAND_PACK
    # (default 16).  Bit-identical results for every width.
    cand_pack: str | None = None
    # Online refresh (serving.refresh.RefreshManager over the LSM index):
    # periodically re-learn the bilinear projections from the accumulated
    # live rows and atomically swap the rebuilt codes/tables in under
    # traffic.  refresh_method is the family the re-learn produces (the
    # paper's point is "lbh" — learned, warm-started at BH; reuses
    # lbh_sample/lbh_steps/lbh_lr).  refresh_ingest_rows arms the service's
    # auto policy: a background refresh starts once that many rows were
    # inserted since the last one (None = manual refresh() only).
    # refresh_traffic_sample weights the learning sample toward rows with
    # small margin to recently served query hyperplanes (the traffic-aware
    # variant; False keeps the seeded uniform subsample).
    refresh_method: str = "lbh"
    refresh_ingest_rows: int | None = None
    refresh_traffic_sample: bool = False


@dataclasses.dataclass
class QueryResult:
    index: int                    # argmin-margin candidate (or -1)
    margin: float
    candidates: np.ndarray        # short-list scanned
    nonempty: bool                # did the hash lookup return anything?


class HyperplaneIndex:
    """Point-to-hyperplane search index (single table, compact codes)."""

    def __init__(self, config: IndexConfig):
        self.config = config
        self.family = None
        self.table: SingleHashTable | None = None
        self.codes = None          # packed (n, W) uint32, device
        self.x = None              # (n, d) database, device
        self.fit_s = 0.0

    # -- build ---------------------------------------------------------------
    def fit(self, x, learn_key=None) -> "HyperplaneIndex":
        cfg = self.config
        t0 = time.perf_counter()
        x = jnp.asarray(x, jnp.float32)
        key = jax.random.PRNGKey(cfg.seed) if learn_key is None else learn_key
        d = x.shape[1]
        if cfg.method == "ah":
            self.family = F.AHHash.create(key, d, cfg.bits)
        elif cfg.method == "eh":
            self.family = F.EHHash.create(key, d, cfg.bits,
                                          sample_dims=cfg.eh_sample_dims)
        elif cfg.method == "bh":
            fam = F.SeededBHHash if cfg.seeded_projections else F.BHHash
            self.family = fam.create(key, d, cfg.bits)
        elif cfg.method == "lbh":
            m = min(cfg.lbh_sample, x.shape[0])
            sel = jax.random.choice(jax.random.fold_in(key, 1), x.shape[0],
                                    (m,), replace=False)
            res = L.learn_lbh(key, x[sel], cfg.bits, x_all=x,
                              steps=cfg.lbh_steps, lr=cfg.lbh_lr)
            self.family = res.family
            self.learn_result = res
        else:
            raise ValueError(f"unknown method {cfg.method!r}")

        self.x = x
        self.codes = self._hash_database(x)
        self.table = SingleHashTable(np.asarray(self.codes), cfg.bits)
        self.fit_s = time.perf_counter() - t0
        return self

    def _hash_database(self, x):
        cfg = self.config
        if cfg.use_kernels and cfg.method in ("bh", "lbh"):
            from repro.kernels import ops
            if type(self.family) is F.SeededBHHash:
                # seed-generated factors: zero projection-weight HBM reads
                return ops.bilinear_hash_seeded(x, self.family.seed,
                                                self.family.k)
            return ops.bilinear_hash(x, self.family.u, self.family.v)
        return self.family.hash_database(x)

    # -- query ---------------------------------------------------------------
    def query(self, w) -> QueryResult:
        """Paper query path: flip-code table lookup + exact-margin re-rank."""
        cfg = self.config
        w = jnp.asarray(w, jnp.float32)
        qcode = np.asarray(self.family.hash_query(w[None, :]))[0]
        cand = self.table.lookup(qcode, cfg.radius, cfg.max_candidates,
                                 cfg.min_candidates)
        if cand.size == 0:
            return QueryResult(-1, float("inf"), cand, False)
        if cfg.rerank:
            margins, ids = margin_rerank(self.x, w, jnp.asarray(cand), 1)
            idx, margin = int(ids[0]), float(margins[0])
        else:
            idx, margin = int(cand[0]), float("nan")
        return QueryResult(idx, margin, cand, True)

    def query_scan(self, w, l: int = 16):
        """Device-side scan path (no table): top-l by Hamming distance, then
        exact re-rank.  This is the path that shards to many nodes
        (core.search.hamming_topk_sharded) and that kernels/hamming.py
        accelerates on TPU."""
        w = jnp.asarray(w, jnp.float32)
        qcode = self.family.hash_query(w[None, :])[0]
        if self.config.use_kernels:
            from repro.kernels import ops
            _, idx = ops.hamming_topk(self.codes, qcode, l,
                                      pack=self.config.cand_pack)
        else:
            _, idx = hamming_topk(self.codes, qcode, l)
        # l > n slots carry id -1 and always sit at the sorted tail — slice
        # them off before the re-rank gather (x[-1] would silently alias the
        # last row)
        margins, ids = margin_rerank(
            self.x, w, idx[:min(l, self.codes.shape[0])], 1)
        return int(ids[0]), float(margins[0])


# ---------------------------------------------------------------------------
# Activation indexer: the paper's AL pipeline with an LM as feature extractor
# ---------------------------------------------------------------------------

class ActivationIndexer:
    """Builds a HyperplaneIndex over pooled backbone activations.

    embed_fn(batch) -> (B, d) pooled embeddings (e.g. mean of final hidden
    states).  Margin-based selection against a linear probe then identifies
    the most informative unlabeled items for fine-tuning (the paper's active
    learning, with the backbone as the representation).
    """

    def __init__(self, embed_fn, config: IndexConfig, batch_size: int = 64):
        self.embed_fn = embed_fn
        self.config = config
        self.batch_size = batch_size
        self.index: HyperplaneIndex | None = None
        self.embeddings = None

    def build(self, corpus) -> HyperplaneIndex:
        outs = []
        n = corpus.shape[0]
        for s in range(0, n, self.batch_size):
            outs.append(self.embed_fn(corpus[s:s + self.batch_size]))
        self.embeddings = jnp.concatenate(outs, axis=0)
        self.index = HyperplaneIndex(self.config).fit(self.embeddings)
        return self.index
