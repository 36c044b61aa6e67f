"""Per-shard exact re-rank's share of the bandwidth roofline: the feature
rows re-ranked (unmasked candidates) and the hyperplanes, at 819 GB/s a
chip, over the device time of the ``sharded_rerank`` program summed over
chips (core.search.sharded_rerank: each chip's gather of the rows it owns,
the margins and the cross-chip min)."""
import reduce

PATTERNS = [r"sharded_rerank"]


def read(ctx):
    return reduce.roofline(ctx, PATTERNS,
                           ctx["counters"].get("rerank_bytes"))
