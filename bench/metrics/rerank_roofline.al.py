"""Exact-margin re-rank's share of the bandwidth roofline: the feature rows
actually re-ranked (unmasked candidates) and the hyperplanes, at 819 GB/s,
over the device time of core.search.margin_rerank_batch."""
import reduce

PATTERNS = [r"margin_rerank_batch"]


def read(ctx):
    return reduce.roofline(ctx, PATTERNS,
                           ctx["counters"].get("rerank_bytes"))
