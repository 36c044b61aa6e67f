"""Sharded Hamming scan's share of the bandwidth roofline: the algorithm's
bytes of the whole scan (``scan_bytes``: every chip's codes, the mask,
query codes, l results per query) at 819 GB/s a chip, over the device time
of the ``sharded_scan`` program summed over chips
(core.search.sharded_topk_lanes: the local kernel launch, the all-gather
and the merge)."""
import reduce

PATTERNS = [r"sharded_scan"]


def read(ctx):
    return reduce.roofline(ctx, PATTERNS, ctx["counters"].get("scan_bytes"))
