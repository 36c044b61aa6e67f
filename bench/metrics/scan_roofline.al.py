"""Hamming scan's share of the bandwidth roofline: the algorithm's bytes
(codes, mask where masked, query codes, l results per query) at 819 GB/s
over the device time of the scan program (kernel, relayout and sort
merge: kernels.ops._topk_grouped_impl)."""
import reduce

PATTERNS = [r"_topk_grouped_impl"]


def read(ctx):
    return reduce.roofline(ctx, PATTERNS, ctx["counters"].get("scan_bytes"))
