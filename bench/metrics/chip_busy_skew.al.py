"""How much busier the busiest chip is than the mean chip in the window,
in percent: 100 x (max busy / mean busy - 1), busy being the union of a
chip's device-op intervals.  None with fewer than two chips."""
import reduce


def read(ctx):
    events, lo, hi = ctx["events"], ctx["lo"], ctx["hi"]
    planes = sorted({e[3] for e in events["ops"] + events["modules"]})
    if len(planes) < 2:
        return None
    busy = [sum(e - s for s, e in reduce.busy(
        {k: [v for v in events[k] if v[3] == p] for k in ("ops", "modules")},
        lo, hi)) for p in planes]
    mean = sum(busy) / len(busy)
    return 100.0 * (max(busy) / mean - 1.0) if mean > 0 else None
