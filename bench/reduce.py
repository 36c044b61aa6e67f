"""Reduction from a profiler trace to the numbers the per-layer metrics
read: device busy time as a union of intervals, device time by event-name
pattern, program launches, the heaviest device operations and the longest
idle gaps by what the host was doing.

A trace is first cut down to plain event lists (``extract``), so the
reduction can be tested on a small committed trace without the profiler.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import heapq
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
WINDOW_SPAN = "bench.window"


def extract(xplane_path: str) -> dict:
    """Events of one ``.xplane.pb`` as {"ops", "modules", "host"} lists of
    [name, start_ns, end_ns, where]: ``ops`` and ``modules`` from each TPU
    plane's "XLA Ops" and "XLA Modules" lines (``where`` = plane), ``host``
    from every host thread (``where`` = thread)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    out = {"ops": [], "modules": [], "host": []}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    out[key] += [[e.name, e.start_ns,
                                  e.start_ns + e.duration_ns, plane.name]
                                 for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [[e.name, e.start_ns,
                                 e.start_ns + e.duration_ns, line.name]
                                for e in line.events]
    return out


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def save(events: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def window(events: dict) -> tuple[float, float]:
    """[start, end] of the benchmark's window span, in ns."""
    spans = [e for e in events["host"] if e[0] == WINDOW_SPAN]
    if len(spans) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, "
                           f"found {len(spans)}")
    return spans[0][1], spans[0][2]


def clip(evts, lo: float, hi: float):
    return [[e[0], max(e[1], lo), min(e[2], hi), e[3]]
            for e in evts if e[2] > lo and e[1] < hi]


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted [start, end] intervals."""
    out: list[list[float]] = []
    for s, e in sorted((i[0], i[1]) for i in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy(events: dict, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of the device-op intervals in [lo, hi] (modules where a
    plane records no ops)."""
    evts = events["ops"] or events["modules"]
    return union((e[1], e[2]) for e in clip(evts, lo, hi))


def chips(events: dict) -> int:
    return max(1, len({e[3] for e in events["ops"] + events["modules"]}))


def matching(evts, patterns) -> list:
    rx = [re.compile(p) for p in patterns]
    return [e for e in evts if any(r.search(e[0]) for r in rx)]


def module_time_s(events: dict, patterns, lo: float, hi: float) -> float:
    """Device seconds of the program launches whose name matches one of
    ``patterns``, summed over chips."""
    return sum(e[2] - e[1] for e in
               matching(clip(events["modules"], lo, hi), patterns)) * 1e-9


def launches(events: dict, lo: float, hi: float) -> int:
    """Program launches that started in [lo, hi]."""
    return sum(lo <= e[1] < hi for e in events["modules"])


def _base(name: str) -> str:
    """A launch or op name without its program id, and an HLO op as its
    instruction name and result shape ("%copy.10 = f32[8,4]{1,0} copy(..."
    -> "copy.10 f32[8,4]"; a tuple result shows its first shape)."""
    name = re.sub(r"\(\d+\)$", "", name)
    if " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    shape = re.search(r"[a-z]+\d*\[[\d,]*\]", rhs)
    return f"{lhs.lstrip('%')} {shape.group(0) if shape else ''}".strip()


def top_ops(events: dict, lo: float, hi: float, k: int = 10):
    """[[module/op name, seconds], ...]: the k heaviest device operations,
    by total time per name, each op named with the launch it ran in."""
    mods = sorted(clip(events["modules"], lo, hi), key=lambda e: e[1])
    starts = [m[1] for m in mods]
    tot: dict[str, float] = {}
    for e in clip(events["ops"], lo, hi):
        i = bisect.bisect_right(starts, e[1]) - 1
        mod = _base(mods[i][0]) if i >= 0 and mods[i][2] >= e[2] else "?"
        name = f"{mod}/{_base(e[0])}"
        tot[name] = tot.get(name, 0.0) + (e[2] - e[1]) * 1e-9
    return sorted(([n, s] for n, s in tot.items()), key=lambda r: -r[1])[:k]


def idle_gaps(events: dict, lo: float, hi: float, k: int = 10):
    """[[host activity, seconds], ...]: device idle time in [lo, hi]
    summed by the innermost (shortest) host event that covers each gap's
    midpoint ("bench.*" spans are the benchmark's own)."""
    spans = busy(events, lo, hi)
    edges = [lo] + [x for s in spans for x in s] + [hi]
    gaps = sorted((0.5 * (s + e), e - s)
                  for s, e in zip(edges[0::2], edges[1::2]) if e > s)
    host = sorted((h for h in clip(events["host"], lo, hi)
                   if h[0] != WINDOW_SPAN), key=lambda h: h[1])
    heap: list = []
    i = 0
    tot: dict[str, float] = {}
    for mid, length in gaps:
        while i < len(host) and host[i][1] <= mid:
            h = host[i]
            heapq.heappush(heap, (h[2] - h[1], h[2], i, h[0]))
            i += 1
        while heap and heap[0][1] < mid:     # ended before this gap
            heapq.heappop(heap)
        name = heap[0][3] if heap else "idle"
        tot[name] = tot.get(name, 0.0) + length * 1e-9
    return sorted(([n, v] for n, v in tot.items()), key=lambda r: -r[1])[:k]


def context(events: dict, counters: dict, peaks: dict) -> dict:
    """What a per-layer metric reader gets: the window, busy seconds per
    chip, the events, the run's counters (with the algorithmic bytes of
    its work) and the device's peaks."""
    lo, hi = window(events)
    n_chips = chips(events)
    planes = {e[3] for e in events["ops"] + events["modules"]}
    busy_s = sum(e - s for p in planes for s, e in busy(
        {k: [v for v in events[k] if v[3] == p] for k in ("ops", "modules")},
        lo, hi)) * 1e-9
    return {"events": events, "lo": lo, "hi": hi,
            "window_s": (hi - lo) * 1e-9, "busy_s": busy_s / n_chips,
            "chips": n_chips, "counters": counters, "peaks": peaks}


def idle_share(ctx: dict) -> float | None:
    """Percent of the window in which no operation ran on the device."""
    if ctx["busy_s"] <= 0 or ctx["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])


def roofline(ctx: dict, patterns, nbytes: float) -> float | None:
    """Percent of the bandwidth roofline: the least time ``nbytes`` take
    at peak HBM bandwidth over the device time of the matching launches.
    None where no launch matches."""
    t = module_time_s(ctx["events"], patterns, ctx["lo"], ctx["hi"])
    if t <= 0 or not nbytes:
        return None
    return 100.0 * nbytes / ctx["peaks"]["hbm_bytes_per_s"] / t
