"""The program's stage spans, read on the device trace's clock.

The program wraps each stage of a query in a host span
(``jax.profiler.TraceAnnotation``): ``repro.query`` around a whole
``HashQueryService.query_batch`` call and, inside it, ``repro.hash``,
``repro.scan``, ``repro.dedup``, ``repro.mask``, ``repro.rerank``, one
``repro.fetch`` per blocking device-to-host read, and ``repro.results``.
``reduce.extract`` keeps them with every other host event, so they share
one clock with the device's busy intervals.  A trace without them (a
program that has no spans) reads ``None``, never 0.

    python bench/spans.py <events saved by bench/run.py --save-trace>

prints the stage table: for each span name, per round, the calls, the
total and self ms (self = the span less the part its child spans cover),
the ms in which the device was idle, and the program launches begun inside
the span (``PJRT_LoadedExecutable_Execute`` host events on its thread).
"""
from __future__ import annotations

import bisect
import sys

import reduce

PREFIX = "repro."
LAUNCH = "PJRT_LoadedExecutable_Execute"
ORDER = ("repro.query", "repro.hash", "repro.scan", "repro.dedup",
         "repro.mask", "repro.rerank", "repro.fetch", "repro.results")


def named(events: dict, name: str, lo: float, hi: float) -> list:
    """The ``name`` spans in [lo, hi], clipped to it."""
    return reduce.clip([e for e in events["host"] if e[0] == name], lo, hi)


def overlap_ns(a, b) -> float:
    """Length covered by both of two merged, sorted interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_ns(spans, busy) -> float:
    """Time inside the union of ``spans`` in which the device was idle
    (``busy``: merged, sorted device intervals)."""
    u = reduce.union((e[1], e[2]) for e in spans)
    return sum(e - s for s, e in u) - overlap_ns(u, busy)


def exposed_ms(ctx: dict, name: str) -> float | None:
    """Device-idle ms per round inside the ``name`` spans of the window."""
    rounds = ctx["counters"].get("rounds")
    spans = named(ctx["events"], name, ctx["lo"], ctx["hi"])
    if not spans or not rounds:
        return None
    busy = reduce.busy(ctx["events"], ctx["lo"], ctx["hi"])
    return 1e-6 * idle_ns(spans, busy) / rounds


def calls_per_round(ctx: dict, name: str) -> float | None:
    """``name`` spans that start in the window, per round."""
    rounds = ctx["counters"].get("rounds")
    n = sum(ctx["lo"] <= e[1] < ctx["hi"]
            for e in ctx["events"]["host"] if e[0] == name)
    return n / rounds if rounds and n else None


def self_ns(spans) -> list[float]:
    """Each span's duration less its direct children's, children being
    the spans nested in it on the same thread (host spans of one thread
    nest properly)."""
    out = [e[2] - e[1] for e in spans]
    by_thread: dict = {}
    for i, e in enumerate(spans):
        by_thread.setdefault(e[3], []).append(i)
    for idx in by_thread.values():
        idx.sort(key=lambda i: (spans[i][1], -spans[i][2]))
        stack: list[int] = []
        for i in idx:
            while stack and spans[stack[-1]][2] <= spans[i][1]:
                stack.pop()
            if stack and spans[i][2] <= spans[stack[-1]][2]:
                out[stack[-1]] -= spans[i][2] - spans[i][1]
            stack.append(i)
    return out


def table(events: dict, rounds: int) -> list[dict]:
    """One row per ``repro.*`` span name in the window, every number per
    round: calls, total_ms, self_ms, idle_ms, launches."""
    lo, hi = reduce.window(events)
    busy = reduce.busy(events, lo, hi)
    spans = [e for e in events["host"]
             if e[0].startswith(PREFIX) and lo <= e[1] < hi]
    starts: dict = {}
    for e in events["host"]:
        if e[0].startswith(LAUNCH) and lo <= e[1] < hi:
            starts.setdefault(e[3], []).append(e[1])
    for v in starts.values():
        v.sort()

    def launched(e):
        at = starts.get(e[3], [])
        return bisect.bisect_left(at, e[2]) - bisect.bisect_left(at, e[1])

    by_name: dict = {}
    for e, own in zip(spans, self_ns(spans)):
        by_name.setdefault(e[0], []).append((e, own))
    rank = lambda n: (ORDER.index(n) if n in ORDER else len(ORDER), n)
    rows = []
    for name in sorted(by_name, key=rank):
        evts = [e for e, _ in by_name[name]]
        rows.append({
            "span": name, "calls": len(evts) / rounds,
            "total_ms": 1e-6 * sum(e[2] - e[1] for e in evts) / rounds,
            "self_ms": 1e-6 * sum(own for _, own in by_name[name]) / rounds,
            "idle_ms": 1e-6 * idle_ns(evts, busy) / rounds,
            "launches": sum(launched(e) for e in evts) / rounds})
    return rows


def main(argv: list[str]) -> int:
    if len(argv) != 1:
        print("usage: " + __doc__.split("\n\n")[2].strip(), file=sys.stderr)
        return 2
    events = reduce.load(argv[0])
    rounds = events["counters"]["rounds"]
    lo, hi = reduce.window(events)
    busy = sum(e - s for s, e in reduce.busy(events, lo, hi))
    print(f"{rounds} rounds, {1e-6 * (hi - lo) / rounds:.3f} ms a round, "
          f"device idle {1e-6 * (hi - lo - busy) / rounds:.3f} ms a round")
    cols = ("calls", "total_ms", "self_ms", "idle_ms", "launches")
    print(f"{'span':<14}" + "".join(f"{c:>10}" for c in cols))
    for r in table(events, rounds):
        print(f"{r['span']:<14}" + "".join(f"{r[c]:>10.3f}" for c in cols))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
