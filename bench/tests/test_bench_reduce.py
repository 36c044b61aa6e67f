"""The reduction from a trace to per-layer metrics, on a small trace of a
``tiny1m.al-scan`` window recorded on a v5e with the program's stage spans
(``data/al_trace.json.gz``: a 0.1 s window of six rounds, the events
``reduce.extract`` keeps) and on hand-made intervals."""
import os

import pytest

import costs
import harness
import reduce

TRACE = os.path.join(os.path.dirname(__file__), "data", "al_trace.json.gz")
V5E = {"hbm_bytes_per_s": 819e9}


def _ev(name, s, e, where="/device:TPU:0"):
    return [name, s, e, where]


def test_union_merges_overlaps_and_touching():
    got = reduce.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 11)])
    assert got == [(0, 4), (5, 7), (10, 11)]


def test_idle_share_is_one_minus_union_over_window():
    events = {"ops": [_ev("a", 100, 300), _ev("b", 200, 400),
                      _ev("c", 700, 800)],
              "modules": [_ev("jit_f(1)", 100, 400),
                          _ev("jit_g(2)", 700, 800)],
              "host": [_ev(reduce.WINDOW_SPAN, 0, 1000, "main"),
                       _ev("bench.host_work", 400, 700, "main")]}
    ctx = reduce.context(events, {}, V5E)
    assert ctx["window_s"] == pytest.approx(1e-6)
    assert ctx["busy_s"] == pytest.approx(400e-9)       # 100..400, 700..800
    assert reduce.idle_share(ctx) == pytest.approx(60.0)
    gaps = dict(reduce.idle_gaps(events, 0, 1000))
    assert gaps["bench.host_work"] == pytest.approx(300e-9)
    assert gaps["idle"] == pytest.approx(300e-9)        # 0..100, 800..1000
    assert reduce.launches(events, 0, 1000) == 2


def test_device_time_by_pattern_and_roofline():
    events = {"ops": [],
              "modules": [_ev("jit__topk_grouped_impl(7)", 0, 2000),
                          _ev("jit_margin_rerank_batch(3)", 2000, 2500),
                          _ev("jit__topk_grouped_impl(7)", 3000, 5000)],
              "host": [_ev(reduce.WINDOW_SPAN, 0, 6000, "main")]}
    t = reduce.module_time_s(events, [r"_topk_grouped_impl"], 0, 6000)
    assert t == pytest.approx(4000e-9)
    ctx = reduce.context(events, {}, V5E)
    nbytes = 819e9 * 1000e-9                 # a quarter of the scan time
    assert reduce.roofline(ctx, [r"_topk_grouped_impl"], nbytes) == \
        pytest.approx(25.0)
    assert reduce.roofline(ctx, [r"no_such_kernel"], nbytes) is None


def test_algorithmic_bytes():
    # 1.06M 20-bit codes (one word), 10 masked queries at l = 128
    assert costs.scan_bytes(1_060_000, 20, 1, 10, 128, True) == \
        1_060_000 * 4 + 1_060_000 + 10 * 4 + 10 * 128 * 8
    assert costs.scan_bytes(100, 40, 2, 3, 5, False) == \
        2 * 100 * 2 * 4 + 2 * 3 * 2 * 4 + 2 * 3 * 5 * 8
    assert costs.rerank_bytes(1000, 385, 10) == (1000 + 10) * 385 * 4


@pytest.fixture(scope="module")
def recorded():
    return reduce.load(TRACE)


def test_recorded_trace_window_and_busy(recorded):
    lo, hi = reduce.window(recorded)
    busy = reduce.busy(recorded, lo, hi)
    assert busy and all(s < e for s, e in busy)
    assert all(a[1] <= b[0] for a, b in zip(busy, busy[1:]))
    total = sum(e - s for s, e in busy)
    assert 0 < total < hi - lo
    ctx = reduce.context(recorded, {}, V5E)
    assert 0.0 < reduce.idle_share(ctx) < 100.0


def test_recorded_trace_layers(recorded):
    lo, hi = reduce.window(recorded)
    scan = reduce.module_time_s(recorded, [r"_topk_grouped_impl"], lo, hi)
    rerank = reduce.module_time_s(recorded, [r"margin_rerank_batch"], lo, hi)
    assert scan > 0 and rerank > 0
    every = reduce.module_time_s(recorded, [r"."], lo, hi)
    assert scan + rerank <= every
    ops = reduce.top_ops(recorded, lo, hi)
    assert 0 < len(ops) <= 10
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
    gaps = reduce.idle_gaps(recorded, lo, hi)
    assert 0 < len(gaps) <= 10


def test_recorded_trace_every_al_metric_reads(recorded):
    ctx = reduce.context(recorded, recorded["counters"], V5E)
    c = harness.load_cell("tiny1m.al-scan")
    for m in c.per_layer:
        v = harness.metric_reader(m["name"]).read(ctx)
        assert v is not None and v > 0, m["name"]
        if m["unit"] == "%":
            assert v <= 100.0, m["name"]
