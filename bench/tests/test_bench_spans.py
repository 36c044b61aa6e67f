"""The program's stage spans read against the device's busy intervals
(``bench/spans.py`` and the four readers that use it), on hand-made
intervals and on a short ``tiny1m.al-scan`` window recorded on a v5e with
the spans in place (``data/al_trace_spans.json.gz``)."""
import os

import pytest

import harness
import reduce
import spans

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "al_trace_spans.json.gz")
V5E = {"hbm_bytes_per_s": 819e9}
READERS = ("host_exposed_ms.al", "fetch_exposed_ms.al", "mask_exposed_ms.al",
           "fetches_per_round.al")
US = 1000          # hand-made times are in microseconds


def _ev(name, s, e, where="python"):
    return [name, s * US, e * US, where]


def _two_rounds():
    """Two rounds of the scan path on one thread: the device runs inside
    ``repro.hash`` and a read, and across the gap between the rounds."""
    host = [_ev(reduce.WINDOW_SPAN, 0, 1000),
            _ev("repro.query", 100, 400), _ev("repro.hash", 110, 150),
            _ev("repro.mask", 150, 190), _ev("repro.fetch", 200, 250),
            _ev("repro.fetch", 260, 300), _ev("repro.results", 300, 390),
            _ev("repro.query", 500, 900), _ev("repro.fetch", 600, 700),
            _ev(spans.LAUNCH + " linkage", 115, 116),
            _ev(spans.LAUNCH + " linkage", 155, 156),
            _ev(spans.LAUNCH + " linkage", 520, 521),
            _ev(spans.LAUNCH + " linkage", 130, 131, "pjrt-tpu-tasks")]
    dev = [_ev("fusion", 120, 140, "/device:TPU:0"),
           _ev("fusion", 210, 240, "/device:TPU:0"),
           _ev("fusion", 450, 550, "/device:TPU:0")]
    return {"ops": dev, "modules": dev, "host": host}


def _read(name, ctx):
    return harness.metric_reader(name).read(ctx)


def test_exposed_time_is_span_union_less_device_busy():
    ctx = reduce.context(_two_rounds(), {"rounds": 2}, V5E)
    # query: 300 + 400 us, of which 20 + 30 + 50 device busy, over 2 rounds
    assert _read("host_exposed_ms.al", ctx) == pytest.approx(0.300)
    # fetch: 50 + 40 + 100 us, of which 30 busy
    assert _read("fetch_exposed_ms.al", ctx) == pytest.approx(0.080)
    assert _read("mask_exposed_ms.al", ctx) == pytest.approx(0.020)
    assert _read("fetches_per_round.al", ctx) == pytest.approx(1.5)


def test_readers_read_nothing_without_spans_or_rounds():
    events = _two_rounds()
    bare = dict(events, host=[e for e in events["host"]
                              if not e[0].startswith(spans.PREFIX)])
    for name in READERS:
        assert _read(name, reduce.context(bare, {"rounds": 2}, V5E)) is None
        assert _read(name, reduce.context(events, {}, V5E)) is None


def test_overlap_of_merged_interval_lists():
    assert spans.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert spans.overlap_ns([(0, 10)], [(10, 20)]) == 0
    assert spans.overlap_ns([], [(0, 5)]) == 0


def test_self_time_subtracts_direct_children_only():
    evts = [_ev("repro.query", 0, 100), _ev("repro.scan", 10, 50),
            _ev("repro.fetch", 20, 30), _ev("repro.fetch", 60, 70),
            _ev("repro.query", 0, 100, "other")]
    assert spans.self_ns(evts) == [50 * US, 30 * US, 10 * US, 10 * US,
                                   100 * US]


def test_stage_table_per_round():
    rows = {r["span"]: r for r in spans.table(_two_rounds(), 2)}
    assert list(rows) == ["repro.query", "repro.hash", "repro.mask",
                          "repro.fetch", "repro.results"]
    q = rows["repro.query"]
    assert q["calls"] == 1.0
    assert q["total_ms"] == pytest.approx(0.350)
    assert q["self_ms"] == pytest.approx(0.170)      # (40 + 300) / 2
    assert q["idle_ms"] == pytest.approx(0.300)
    assert q["launches"] == 1.5                       # not the other thread's
    assert rows["repro.hash"]["launches"] == 0.5
    assert rows["repro.hash"]["idle_ms"] == pytest.approx(0.010)
    f = rows["repro.fetch"]
    assert f["calls"] == 1.5 and f["self_ms"] == f["total_ms"]


@pytest.fixture(scope="module")
def recorded():
    return reduce.load(TRACE)


def test_recorded_spans_every_reader_reads(recorded):
    ctx = reduce.context(recorded, recorded["counters"], V5E)
    got = {name: _read(name, ctx) for name in READERS}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["fetches_per_round.al"] == 6.0
    idle_ms = (ctx["window_s"] - ctx["busy_s"]) * 1e3 / ctx["counters"][
        "rounds"]
    assert got["fetch_exposed_ms.al"] < got["host_exposed_ms.al"] <= idle_ms
    assert got["mask_exposed_ms.al"] < got["host_exposed_ms.al"]


def test_recorded_stage_table(recorded):
    rows = {r["span"]: r for r in spans.table(recorded,
                                               recorded["counters"]["rounds"])}
    assert set(spans.ORDER) <= set(rows)
    q = rows["repro.query"]
    assert q["calls"] == 1.0 and rows["repro.fetch"]["calls"] == 6.0
    stages = sum(rows[n]["total_ms"] for n in spans.ORDER[1:])
    assert q["self_ms"] == pytest.approx(q["total_ms"] - stages)
    assert all(0 <= r["idle_ms"] <= r["total_ms"] for r in rows.values())
    lo, hi = reduce.window(recorded)
    per_round = reduce.launches(recorded, lo, hi) / \
        recorded["counters"]["rounds"]
    assert q["launches"] == per_round      # every launch is inside a query
