"""The ``deep100m.al-scan`` cell at a small size: its reference agrees with
``reference.py`` where both apply and keeps the (distance, row) order past
``reference.py``'s 2^21 rows; its control fails; the whole cell runs
``correct`` on four CPU devices with the corpus row-sharded; and the
cell's four per-layer readers read a synthetic four-chip trace, or nothing
where their programs are absent."""
import copy
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import harness
import reduce
import reference
from small import run_small, small_cell
from test_bench_correct import (Faulty, altered_answer, altered_candidates,
                                half_batch_left_out)

CELL = "deep100m.al-scan"
SMALL = {"n_labeled": 400, "n_unlabeled": 3600, "d": 32}


def deep_cell(root=harness.ROOT):
    """The cell with its corpus cut to 4,000 rows of 32 + 1 features."""
    c = harness.load_cell(CELL, root)
    c.cfg["corpus"].update(SMALL)
    c.cfg["rows"] = SMALL["n_labeled"] + SMALL["n_unlabeled"]
    c.cfg["features"] = SMALL["d"] + 1
    c.cfg["precision"]["hash_operands"] = "float32"
    c.mix["check_answers"] = 40
    return c


deep96 = harness.load_module("references", "deep96")


@pytest.mark.parametrize("fault", [None, altered_answer, half_batch_left_out,
                                   altered_candidates, "control"])
def test_reference_agrees_below_2_21_rows(fault, monkeypatch):
    """On the answers of a small ``tiny1m.al-scan`` run, sound, with each
    planted fault, or from the control, the compared numbers of this
    reference equal ``reference.py``'s."""
    seen = []
    check = harness.check

    def both(c, x, answers, seed):
        got = check(c, x, answers, seed)
        mine = deep96.Reference(x, c.cfg, harness.index_seed(seed))
        seen.append((dict(got), reference.compare(answers, mine,
                                                  x.shape[0])))
        return got

    monkeypatch.setattr(harness, "check", both)
    kw = {"control": True} if fault == "control" else \
        {} if fault is None else {"wrap": lambda s: Faulty(s, fault)}
    run_small(small_cell("tiny1m.al-scan"), **kw)
    (plain, mine), = seen
    for k in ("topl_bad", "pick_bad", "gap_units", "margin_units", "checked"):
        assert mine[k] == plain[k], k
    shows = plain["topl_bad"] > 0 or plain["pick_bad"] > 0 \
        or plain["gap_units"] > 64
    assert shows == (fault is not None)


def _bare_reference(codes, unsure, l):
    """A deep96 Reference over given codes, with no corpus."""
    from jax.sharding import Mesh
    import jax
    ref = deep96.Reference.__new__(deep96.Reference)
    ref.mesh = Mesh(np.asarray(jax.devices()[:1]), ("rows",))
    ref.axis, ref.shards = "rows", 1
    ref.n = ref.n_local = codes.size
    ref.l, ref.bits = l, 24
    ref.codes, ref.unsure = jnp.asarray(codes), jnp.asarray(unsure)
    return ref


def test_keys_keep_order_past_2_21_rows():
    """3·2^20 codes: the best rows by (distance, row) and the top-l checks
    agree with a plain host sort, with ties between rows on both sides of
    2^21 and near the end."""
    n, l = 3 << 20, 16
    rng = np.random.default_rng(11)
    codes = rng.integers(0, 1 << 24, n, dtype=np.uint32)
    q = np.uint32(0xABCDEF)
    near = np.r_[np.arange((1 << 21) - 4, (1 << 21) + 4),
                 np.arange(3_000_001, 3_000_005), [n - 1]]
    codes[near] = q ^ np.uint32(0b111)          # distance 3, all tied
    codes[near[::4]] = q                        # distance 0
    unsure = np.zeros(n, np.uint32)
    ref = _bare_reference(codes, unsure, l)
    qs, qu = jnp.asarray([q, q ^ 1], jnp.uint32), jnp.zeros(2, jnp.uint32)
    best = ref.best_high(qs, qu, 24)
    for i, qq in enumerate([q, q ^ np.uint32(1)]):
        dist = np.asarray([bin(int(c ^ qq)).count("1") for c in codes])
        order = np.lexsort((np.arange(n), dist))[:24]
        assert np.array_equal(best[i] & ((1 << 40) - 1), order)
        assert np.array_equal(best[i] >> 40, dist[order])
    top = np.sort(best[0][:l] & ((1 << 40) - 1))
    assert top.max() >= 3_000_000
    wrong = top.copy()
    wrong[-1] = 5
    assert _topl_bad_codes(ref, [q], [top, wrong]).tolist() == [0, 1]


def _topl_bad_codes(ref, qs, cands):
    """topl_bad with the query codes given directly."""
    ref.query_codes = lambda ws: (jnp.asarray(qs * len(cands), jnp.uint32),
                                  jnp.zeros(len(cands), jnp.uint32))
    return ref.topl_bad(np.zeros((len(cands), 4), np.float32), cands)


def test_control_is_not_correct():
    out = run_small(deep_cell(), seed=2 ** 33 + 17, control=True)
    assert out["correct"] is False
    assert out["compared"]["topl_bad"]["value"] > 0


FOUR_DEVICES = '''
import json, sys
sys.path[:0] = sys.argv[1:3]
import harness
import test_bench_deep96 as t
from small import run_small
from repro.serving.multi_table import MultiTableIndex

seen = []
fit = MultiTableIndex.fit


def spy(self, x, *a, **kw):
    out = fit(self, x, *a, **kw)
    seen.append((x, self))
    return out


MultiTableIndex.fit = spy
out = run_small(t.deep_cell(), seed=2 ** 31 + 9, seconds=2.0)
x, index = seen[0]
print(json.dumps({"correct": out["correct"], "compared": out["compared"],
                  "spec": str(x.sharding.spec), "kept": index.x is x,
                  "stats": [index.stats()["shards"],
                            index.stats()["rows_per_shard"]],
                  "metrics": sorted(out["metrics"])}))
'''


def test_cell_runs_correct_on_four_devices():
    """The whole cell at 4,000 rows on four CPU devices: the corpus comes
    row-sharded from the generator, the index keeps it so over four shards
    of 1,000 rows, and the run comes out correct."""
    tests = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", FOUR_DEVICES, tests,
                        harness.BENCH], env=env, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["correct"] is True, got["compared"]
    assert "rows" in got["spec"] and got["kept"] is True
    assert got["stats"] == [4, 1000]
    assert got["metrics"] == ["al_round_ms", "al_round_p95_ms", "setup_s"]


# -- the cell's per-layer readers on a synthetic four-chip trace ------------

def _ev(name, start, end, plane):
    return [name, start, end, plane]


def _four_chip_events():
    """Window [0, 10000) ns; per chip p: a scan launch of 1000 + 100·p ns
    (a fusion of 800 + 100·p ns, then an all-gather of 200 ns) and a
    re-rank launch of 500 ns (an all-reduce pair of 50 + 50 ns, then a
    fusion of 300 ns)."""
    events = {"ops": [], "modules": [],
              "host": [_ev(reduce.WINDOW_SPAN, 0, 10000, "python")]}
    for p in range(4):
        plane = f"/device:TPU:{p}"
        scan_end = 1000 + 1000 + 100 * p
        events["modules"] += [
            _ev("jit_sharded_scan(7)", 1000, scan_end, plane),
            _ev("jit_sharded_rerank(8)", 5000, 5500, plane)]
        events["ops"] += [
            _ev("%fusion.3 = s32[16]{0} fusion()", 1000,
                1800 + 100 * p, plane),
            _ev("%all-gather.1 = s32[4,1,16,128]{3,2,1,0} all-gather()",
                1800 + 100 * p, scan_end, plane),
            _ev("%all-reduce-start.2 = f32[10,128]{1,0} all-reduce-start()",
                5100, 5150, plane),
            _ev("%all-reduce-done.2 = f32[10,128]{1,0} all-reduce-done()",
                5150, 5200, plane),
            _ev("%fusion.9 = f32[10,128]{1,0} fusion()", 5200, 5500, plane)]
    return events


PEAKS = {"hbm_bytes_per_s": 1e12}


def _ctx(events, rounds=2):
    return reduce.context(events, {"rounds": rounds, "scan_bytes": 2000,
                                   "rerank_bytes": 500}, PEAKS)


def _read(name, ctx):
    return harness.metric_reader(name).read(ctx)


def test_readers_on_four_chips():
    ctx = _ctx(_four_chip_events())
    # 2000 bytes at 1e12 B/s = 2 ns against 4000 + 600 ns summed over chips
    assert _read("shard_scan_roofline.al", ctx) == pytest.approx(
        100 * 2.0 / 4600)
    assert _read("shard_rerank_roofline.al", ctx) == pytest.approx(
        100 * 0.5 / 2000)
    # 300 ns of collectives a chip, over 2 rounds
    assert _read("collective_ms.al", ctx) == pytest.approx(300e-6 / 2)
    # busy per chip: 1000 + 100·p ns in the scan, 400 in the re-rank
    assert _read("chip_busy_skew.al", ctx) == pytest.approx(
        100 * (1700 / 1550 - 1))


def test_readers_read_nothing_without_their_programs():
    events = _four_chip_events()
    events["modules"] = [e for e in events["modules"]
                         if "sharded" not in e[0]]
    events["ops"] = [e for e in events["ops"] if "fusion" in e[0]]
    ctx = _ctx(events)
    for name in ("shard_scan_roofline.al", "shard_rerank_roofline.al",
                 "collective_ms.al"):
        assert _read(name, ctx) is None, name
    one = copy.deepcopy(_four_chip_events())
    for k in ("ops", "modules"):
        one[k] = [e for e in one[k] if e[3] == "/device:TPU:0"]
    assert _read("chip_busy_skew.al", _ctx(one)) is None
