"""The comparison that decides ``correct``, driven through a whole run at
a small size on the CPU (the harness's look for a chip is skipped): sound
runs pass, the lower-precision control fails, and so does the timed path
with each fault a cell can have planted under it."""
import copy

import numpy as np
import pytest

import harness
import reference
from small import run_small, small_cell


class Faulty:
    """Wraps the service under test; ``fault`` edits a batch's answers."""

    def __init__(self, inner, fault):
        self.inner, self.fault = inner, fault
        self.calls = 0

    def query_batch(self, ws, mask=None):
        res = self.inner.query_batch(ws, mask=mask)
        self.calls += 1
        return self.fault([copy.copy(r) for r in res]) \
            if self.calls > 2 else res     # warm-up calls stay sound

    def stats(self):
        return self.inner.stats()


def altered_answer(res):
    """The pick is swapped for the candidate of largest margin index."""
    for r in res:
        if r.nonempty and len(r.candidates) > 1:
            r.index = int(r.candidates[-1]) if r.index != r.candidates[-1] \
                else int(r.candidates[0])
    return res


def half_batch_left_out(res):
    """Only the first half of the batch is answered; the rest repeat it."""
    h = max(1, len(res) // 2)
    return res[:h] + [copy.copy(res[i % h]) for i in range(h, len(res))]


def altered_candidates(res):
    """One candidate of each list is replaced by another row."""
    for r in res:
        c = np.asarray(r.candidates).copy()
        c[0] = (c.max() + 1) % 4600
        r.candidates = c
    return res


@pytest.mark.parametrize("workload", ["tiny1m.al-scan", "newsgroups.al-scan"])
def test_sound_run_is_correct(workload):
    out = run_small(small_cell(workload))
    assert out["correct"] is True, out["compared"]
    assert list(out)[-1] == "compared"
    assert out["compared"]["topl_bad"]["value"] == 0


@pytest.mark.parametrize("workload", ["tiny1m.al-scan", "newsgroups.al-scan"])
def test_control_is_not_correct(workload):
    out = run_small(small_cell(workload), control=True)
    assert out["correct"] is False
    assert out["compared"]["topl_bad"]["value"] > 0


@pytest.mark.parametrize("fault", [altered_answer, half_batch_left_out,
                                   altered_candidates])
def test_fault_is_not_correct(fault):
    out = run_small(small_cell("tiny1m.al-scan"),
                    wrap=lambda s: Faulty(s, fault))
    assert out["correct"] is False, out["compared"]


@pytest.mark.parametrize("workload,control", [("tiny1m.al-scan", False),
                                              ("newsgroups.al-scan", True)])
def test_gathered_margins_match_a_host_copy(workload, control, monkeypatch):
    """The reference reads margins from the rows it gathers on the device;
    the compared numbers are those of the whole corpus copied to the host,
    to the last bit."""
    seen = []
    check = harness.check

    def both(c, x, answers, seed):
        got = dict(check(c, x, answers, seed))
        ref = c.reference.Reference(x, c.cfg, harness.index_seed(seed))
        x_host = np.asarray(x)
        ref.margins = lambda w, rows: reference.margins64(x_host[rows], w)
        seen.append((got, reference.compare(answers, ref, x_host.shape[0])))
        return got

    monkeypatch.setattr(harness, "check", both)
    run_small(small_cell(workload), control=control)
    (got, host), = seen
    for k in ("topl_bad", "pick_bad", "gap_units", "margin_units", "checked"):
        assert got[k] == host[k], k
    assert host["margin_units"] > 0
