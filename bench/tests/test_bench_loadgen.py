"""The closed active-learning loop does the same work in every window: a
new learner every ``rounds_per_learner`` rounds, and a fallback pick for
an empty answer that is always a row still unlabeled."""
from types import SimpleNamespace

import numpy as np

import loadgen


class Recorder:
    """Answers each hyperplane with the first unmasked row (or nothing),
    and keeps the masks it was asked with."""

    def __init__(self, empty=False):
        self.empty, self.masks = empty, []

    def query_batch(self, ws, mask=None):
        self.masks.append(mask.copy())
        free = np.flatnonzero(mask)
        return [SimpleNamespace(index=int(free[q]), nonempty=not self.empty,
                                candidates=free[q:q + (not self.empty)])
                for q in range(len(ws))]


def _run(service, per_learner=4, c=3, n=200, seconds=0.05):
    pool = np.zeros((5, c, 8), np.float32)
    initial = np.ones(n, bool)
    initial[:10] = False
    _, rounds = loadgen.closed_al(service, pool, initial, per_learner,
                                  seconds, np.random.default_rng(9))
    return initial, rounds


def test_new_learner_every_rounds_per_learner():
    svc = Recorder()
    initial, rounds = _run(svc)
    assert len(rounds) > 8
    for k, (x, mask) in enumerate(zip(rounds, svc.masks)):
        assert x.restart == (k % 4 == 0)
        labeled = np.count_nonzero(initial) - np.count_nonzero(mask)
        assert labeled == 3 * (k % 4)
    # each answer's one candidate was unmasked when it was asked
    assert loadgen.replay_masks(initial, rounds, {2}) == 3 * len(rounds)
    assert (rounds[2].mask == svc.masks[2]).all()


def test_fallback_picks_are_unlabeled_rows():
    svc = Recorder(empty=True)
    initial, rounds = _run(svc)
    for x, mask in zip(rounds, svc.masks):
        assert mask[x.picks].all()
        assert not initial[:10][x.picks[x.picks < 10]].any()
