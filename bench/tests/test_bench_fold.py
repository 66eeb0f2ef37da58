"""The traced run's device path: the vote matrix rebuilt from spans, for
fast-path and slow-path proposals alike, and its fold."""

import numpy as np

import fold

EVENTS = [
    (1.000, "fast_propose", 2, 77, 5),
    (1.001, "fast_accept", 2, 77, 0, 1),
    (1.002, "fast_accept", 2, 77, 3, 0),
    (1.500, "slow_propose", 0, 77, 6),        # same id, other path
    (1.600, "slow_accept", 0, 77, 1, 0.3),
    (9.000, "fast_propose", 1, 5, 9),         # after the window
]


def test_rows_hold_the_proposer_and_its_accepts():
    a, w = fold.vote_rows(EVENTS, 5, 1, 0.0, 5.0)
    inf = np.inf
    np.testing.assert_array_equal(a, np.float32(
        [[1.001, inf, 1.0, 1.002, inf], [1.5, 1.6, inf, inf, inf]]))
    # geometric weights, proposer first, the others in id order
    assert w[0, 2] == w[1, 0] == w.max()
    assert (np.sort(w[0]) == np.sort(w[1])).all()
    assert w[0, 0] > w[0, 1] > w[0, 3] > w[0, 4]


def test_a_window_without_proposals_has_no_rows():
    a, w = fold.vote_rows(EVENTS, 5, 1, 2.0, 5.0)
    assert a.shape == w.shape == (0, 5)


def test_replay_folds_every_row_in_fixed_chunks():
    rows = fold.CHUNK + 3
    a = np.tile(np.float32([0.0, 1.0, 2.0, np.inf, np.inf]), (rows, 1))
    w = np.tile(np.float32([5, 4, 3, 2, 1]), (rows, 1))
    assert fold.replay(a, w) == rows      # 5 + 4 + 3 > 15 / 2
