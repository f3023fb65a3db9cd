"""The exact parts of `check`: graphs as lists and the guarantees a
refresh's lists keep."""
import numpy as np

import check


def test_dense_masks_list_their_peers_and_widen_past_the_width():
    g = np.eye(4, dtype=bool)
    g[0, [1, 2, 3]] = True
    g[2, 3] = True
    lists = check.as_lists(g, 2)
    assert lists.shape == (4, 3)
    assert lists[0].tolist() == [1, 2, 3]
    assert lists[1].tolist() == [-1, -1, -1]
    assert lists[2].tolist() == [3, -1, -1]


def test_broken_lists_counts_each_broken_guarantee_once_per_client():
    omega = np.array([[1, 2, -1], [0, 2, -1], [0, 1, -1]])
    good = np.array([[2, -1], [0, -1], [-1, -1]])
    assert check.broken_lists(omega, good, budget=2) == 0
    outside = np.array([[2, -1], [0, -1], [2, -1]])      # 2 lists itself
    assert check.broken_lists(omega, outside, budget=2) == 1
    twice = np.array([[2, 2], [0, -1], [-1, -1]])
    assert check.broken_lists(omega, twice, budget=2) == 1
    over = np.array([[1, 2], [0, 2], [-1, -1]])
    assert check.broken_lists(omega, over, budget=1) == 2
    foreign = np.array([[-1, -1], [-1, -1], [3, -1]])    # not in Omega_2
    assert check.broken_lists(omega, foreign, budget=2) == 1
