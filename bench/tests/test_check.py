"""The sample the check draws and the numbers it compares."""
import numpy as np
import pytest

import check


class _R:
    def __init__(self, n):
        self.n_tokens = n


class _Q:
    def __init__(self, p):
        self.tokens = np.zeros(p, np.int32)


def test_sample_holds_the_longest_and_enough_tokens():
    served = [(_Q(100), _R(n)) for n in (5, 40, 7, 9, 3, 12)]
    for seed in (1, 2, 3):
        got = check.sample(served, seed, 20)
        assert got[0][1].n_tokens == 40
        assert sum(r.n_tokens for _, r in got) >= 20
    a = check.sample(served, 5, 50)
    assert [r.n_tokens for _, r in a] == [r.n_tokens
                                          for _, r in check.sample(served, 5,
                                                                   50)]
    assert sum(r.n_tokens for _, r in a) >= 50


def test_numbers():
    g = [np.array([0.0, 0.5, 0.0]), np.array([0.0, 0.1])]
    n = check.numbers(g)
    assert n["max_logit_gap"] == 0.5
    assert n["mean_logit_gap"] == pytest.approx(0.12)
