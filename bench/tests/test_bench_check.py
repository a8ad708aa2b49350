"""The sample the check compares: whole cohorts under the per-call scale,
the longest request first under the per-row scale."""
from types import SimpleNamespace

import pytest

from bench import check


def served(started, n_tokens):
    return SimpleNamespace(req=SimpleNamespace(started=started,
                                               tokens=[0] * n_tokens))


def test_cohort_sample_keeps_cohorts_whole():
    fin = [served(t, 13) for t in (1, 1, 1, 1, 9, 9, 9, 9, 17, 17, 17, 17,
                                   25, 25, 25)]     # the last cohort is cut
    chk = {"scale": "cohort", "max_requests": 8}
    picked = check.sample(fin, chk, 2147483647, batch=4)
    ticks = [s.req.started for s in picked]
    assert len(picked) == 8 and 25 not in ticks
    assert all(ticks.count(t) == 4 for t in set(ticks))


@pytest.mark.parametrize("seed", [1, 2147483647])
def test_row_sample_takes_the_longest_first(seed):
    fin = [served(t, n) for t, n in ((1, 40), (1, 300), (2, 90), (3, 20))]
    chk = {"served_tokens": 350, "max_requests": 16}
    picked = check.sample(fin, chk, seed, batch=4)
    assert len(picked[0].req.tokens) == 300
    assert sum(len(s.req.tokens) for s in picked) >= 350
