"""The load generator: sourced lengths, an even cover, seeds that change
the prompts and never the work."""
import numpy as np
import pytest

from bench import spec, traffic


def mix(name):
    return traffic.Mix.from_json(name, spec.traffic(name))


def test_decode_pool_keeps_the_sources_mean():
    m = mix("decode")
    assert len(m.lengths) == 512
    # ShareGPT's mean output, 337.99 tokens
    assert np.mean(m.lengths) == pytest.approx(338.0, abs=1.0)
    assert m.prompt_len + max(m.lengths) <= m.max_seq


@pytest.mark.parametrize("k", [8, 16, 64])
def test_every_prefix_covers_the_distribution(k):
    m = mix("decode")
    n = len(m.lengths)
    q = traffic.cover_order(n)
    assert sorted(q) == list(range(n))
    # the first k requests take one quantile from each k-th of the pool
    assert sorted(j * k // n for j in q[:k]) == list(range(k))
    srt = np.sort(m.lengths)
    assert [m.lengths[i] for i in range(k)] == [srt[j] for j in q[:k]]


def test_seed_draws_prompts_not_lengths():
    m = mix("decode")
    a = traffic.requests(m, 1000, 2147483647, 64)
    b = traffic.requests(m, 1000, 3, 64)
    assert [n for _, n in a] == [n for _, n in b]
    assert any((pa != pb).any() for (pa, _), (pb, _) in zip(a, b))
    assert [n for _, n in a[:m.batch]] == list(m.first)


def test_prefill_mix_fixed_outputs():
    m = mix("prefill")
    assert m.lengths == (13,) and m.prompt_len + 13 <= m.max_seq


@pytest.mark.parametrize("n", [1, 3, 4, 8, 12])
def test_cover_order_is_a_permutation(n):
    assert sorted(traffic.cover_order(n)) == list(range(n))


def test_first_cohort_puts_short_and_long_in_both_halves():
    m = mix("decode")
    h = m.batch // 2
    first = np.asarray(m.first)
    assert first[:h].min() < np.median(first) < first[:h].max()
    assert first[h:].min() < np.median(first) < first[h:].max()
