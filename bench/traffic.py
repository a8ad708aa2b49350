"""The one load generator: turns a traffic file and a seed into requests.

A traffic file (``bench/traffic/<mix>.json``) gives the batch, the prompt
length, the cache length, the output-length distribution and how the first
cohort is drawn, with the public source of its lengths. The output lengths
are a stratified cover of the distribution in one fixed order, the same for
every seed; the seed draws the prompt ids (and the weights). So the seed
changes which tokens are served, never how much work a window holds or when
requests retire: a window admits only some tens of requests, and a length
order drawn from the seed would move whole admissions in and out of it.

Output lengths:

* ``{"dist": "fixed", "value": n}``
* ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b,
  "pool": n}``: the n quantiles at (j + 0.5) / n, rounded and clipped, in
  van der Corput order (:func:`cover_order`), so every run of consecutive
  requests spreads evenly over the distribution.

``first_cohort``: ``"same"`` draws the first ``batch`` requests like the
rest; ``"residual"`` draws their lengths from the residual-length
distribution (what is left of a request caught mid-flight in a steady
stream), so slots retire staggered from the start of the window. Its
quantiles go to the slots in van der Corput order too, so that both halves
of the batch hold short and long requests.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass(frozen=True)
class Mix:
    name: str
    batch: int
    prompt_len: int
    max_seq: int
    lengths: tuple          # the pool of output lengths, in request order
    first: tuple            # the first cohort's lengths, slot by slot
    check: dict

    @classmethod
    def from_json(cls, name: str, t: dict) -> "Mix":
        pool = _pool(t["output_tokens"])
        first = _residual(pool, t["batch"]) \
            if t.get("first_cohort", "same") == "residual" else ()
        mix = cls(name=name, batch=t["batch"], prompt_len=t["prompt_len"],
                  max_seq=t["max_seq"], lengths=pool, first=first,
                  check=t["check"])
        if mix.prompt_len + max(pool) > mix.max_seq:
            raise ValueError(f"traffic {name}: prompt + longest output "
                             f"exceeds max_seq {mix.max_seq}")
        return mix


def _radical_inverse(k: int, bits: int) -> int:
    """k with its ``bits`` low bits in reverse order."""
    return int(format(k, f"0{bits}b")[::-1], 2) if bits else 0


def cover_order(n: int) -> list:
    """0 .. n-1 in van der Corput order: the base-2 radical inverses of
    0, 1, 2, ... over the next power of two, those below n. Every prefix
    of k takes about one index from each k-th of the range."""
    bits = max(n - 1, 0).bit_length()
    return [j for j in (_radical_inverse(k, bits) for k in range(1 << bits))
            if j < n]


def _pool(spec: dict) -> tuple:
    if spec["dist"] == "fixed":
        return (int(spec["value"]),)
    if spec["dist"] == "lognormal":
        n = int(spec["pool"])
        z = [NormalDist().inv_cdf((j + 0.5) / n) for j in cover_order(n)]
        v = np.rint(spec["median"] * np.exp(spec["sigma"] * np.asarray(z)))
        return tuple(int(x) for x in np.clip(v, spec["min"], spec["max"]))
    raise ValueError(f"unknown output-length distribution {spec['dist']!r}")


def _residual(pool: tuple, n: int) -> tuple:
    """n quantiles of the residual length R: P(R = r) is proportional to
    P(L >= r), r = 1 .. max L (the length-biased remainder)."""
    L = np.asarray(pool)
    r = np.arange(1, L.max() + 1)
    w = (L[None, :] >= r[:, None]).mean(axis=1)
    cdf = np.cumsum(w) / w.sum()
    return tuple(int(r[np.searchsorted(cdf, (j + 0.5) / n)])
                 for j in cover_order(n))


def requests(mix: Mix, vocab: int, seed: int, count: int):
    """``count`` requests ``(prompt ids, max_new)``: the first cohort (if the
    mix draws one), then the pool in its order, repeated; prompt ids from
    ``seed``."""
    out_lens = list(mix.first)
    while len(out_lens) < count:
        out_lens += list(mix.lengths)
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, vocab, (count, mix.prompt_len), dtype=np.int32)
    return [(prompts[i], int(out_lens[i])) for i in range(count)]
