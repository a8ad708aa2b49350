"""What decides ``correct``: served tokens against the plain reference.

After the window, a seeded sample of the requests the window finished is
run through the configuration's reference, teacher-forced on each prompt and
the tokens the program served. At every served position the reading is the
gap by which the served token's reference logit lies below the reference's
best there. Greedy serving of the stated arithmetic keeps that gap at zero
on most tokens: it opens only where the program's rounding and the
reference's part ways between near-equal logits. The number compared is the
mean gap over every served token compared; the widest gap is printed beside
it (it does not separate the control from sound runs: see PERF.md).

The mix's ``check.scale`` says how the reference scales activations:

* ``"row"``: one scale per token row; the sample is the finished request
  with the most served tokens, then others in a seeded order, until
  ``served_tokens`` are covered or ``max_requests`` are taken;
* ``"cohort"``: the program's scale, one per call over the rows that shared
  it, for mixes whose cohorts are admitted and retire together; the sample
  is whole cohorts (``batch`` requests admitted on one tick) in a seeded
  order, ``max_requests`` requests in all.
"""
from __future__ import annotations

import importlib
from collections import defaultdict

import numpy as np


def sample(finished: list, check: dict, seed: int, batch: int) -> list:
    """The requests compared (see the module docstring); a cohort's
    requests stay together."""
    if not finished:
        return []
    rng = np.random.default_rng(seed + 1)
    if check.get("scale", "row") == "cohort":
        cohorts = _cohorts(finished, batch)
        take = check["max_requests"] // batch
        return [s for i in rng.permutation(len(cohorts))[:take]
                for s in cohorts[i]]
    order = sorted(finished, key=lambda s: -len(s.req.tokens))
    picked, rest = [order[0]], order[1:]
    total = len(order[0].req.tokens)
    for i in rng.permutation(len(rest)):
        if total >= check["served_tokens"] or \
                len(picked) >= check["max_requests"]:
            break
        picked.append(rest[i])
        total += len(rest[i].req.tokens)
    return picked


def _cohorts(served: list, batch: int) -> list:
    """Whole cohorts: ``batch`` requests admitted on one tick."""
    by_tick = defaultdict(list)
    for s in served:
        by_tick[s.req.started].append(s)
    return [v for _, v in sorted(by_tick.items()) if len(v) == batch]


def gaps(conf: dict, sizes: dict, weights, picked: list, check: dict,
         batch: int) -> list:
    """The gap of every served token of ``picked``, one array per request,
    through the reference that the configuration names
    (``bench/references/<reference>.py``)."""
    ref = importlib.import_module(f"bench.references.{conf['reference']}")
    shape = ref.Shape.from_hf(sizes)
    ar = ref.Arithmetic.from_config(conf["arithmetic"])
    seqs = [np.concatenate([s.req.prompt, np.asarray(s.req.tokens, np.int32)])
            for s in picked]
    plens = [len(s.req.prompt) for s in picked]
    if check.get("scale", "row") == "cohort":
        pos = {id(s): i for i, s in enumerate(picked)}
        idx = [[pos[id(s)] for s in c] for c in _cohorts(picked, batch)]
        return ref.cohort_gaps(weights, seqs, plens, idx, shape, ar,
                               block_len=check["block_len"])
    return ref.served_gaps(weights, seqs, plens, shape, ar,
                           block_len=check["block_len"])
