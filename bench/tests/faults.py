"""Runs ``bench/run.py`` with the timed path broken underneath.

    python bench/tests/faults.py <fault> -- <bench/run.py arguments>

Each fault wraps a step the scheduler builds, so the harness, the window
and the check run unchanged around it. In the decode step
(``repro.launch.serve.make_decode_step``):

* ``stale_state``: the step returns the cache it was given, unchanged;
* ``half_batch``: the second half of the batch is left out, each of its
  rows answered with a row of the first half;
* ``altered_token``: every row's token is altered where it is produced
  (the logits shifted by one vocabulary entry).

In the admission (the model's ``prefill`` and the scheduler's cache insert):

* ``admit_stale``: the insert returns the cache it was given, so the
  admitted prompts' keys and values never reach it;
* ``admit_half_batch``: the prefill's second half of rows answered with
  the first half's first tokens;
* ``admit_altered_token``: every admitted row's first token altered.

The exchange between chips cannot be left out: every cell runs on one chip.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def _wrap(fault):
    import jax.numpy as jnp
    from repro.launch import serve

    real = serve.make_decode_step

    def make(lm, donate=None):
        step = real(lm, donate)

        def broken(params, cache, tok, pos):
            logits, new = step(params, cache, tok, pos)
            if fault == "stale_state":
                return logits, cache
            if fault == "half_batch":
                h = logits.shape[0] // 2
                return jnp.concatenate([logits[:h], logits[:h]]), new
            if fault == "altered_token":
                return jnp.roll(logits, 1, axis=-1), new
            raise SystemExit(f"unknown fault {fault!r}")
        return broken

    serve.make_decode_step = make


def _wrap_admission(fault):
    from dataclasses import dataclass

    import jax.numpy as jnp
    from repro.launch import scheduler
    from repro.models.model import LM

    if fault == "admit_stale":
        scheduler.Scheduler._insert_impl = \
            lambda self, cache, pre, slot_ix: cache
        return

    @dataclass(frozen=True)
    class Broken(LM):
        def prefill(self, params, batch):
            logits, cache = LM.prefill(self, params, batch)
            if fault == "admit_half_batch":
                h = logits.shape[0] // 2
                return jnp.concatenate([logits[:h], logits[:h]]), cache
            if fault == "admit_altered_token":
                return jnp.roll(logits, 1, axis=-1), cache
            raise SystemExit(f"unknown fault {fault!r}")

    scheduler.build = Broken


def main(argv):
    fault, rest = argv[0], argv[argv.index("--") + 1:]
    (_wrap_admission if fault.startswith("admit_") else _wrap)(fault)
    from bench import run
    run.main(rest)


if __name__ == "__main__":
    main(sys.argv[1:])
