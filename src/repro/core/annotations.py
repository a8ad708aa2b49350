"""Width-contract annotations consumed by the static analyzer.

The bit-width verifier (:mod:`repro.analysis.widthcheck`) propagates a
non-relational interval x possible-bits domain through jaxprs. One datapath
fact is inherently *relational* and therefore invisible to that domain: the
Mitchell log packing ``L = (k << F) | x_fp`` is disjoint only because
``x_fp = frac << (F - k)`` and ``frac < 2^(k+1)`` share the same ``k``.
These annotations bridge the gap with checked contracts:

* :func:`require_range` declares a precondition on a value. The analyzer
  *verifies* the incoming abstract interval against it — a caller feeding
  an out-of-domain operand (e.g. a float clamp that rounds past the lane
  maximum) becomes a finding at this equation. It may also open a scope in
  which named analyzer rules are assumed (``assume=...``) until the
  matching :func:`ensure_range`.
* :func:`ensure_range` declares a postcondition and closes the scope. The
  analyzer *refines* the abstract value to it. Postconditions are not
  proved by the abstract domain — they are backed by the exhaustive
  bit-parity suites (tests/test_fastpath.py, tests/conformance) and listed
  as "assumed contracts" in every analyzer report.

Outside analyzer tracing both functions are exact no-ops (identity,
zero-cost): the primitive is only ever bound while
:func:`analysis_tracing` is active, so jitted production code never sees
it. An identity lowering is registered anyway as a safety net.
"""
from __future__ import annotations

from contextlib import contextmanager

from jax.extend.core import Primitive
from jax.interpreters import mlir

__all__ = [
    "range_contract_p",
    "analysis_tracing",
    "tracing_active",
    "require_range",
    "ensure_range",
]

_ACTIVE = False

range_contract_p = Primitive("simdive_range_contract")
range_contract_p.def_impl(lambda x, **_: x)
range_contract_p.def_abstract_eval(lambda x, **_: x)
# identity lowering: annotated code stays jittable if a trace escapes
mlir.register_lowering(range_contract_p, lambda ctx, x, **_: [x])


def tracing_active() -> bool:
    """True while the analyzer is tracing (annotations bind their
    primitive instead of being identity no-ops)."""
    return _ACTIVE


@contextmanager
def analysis_tracing():
    """Arm the annotations for one analyzer trace (widthcheck-internal)."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = True
    try:
        yield
    finally:
        _ACTIVE = prev


def require_range(x, *, hi: int, lo: int = 0, what: str = "",
                  assume: tuple = ()):
    """Checked precondition: the analyzer flags ``x`` unless its abstract
    interval is provably inside ``[lo, hi]``, then refines it to the
    declared range (so one caller bug yields one finding, not a cascade).
    ``assume`` names analyzer rules suppressed until the matching
    :func:`ensure_range` — the contract-verified region."""
    if not _ACTIVE:
        return x
    return range_contract_p.bind(
        x, phase="require", lo=int(lo), hi=int(hi), bits=None,
        what=str(what), assume=tuple(assume))


def ensure_range(x, *, hi: int, lo: int = 0, bits: int | None = None,
                 what: str = ""):
    """Declared postcondition: refines the abstract value to
    ``[lo, hi]`` (and possible-bits mask ``bits``) and closes the
    innermost :func:`require_range` scope. Backed by exhaustive tests,
    reported as an assumed contract — see the module docstring."""
    if not _ACTIVE:
        return x
    return range_contract_p.bind(
        x, phase="ensure", lo=int(lo), hi=int(hi),
        bits=None if bits is None else int(bits), what=str(what), assume=())
