"""Jaxpr bit-width / overflow verifier for the SIMDive integer datapath.

Traces every registered op (``registry.all_ops()``) with abstract uint
operands of the declared lane width, under ``faithful_mode(True)`` (so the
exhaustively bit-parity-tested faithful path is what gets verified, and
float-bitcast fast paths never enter the jaxpr), and propagates the
interval x possible-bits domain of :mod:`repro.analysis.domain` through
the primitives the datapath uses. Per (op, width, coeff_bits, index_bits,
frac_out, lane-count) config it proves:

* **overflow** — no integer add/sub/mul/reduce_sum/dot_general result can
  exceed its carrier dtype,
* **shift-range** — every shift amount is statically in ``[0, nbits-1]``
  (out-of-range shifts are undefined in XLA),
* **lane-overlap** — every integer OR is a provably disjoint bit-field
  union (the packed-lane / log-packing invariant),
* **signedness** — no conversion crosses a signedness boundary with a
  possibly-out-of-range value,
* **gather-bounds** — 1-D table lookups (correction LUTs) are in range,
* **lane-domain** — ``require_range`` contract preconditions hold.

``shift_left`` *value* overflow is deliberately not a rule: XLA shifts are
modular and the datapath's saturation selects (``where(over, max_out, _)``)
discard exactly the lanes that wrapped; flagging them would make the
verifier unusable. The discipline the repo actually relies on — and which
this pass enforces — is that every surviving lane was produced under an
in-range shift and lands in a checked interval.

Unknown primitives widen to the top of their output dtype (sound) and are
listed per case in the report, never silently dropped.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field

import numpy as np

from .domain import (AbsVal, CaseReport, Finding, TraceCase, from_concrete,
                     join, top)

from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

__all__ = ["check_case", "run_matrix", "render_text", "to_json",
           "MatrixResult"]

_LOOP_CAP = 4096          # max statically-simulated loop iterations


def _src_of(eqn) -> str:
    try:
        from jax._src import source_info_util
        s = source_info_util.summarize(eqn.source_info)
        # keep "<file>:<line> (<fn>)" with a repo-relative-ish file part
        for marker in ("/src/", "/repo/"):
            if marker in s:
                return s.split(marker, 1)[1]
        return s.rsplit("/", 1)[-1]
    except Exception:  # pragma: no cover - jax-internal API drift
        return ""


def _eqn_str(eqn, ins) -> str:
    parts = ", ".join(
        f"{np.dtype(v.dtype).name}{list(v.shape)}{v.describe()}" for v in ins)
    out = eqn.outvars[0].aval
    return (f"{eqn.primitive.name}({parts}) -> "
            f"{np.dtype(out.dtype).name}{list(out.shape)}")


def _iinfo(dt):
    dt = np.dtype(dt)
    if dt.kind == "b":
        return 0, 1
    ii = np.iinfo(dt)
    return int(ii.min), int(ii.max)


def _corners(a, b, op):
    vals = [op(x, y) for x in (a.lo, a.hi) for y in (b.lo, b.hi)]
    vals = [v for v in vals if v == v]          # drop nan (inf - inf etc.)
    if not vals:
        return -math.inf, math.inf
    return min(vals), max(vals)


def _exact(dtype, shape, v: int) -> AbsVal:
    return AbsVal(np.dtype(dtype), tuple(shape), int(v), int(v),
                  int(v) if v >= 0 else None).norm()


def _refine(val: AbsVal, lo, hi, bits=None) -> AbsVal:
    """Intersect ``val`` with a declared range (contract refinement)."""
    if not val.is_int:
        return AbsVal(val.dtype, val.shape, float(lo), float(hi))
    nb = val.bits
    if bits is not None:
        nb = bits if nb is None else (nb & bits)
    return AbsVal(val.dtype, val.shape, max(val.lo, int(lo)),
                  min(val.hi, int(hi)), nb).norm()


# monotone float unaries: name -> (fn, increasing)
_FLOAT_MONO = {
    "exp": (math.exp, True),
    "exp2": (lambda x: 2.0 ** x, True),
    "log": (lambda x: math.log(x) if x > 0 else -math.inf, True),
    "log2": (lambda x: math.log2(x) if x > 0 else -math.inf, True),
    "log1p": (lambda x: math.log1p(x) if x > -1 else -math.inf, True),
    "expm1": (math.expm1, True),
    "sqrt": (lambda x: math.sqrt(x) if x >= 0 else math.nan, True),
    "cbrt": (lambda x: math.copysign(abs(x) ** (1 / 3), x), True),
    "floor": (math.floor, True),
    "ceil": (math.ceil, True),
    "round": (round, True),
    "rsqrt": (lambda x: 1.0 / math.sqrt(x) if x > 0 else math.inf, False),
    "tanh": (math.tanh, True),
    "logistic": (lambda x: 1.0 / (1.0 + math.exp(-x)), True),
}

_IDENTITY = frozenset({
    "broadcast_in_dim", "reshape", "squeeze", "transpose", "slice", "rev",
    "expand_dims", "copy", "stop_gradient", "reduce_max", "reduce_min",
    "real", "device_put", "optimization_barrier",
})

_BOOL_OUT = frozenset({
    "eq", "ne", "lt", "le", "gt", "ge", "is_finite", "reduce_and",
    "reduce_or",
})

#: structured control flow carries sub-jaxprs too, but has its own rules
_CONTROL_PRIMS = frozenset({"while", "scan", "cond"})


def _sub_jaxpr(eqn):
    """The body of a call-like equation (``jit``/``pjit``, custom_jvp/vjp
    calls, remat, ...), or None. Keyed on the presence of a sub-jaxpr
    param rather than on primitive names, which differ across JAX
    releases (``jnp.where`` traces as ``pjit`` on some, ``jit`` on
    others). Calls are recursed into; pendings pass through unsettled."""
    if eqn.primitive.name in _CONTROL_PRIMS:
        return None
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = eqn.params.get(key)
        if isinstance(sub, (ClosedJaxpr, Jaxpr)):
            return sub
    return None


class _Interp:
    """One abstract interpretation of one trace case's jaxpr."""

    def __init__(self, report: CaseReport, label: str):
        self.report = report
        self.label = label
        self.scopes: list = []        # (frozenset(assumed rules), what)
        self._seen: set = set()       # finding dedupe across loop iterations
        self._unknown: set = set()
        self._defs: dict = {}         # var -> defining eqn (provenance)
        self._alias: dict = {}        # inner call invar -> outer atom

    # ----------------------------------------------------------- findings --
    def flag(self, rule: str, msg: str, eqn, ins):
        for assumed, _ in self.scopes:
            if rule in assumed:
                return
        src = _src_of(eqn)
        key = (rule, src, eqn.primitive.name)
        if key in self._seen:
            return
        self._seen.add(key)
        self.report.findings.append(
            Finding(rule, self.label, msg, eqn=_eqn_str(eqn, ins), source=src))

    def note_unknown(self, name: str):
        if name not in self._unknown:
            self._unknown.add(name)
            self.report.unknown_prims.append(name)

    # ---------------------------------------- deferred unsigned underflow --
    # ``where(a >= b, a - b, _)`` is the datapath's barrel-shifter idiom:
    # the sub underflows on lanes the select then discards. The sub defers
    # its finding as AbsVal.pending; the select with the *matching*
    # comparison clears it, any other consumption reports it.
    def _flag_raw(self, rule, msg, eqn_str, src):
        for assumed, _ in self.scopes:
            if rule in assumed:
                return
        key = (rule, src, "sub")
        if key in self._seen:
            return
        self._seen.add(key)
        self.report.findings.append(
            Finding(rule, self.label, msg, eqn=eqn_str, source=src))

    def _settle(self, v):
        if getattr(v, "pending", None) is None:
            return v
        _, rule, msg, eqn_str, src = v.pending
        self._flag_raw(rule, msg, eqn_str, src)
        return top(v.dtype, v.shape)

    def _resolve_key(self, atom, depth=0):
        """Identity of a select/compare operand, looking through shape-only
        ops so broadcast literals and vars match across equations."""
        if isinstance(atom, Literal):
            try:
                arr = np.asarray(atom.val)
                if arr.size == 1:
                    return ("lit", float(arr.reshape(-1)[0]))
            except (TypeError, ValueError):
                pass
            return ("lit", repr(atom.val))
        if depth < 16 and atom in self._alias:
            # jnp.where and friends trace as pjit; the predicate/operands
            # enter the inner jaxpr as invars bound to outer atoms
            return self._resolve_key(self._alias[atom], depth + 1)
        d = self._defs.get(atom)
        if depth < 16 and d is not None and len(d.invars) == 1 and \
                d.primitive.name in ("broadcast_in_dim",
                                     "convert_element_type", "copy",
                                     "reshape", "squeeze", "expand_dims"):
            return self._resolve_key(d.invars[0], depth + 1)
        return ("var", id(atom))

    def _def_of(self, atom, depth=0):
        """Defining eqn of ``atom``, looking through call-boundary aliases
        and shape-only wrappers (a broadcast pjit around the compare)."""
        if isinstance(atom, Literal):
            return None
        d = self._defs.get(atom)
        if d is not None and depth < 16 and len(d.invars) == 1 and \
                d.primitive.name in ("broadcast_in_dim", "copy", "reshape",
                                     "squeeze", "expand_dims"):
            return self._def_of(d.invars[0], depth + 1)
        if d is None and depth < 16 and atom in self._alias:
            return self._def_of(self._alias[atom], depth + 1)
        return d

    def _select_clear(self, eqn, ins):
        """Clear pendings proven dead by this select's predicate."""
        pred_atom = eqn.invars[0]
        cmp = kx = ky = None
        if not isinstance(pred_atom, Literal):
            d = self._def_of(pred_atom)
            if d is not None and d.primitive.name in ("ge", "gt", "lt", "le"):
                cmp = d.primitive.name
                kx = self._resolve_key(d.invars[0])
                ky = self._resolve_key(d.invars[1])
        out = [ins[0]]
        for idx, v in enumerate(ins[1:]):
            if getattr(v, "pending", None) is None:
                out.append(v)
                continue
            ka, kb = v.pending[0]
            # select_n picks cases[pred]: index 1 is the pred-true branch
            if cmp in ("ge", "gt"):          # true <=> x >= y / x > y
                ok = (idx == 1 and (ka, kb) == (kx, ky)) or \
                     (idx == 0 and (ka, kb) == (ky, kx))
            elif cmp in ("lt", "le"):        # true <=> x < y / x <= y
                ok = (idx == 1 and (ka, kb) == (ky, kx)) or \
                     (idx == 0 and (ka, kb) == (kx, ky))
            else:
                ok = False
            out.append(dataclasses.replace(v, pending=None) if ok
                       else self._settle(v))
        return out

    # --------------------------------------------------------- evaluation --
    def eval_closed(self, closed: ClosedJaxpr, invals):
        return self.eval_jaxpr(closed.jaxpr, closed.consts, invals)

    def eval_jaxpr(self, jaxpr: Jaxpr, consts, invals):
        env: dict = {}

        def read(a):
            if isinstance(a, Literal):
                return from_concrete(a.val)
            return env[a]

        for v, c in zip(jaxpr.constvars, consts):
            env[v] = c if isinstance(c, AbsVal) else from_concrete(c)
        for v, x in zip(jaxpr.invars, invals):
            env[v] = x
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                self._defs[v] = eqn
            ins = [read(x) for x in eqn.invars]
            outs = self.eval_eqn(eqn, ins)
            for v, o in zip(eqn.outvars, outs):
                env[v] = o
        # pendings flow out unsettled — an enclosing select may still clear
        # them; check_case settles whatever escapes the whole trace
        return [read(v) for v in jaxpr.outvars]

    def _top_out(self, eqn):
        return [top(v.aval.dtype, v.aval.shape) for v in eqn.outvars]

    def _mk(self, eqn, lo, hi, bits=None, check=True, ins=(), what="result"):
        """Build the (single) output value; flag overflow if out of dtype."""
        v = eqn.outvars[0].aval
        dt = np.dtype(v.dtype)
        if dt.kind in ("u", "i", "b"):
            lo, hi = int(lo), int(hi)
            dlo, dhi = _iinfo(dt)
            if check and (lo < dlo or hi > dhi):
                self.flag("overflow",
                          f"{what} [{lo}, {hi}] exceeds {dt.name} "
                          f"[{dlo}, {dhi}]", eqn, ins)
                return [top(dt, v.shape)]
            return [AbsVal(dt, tuple(v.shape), lo, hi, bits).norm()]
        if lo != lo:
            lo = -math.inf
        if hi != hi:
            hi = math.inf
        return [AbsVal(dt, tuple(v.shape), float(lo), float(hi))]

    # the dispatcher — one branch per primitive family
    def eval_eqn(self, eqn, ins):
        name = eqn.primitive.name
        sub = _sub_jaxpr(eqn)
        out_aval = eqn.outvars[0].aval
        odt = np.dtype(out_aval.dtype)

        if name == "select_n":
            p = ins[0]
            if p.is_int and p.lo == p.hi and 1 + int(p.lo) < len(ins):
                # statically decided select: only the live branch matters
                # (dead-branch pendings die with the branch)
                v = ins[1 + int(p.lo)]
                shp = tuple(out_aval.shape)
                return [v.with_shape(shp).norm() if v.is_int else
                        AbsVal(v.dtype, shp, v.lo, v.hi)]
            ins = self._select_clear(eqn, ins)
        elif name not in _IDENTITY and sub is None:
            # any non-shape consumption of a deferred underflow reports it
            # (calls pass pendings through — the select may live inside)
            ins = [self._settle(v) for v in ins]
        if name == "simdive_range_contract":
            return self._contract(eqn, ins)
        if name in _IDENTITY:
            a = ins[0]
            return [dataclasses.replace(
                        a.with_shape(tuple(out_aval.shape)).norm(),
                        pending=a.pending)
                    if a.is_int else
                    AbsVal(out_aval.dtype, tuple(out_aval.shape),
                           float(a.lo), float(a.hi))]
        if name in _BOOL_OUT:
            lo, hi = 0, 1
            if name in ("lt", "le", "gt", "ge", "eq", "ne") and \
                    all(math.isfinite(v.lo) and math.isfinite(v.hi)
                        for v in ins):
                a, b = ins
                # interval-decidable comparisons collapse to a constant —
                # jnp's negative-index wrap select(idx < 0, idx + T, idx)
                # depends on this to keep the dead branch dead
                if name in ("lt", "le"):
                    strict = name == "lt"
                    if a.hi < b.lo or (not strict and a.hi <= b.lo):
                        lo = hi = 1
                    elif a.lo > b.hi or (strict and a.lo >= b.hi):
                        lo = hi = 0
                elif name in ("gt", "ge"):
                    strict = name == "gt"
                    if a.lo > b.hi or (not strict and a.lo >= b.hi):
                        lo = hi = 1
                    elif a.hi < b.lo or (strict and a.hi <= b.lo):
                        lo = hi = 0
                elif name == "eq":
                    if a.lo == a.hi == b.lo == b.hi:
                        lo = hi = 1
                    elif a.hi < b.lo or a.lo > b.hi:
                        lo = hi = 0
                elif name == "ne":
                    if a.lo == a.hi == b.lo == b.hi:
                        lo = hi = 0
                    elif a.hi < b.lo or a.lo > b.hi:
                        lo = hi = 1
            return [AbsVal(np.dtype(np.bool_), tuple(out_aval.shape),
                           lo, hi, hi)]
        if name in ("add", "sub", "mul"):
            return self._arith(eqn, ins, name)
        if name in ("and", "or", "xor", "not"):
            return self._bitwise(eqn, ins, name)
        if name in ("shift_left", "shift_right_logical",
                    "shift_right_arithmetic"):
            return self._shift(eqn, ins, name)
        if name == "convert_element_type":
            return self._convert(eqn, ins)
        if name == "select_n":
            out = ins[1]
            for c in ins[2:]:
                out = join(out, c)
            return [out.with_shape(tuple(out_aval.shape))]
        if name in ("max", "min"):
            f = max if name == "max" else min
            a, b = ins
            return self._mk(eqn, f(a.lo, b.lo), f(a.hi, b.hi),
                            check=False, ins=ins)
        if name == "clamp":
            l, x, h = ins
            lo = min(max(x.lo, l.lo), h.lo)
            hi = min(max(x.hi, l.hi), h.hi)
            return self._mk(eqn, lo, hi, check=False, ins=ins)
        if name == "div":
            return self._div(eqn, ins)
        if name == "rem":
            a, b = ins
            if a.is_int and a.lo >= 0 and b.lo >= 1:
                return self._mk(eqn, 0, min(a.hi, b.hi - 1), check=False,
                                ins=ins)
            return self._top_out(eqn)
        if name == "neg":
            a = ins[0]
            return self._mk(eqn, -a.hi, -a.lo, ins=ins, what="negation")
        if name == "abs":
            a = ins[0]
            lo = 0 if a.lo <= 0 <= a.hi else min(abs(a.lo), abs(a.hi))
            return self._mk(eqn, lo, max(abs(a.lo), abs(a.hi)), ins=ins,
                            what="abs")
        if name == "sign":
            a = ins[0]
            return self._mk(eqn, -1 if a.lo < 0 else (0 if a.lo == 0 else 1),
                            1 if a.hi > 0 else (0 if a.hi == 0 else -1),
                            check=False, ins=ins)
        if name in ("integer_pow", "pow"):
            return self._pow(eqn, ins)
        if name == "square":
            a = ins[0]
            lo = 0 if (a.lo <= 0 <= a.hi) else min(a.lo * a.lo, a.hi * a.hi)
            return self._mk(eqn, lo, max(a.lo * a.lo, a.hi * a.hi), ins=ins,
                            what="square")
        if name == "reduce_sum":
            return self._reduce_sum(eqn, ins)
        if name == "dot_general":
            return self._dot_general(eqn, ins)
        if name == "iota":
            dim = eqn.params["dimension"]
            n = out_aval.shape[dim] if out_aval.shape else 1
            return self._mk(eqn, 0, max(n - 1, 0), check=False, ins=ins)
        if name in ("argmax", "argmin"):
            n = int(np.prod(ins[0].shape) // max(np.prod(out_aval.shape), 1))
            return self._mk(eqn, 0, max(n - 1, 0), check=False, ins=ins)
        if name == "concatenate":
            out = ins[0]
            for c in ins[1:]:
                out = join(out, c)
            return [out.with_shape(tuple(out_aval.shape))]
        if name == "pad":
            return [join(ins[0], ins[1]).with_shape(tuple(out_aval.shape))]
        if name == "gather":
            return self._gather(eqn, ins)
        if name == "dynamic_slice":
            return [ins[0].with_shape(tuple(out_aval.shape))]
        if name == "dynamic_update_slice":
            return [join(ins[0], ins[1].with_shape(ins[0].shape))]
        if name == "clz":
            return self._mk(eqn, 0, ins[0].nbits, check=False, ins=ins)
        if name == "population_count":
            return self._mk(eqn, 0, ins[0].nbits, check=False, ins=ins)
        if name in _FLOAT_MONO:
            f, inc = _FLOAT_MONO[name]
            a = ins[0]
            try:
                v0, v1 = f(float(a.lo)), f(float(a.hi))
            except (OverflowError, ValueError):
                return self._top_out(eqn)
            lo, hi = (v0, v1) if inc else (v1, v0)
            return self._mk(eqn, min(lo, hi), max(lo, hi), check=False,
                            ins=ins)
        if name in ("sin", "cos", "erf"):
            return self._mk(eqn, -1.0, 1.0, check=False, ins=ins)
        if name == "while":
            return self._while(eqn, ins)
        if name == "scan":
            return self._scan(eqn, ins)
        if name == "cond":
            return self._cond(eqn, ins)
        if sub is not None:
            return self._call(eqn, sub, ins)
        self.note_unknown(name)
        return self._top_out(eqn)

    # ------------------------------------------------------- arith family --
    def _arith(self, eqn, ins, name):
        a, b = ins
        odt = np.dtype(eqn.outvars[0].aval.dtype)
        if not a.is_int or not b.is_int or odt.kind == "f":
            if name == "add":
                lo, hi = _corners(a, b, lambda x, y: x + y)
            elif name == "sub":
                lo, hi = _corners(a, b, lambda x, y: x - y)
            else:
                lo, hi = _corners(a, b, lambda x, y: x * y)
            return self._mk(eqn, lo, hi, check=False, ins=ins)
        if name == "add":
            lo, hi = a.lo + b.lo, a.hi + b.hi
            what = "integer sum"
        elif name == "sub":
            lo, hi = a.lo - b.hi, a.hi - b.lo
            what = "integer difference"
            if odt.kind == "u" and lo < 0:
                msg = (f"possible unsigned underflow: {a.describe()} - "
                       f"{b.describe()} reaches {lo}")
                if hi < 0:       # certain underflow — no guard saves this
                    self.flag("overflow", msg, eqn, ins)
                    return self._top_out(eqn)
                shape = tuple(eqn.outvars[0].aval.shape)
                pend = ((self._resolve_key(eqn.invars[0]),
                         self._resolve_key(eqn.invars[1])),
                        "overflow", msg, _eqn_str(eqn, ins), _src_of(eqn))
                val = AbsVal(odt, shape, 0, min(hi, _iinfo(odt)[1])).norm()
                return [dataclasses.replace(val, pending=pend)]
        else:
            lo, hi = _corners(a, b, lambda x, y: x * y)
            what = "integer product"
        return self._mk(eqn, lo, hi, ins=ins, what=what)

    def _bitwise(self, eqn, ins, name):
        odt = np.dtype(eqn.outvars[0].aval.dtype)
        if odt.kind == "b":
            return [AbsVal(odt, tuple(eqn.outvars[0].aval.shape), 0, 1, 1)]
        if name == "not":
            a = ins[0]
            if a.lo >= 0 and odt.kind == "u":
                m = _iinfo(odt)[1]
                return self._mk(eqn, m - a.hi, m - a.lo, check=False, ins=ins)
            return self._top_out(eqn)
        a, b = ins
        if name == "and":
            if a.lo >= 0 and b.lo >= 0:
                bits = None
                if a.bits is not None and b.bits is not None:
                    bits = a.bits & b.bits
                elif a.bits is not None:
                    bits = a.bits
                elif b.bits is not None:
                    bits = b.bits
                hi = min(a.hi, b.hi)
                if bits is not None:
                    hi = min(hi, bits)
                return self._mk(eqn, 0, hi, bits, check=False, ins=ins)
            # x & m with m >= 0 clears the sign bit too: result in [0, m]
            # even for possibly-negative x (two's complement AND keeps only
            # bits m has set) — the fraction extract `ls & (2^F - 1)` on the
            # signed log difference lands here.
            for m in (a, b):
                if m.lo >= 0:
                    bits = m.bits if m.bits is not None else _mask_for(m.hi)
                    return self._mk(eqn, 0, min(m.hi, bits), bits,
                                    check=False, ins=ins)
            return self._top_out(eqn)
        if name == "xor":
            if a.lo >= 0 and b.lo >= 0 and a.bits is not None \
                    and b.bits is not None:
                bits = a.bits | b.bits
                return self._mk(eqn, 0, bits, bits, check=False, ins=ins)
            return self._top_out(eqn)
        # name == "or": the repo invariant — every integer OR is a disjoint
        # bit-field union (lane packing, log packing, region indices)
        disjoint = (a.lo >= 0 and b.lo >= 0 and a.bits is not None
                    and b.bits is not None and (a.bits & b.bits) == 0)
        if not disjoint:
            self.flag("lane-overlap",
                      f"integer OR operands not provably disjoint: "
                      f"{a.describe()} | {b.describe()}", eqn, ins)
            return self._top_out(eqn)
        bits = a.bits | b.bits
        return self._mk(eqn, max(a.lo, b.lo), min(a.hi + b.hi, bits), bits,
                        check=False, ins=ins)

    def _shift(self, eqn, ins, name):
        a, amt = ins
        nbits = a.nbits
        odt = np.dtype(eqn.outvars[0].aval.dtype)
        if not (amt.is_int and amt.lo >= 0 and amt.hi <= nbits - 1):
            self.flag("shift-range",
                      f"shift amount {amt.describe()} not provably in "
                      f"[0, {nbits - 1}]", eqn, ins)
            return self._top_out(eqn)
        if a.lo < 0:
            if name == "shift_right_arithmetic":
                # Python's >> floors like shra; corners are sound because
                # the shift is monotone in the value for each fixed amount.
                c = [x >> s for x in (int(a.lo), int(a.hi))
                     for s in (int(amt.lo), int(amt.hi))]
                return self._mk(eqn, min(c), max(c), check=False, ins=ins)
            return self._top_out(eqn)
        span = int(amt.hi) - int(amt.lo)
        dlo, dhi = _iinfo(odt)
        mask = dhi if odt.kind == "i" else (1 << nbits) - 1
        if name == "shift_left":
            bits = None
            if a.bits is not None and span <= 64:
                bits = 0
                for s in range(int(amt.lo), int(amt.hi) + 1):
                    bits |= (a.bits << s) & mask
            hi = a.hi << int(amt.hi)
            if hi <= dhi:
                return self._mk(eqn, a.lo << int(amt.lo), hi, bits,
                                check=False, ins=ins)
            # modular wrap is defined; saturation selects downstream decide
            return self._mk(eqn, 0, mask, bits, check=False, ins=ins)
        bits = None
        if a.bits is not None and span <= 64:
            bits = 0
            for s in range(int(amt.lo), int(amt.hi) + 1):
                bits |= a.bits >> s
        return self._mk(eqn, a.lo >> int(amt.hi), a.hi >> int(amt.lo), bits,
                        check=False, ins=ins)

    def _convert(self, eqn, ins):
        a = ins[0]
        odt = np.dtype(eqn.params["new_dtype"])
        shape = tuple(eqn.outvars[0].aval.shape)
        if odt.kind == "b":
            return [AbsVal(odt, shape, 0, 1, 1)]
        if odt.kind == "f":
            return [AbsVal(odt, shape, float(a.lo), float(a.hi))]
        # integer destination
        if not a.is_int:  # float -> int truncates toward zero
            if not (math.isfinite(a.lo) and math.isfinite(a.hi)):
                self.flag("overflow",
                          f"unbounded float {a.describe()} converted to "
                          f"{odt.name}", eqn, ins)
                return [top(odt, shape)]
            lo, hi = int(a.lo), int(a.hi)
        else:
            lo, hi = a.lo, a.hi
        dlo, dhi = _iinfo(odt)
        if lo < dlo or hi > dhi:
            crossing = (odt.kind == "u" and lo < 0) or \
                       (odt.kind == "i" and a.is_int and a.kind == "u"
                        and hi > dhi)
            self.flag("signedness" if crossing else "overflow",
                      f"conversion of [{lo}, {hi}] to {odt.name} "
                      f"[{dlo}, {dhi}] can change the value", eqn, ins)
            return [top(odt, shape)]
        bits = a.bits if a.is_int else None
        return [AbsVal(odt, shape, lo, hi, bits).norm()]

    def _div(self, eqn, ins):
        a, b = ins
        odt = np.dtype(eqn.outvars[0].aval.dtype)
        if odt.kind == "f":
            if b.lo > 0 or b.hi < 0:
                lo, hi = _corners(a, b, lambda x, y: x / y if y else math.inf)
                return self._mk(eqn, lo, hi, check=False, ins=ins)
            return self._top_out(eqn)
        if a.is_int and b.is_int and a.lo >= 0 and b.lo >= 1:
            return self._mk(eqn, a.lo // b.hi, a.hi // b.lo, check=False,
                            ins=ins)
        return self._top_out(eqn)

    def _pow(self, eqn, ins):
        a = ins[0]
        if eqn.primitive.name == "integer_pow":
            y = int(eqn.params["y"])
            if y >= 0 and a.is_int:
                vals = [a.lo ** y, a.hi ** y]
                lo = 0 if (y % 2 == 0 and a.lo <= 0 <= a.hi) else min(vals)
                return self._mk(eqn, lo, max(vals), ins=ins,
                                what=f"integer_pow({y})")
            if y >= 0:
                vals = [float(a.lo) ** y, float(a.hi) ** y]
                lo = 0.0 if (y % 2 == 0 and a.lo <= 0 <= a.hi) else min(vals)
                return self._mk(eqn, lo, max(vals), check=False, ins=ins)
        return self._top_out(eqn)

    def _reduce_sum(self, eqn, ins):
        a = ins[0]
        out = eqn.outvars[0].aval
        n = int(np.prod(a.shape) // max(int(np.prod(out.shape)), 1))
        n = max(n, 1)
        return self._mk(eqn, a.lo * n, a.hi * n, ins=ins,
                        what=f"sum of {n} elements")

    def _dot_general(self, eqn, ins):
        a, b = ins
        (lc, _), _ = eqn.params["dimension_numbers"]
        k = int(np.prod([a.shape[d] for d in lc])) if lc else 1
        k = max(k, 1)
        plo, phi = _corners(a, b, lambda x, y: x * y)
        return self._mk(eqn, plo * k, phi * k, ins=ins,
                        what=f"dot_general contraction over {k}")

    def _gather(self, eqn, ins):
        operand, idx = ins
        out = eqn.outvars[0].aval
        if len(operand.shape) == 1 and idx.is_int:
            t = int(operand.shape[0])
            if not (idx.lo >= 0 and idx.hi <= t - 1):
                self.flag("gather-bounds",
                          f"table index {idx.describe()} not provably in "
                          f"[0, {t - 1}]", eqn, ins)
        return [operand.with_shape(tuple(out.shape))]

    # ----------------------------------------------------------- contracts --
    def _contract(self, eqn, ins):
        val = ins[0]
        p = eqn.params
        if p["phase"] == "require":
            ok = (val.is_int and val.lo >= p["lo"] and val.hi <= p["hi"])
            if not ok:
                self.flag("lane-domain",
                          f"{p['what']}: operand {val.describe()} not "
                          f"provably within [{p['lo']}, {p['hi']}]", eqn, ins)
            if p["assume"]:
                self.scopes.append((frozenset(p["assume"]), p["what"]))
            return [_refine(val, p["lo"], p["hi"])]
        # ensure: closes the innermost assume scope, refines to declared
        if self.scopes:
            _, what = self.scopes.pop()
            tag = f"{what} -> {p['what']}" if p["what"] else what
        else:
            tag = p["what"]
        if tag and tag not in self.report.assumed:
            self.report.assumed.append(tag)
        return [_refine(val, p["lo"], p["hi"], p["bits"])]

    # --------------------------------------------------------- control flow --
    def _call(self, eqn, sub, ins):
        jx = sub.jaxpr if isinstance(sub, ClosedJaxpr) else sub
        # bind inner invars to the outer atoms so pending-underflow keys
        # and select predicates match across the call boundary
        for iv, outer in zip(jx.invars, eqn.invars):
            self._alias[iv] = outer
        if isinstance(sub, ClosedJaxpr):
            return self.eval_closed(sub, ins)
        return self.eval_jaxpr(sub, [], ins)

    def _cond(self, eqn, ins):
        branches = eqn.params["branches"]
        results = [self.eval_closed(br, ins[1:]) for br in branches]
        outs = results[0]
        for r in results[1:]:
            outs = [join(a, b) for a, b in zip(outs, r)]
        return outs

    def _while_static(self, eqn, ins):
        """Recognize the fori_loop-shaped while: ``cond = lt(i, N)`` with a
        unit-increment counter carry and exact init. Returns
        (carry_idx, init, bound) or None."""
        p = eqn.params
        cj, bj = p["cond_jaxpr"], p["body_jaxpr"]
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        carry = ins[cn + bn:]
        cjx = cj.jaxpr
        if len(cjx.eqns) != 1 or cjx.eqns[0].primitive.name != "lt":
            return None
        ce = cjx.eqns[0]
        a, b = ce.invars
        if isinstance(a, Literal) or a not in cjx.invars:
            return None
        pos = cjx.invars.index(a)
        if pos < cn:
            return None
        cidx = pos - cn
        if isinstance(b, Literal):
            bound = int(np.asarray(b.val))
        elif b in cjx.invars and cjx.invars.index(b) < cn:
            bv = ins[cjx.invars.index(b)]
            if bv.lo != bv.hi:
                return None
            bound = int(bv.lo)
        elif b in cjx.constvars:
            bound = int(np.asarray(cj.consts[cjx.constvars.index(b)]))
        else:
            return None
        # counter carry must step by a literal 1 in the body
        bjx = bj.jaxpr
        ov = bjx.outvars[cidx]
        step_ok = False
        for be in bjx.eqns:
            if ov in be.outvars and be.primitive.name == "add":
                x, y = be.invars
                lit = y if isinstance(y, Literal) else (
                    x if isinstance(x, Literal) else None)
                var = x if lit is y else y
                if lit is not None and int(np.asarray(lit.val)) == 1 \
                        and var is bjx.invars[bn + cidx]:
                    step_ok = True
                break
        if not step_ok:
            return None
        init = carry[cidx]
        if init.lo != init.hi:
            return None
        if not (0 < bound - init.lo <= _LOOP_CAP):
            return None
        return cidx, int(init.lo), bound

    def _while(self, eqn, ins):
        p = eqn.params
        bj = p["body_jaxpr"]
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        bconsts = ins[cn:cn + bn]
        carry = list(ins[cn + bn:])
        static = self._while_static(eqn, ins)
        if static is not None:
            cidx, i, bound = static
            cv = carry[cidx]
            while i < bound:
                carry[cidx] = _exact(cv.dtype, cv.shape, i)
                carry = list(self.eval_closed(bj, bconsts + carry))
                i += 1
            carry[cidx] = _exact(cv.dtype, cv.shape, bound)
            return carry
        return self._widen_loop(bj, bconsts, carry,
                                note="while: trip count not static — widened")

    def _scan(self, eqn, ins):
        p = eqn.params
        closed = p["jaxpr"]
        length = int(p["length"])
        nc, ncar = p["num_consts"], p["num_carry"]
        consts = ins[:nc]
        carry = list(ins[nc:nc + ncar])
        xel = [x.with_shape(tuple(x.shape[1:])) for x in ins[nc + ncar:]]
        outvars = eqn.outvars
        if length == 0:
            return [top(v.aval.dtype, v.aval.shape) for v in outvars]
        if length <= _LOOP_CAP:
            ys = None
            for _ in range(length):
                outs = self.eval_closed(closed, consts + carry + xel)
                carry = list(outs[:ncar])
                yel = outs[ncar:]
                ys = yel if ys is None else [join(a, b)
                                             for a, b in zip(ys, yel)]
            stacked = [y.with_shape(tuple(v.aval.shape))
                       for y, v in zip(ys, outvars[ncar:])]
            return carry + stacked
        carry = self._widen_loop(
            closed, consts, carry, extra=xel,
            note=f"scan: length {length} > {_LOOP_CAP} — widened")
        outs = self.eval_closed(closed, consts + carry + xel)
        stacked = [y.with_shape(tuple(v.aval.shape))
                   for y, v in zip(outs[ncar:], outvars[ncar:])]
        return carry + stacked

    def _widen_loop(self, closed, consts, carry, extra=(), note=""):
        """Sound fallback: widen unstable carries to top, re-evaluate."""
        if note and note not in self.report.unknown_prims:
            self.report.unknown_prims.append(note)
        ncar = len(carry)
        for _ in range(3):
            outs = self.eval_closed(closed, consts + carry + list(extra))
            changed = False
            nxt = []
            for c, o in zip(carry, outs[:ncar]):
                j = join(c, o.with_shape(c.shape))
                if (j.lo, j.hi, j.bits) != (c.lo, c.hi, c.bits):
                    changed = True
                    nxt.append(top(c.dtype, c.shape))
                else:
                    nxt.append(c)
            carry = nxt
            if not changed:
                break
        outs = self.eval_closed(closed, consts + carry + list(extra))
        return [join(c, o.with_shape(c.shape))
                for c, o in zip(carry, outs[:ncar])]


# ============================================================== the driver ==
def check_case(case: TraceCase) -> CaseReport:
    """Trace one case under faithful semantics and interpret it abstractly."""
    import jax

    from repro.core.annotations import analysis_tracing
    from repro.core.fastpath import faithful_mode

    report = CaseReport(label=case.label, requires_x64=case.requires_x64,
                        note=case.note)
    if case.requires_x64 and not jax.config.read("jax_enable_x64"):
        report.note = (case.note + "; " if case.note else "") + \
            "skipped: requires x64 (jax_enable_x64 is off)"
        return report
    args = [jax.ShapeDtypeStruct(tuple(a.shape), np.dtype(a.dtype))
            for a in case.args]
    try:
        with faithful_mode(True), analysis_tracing():
            closed = jax.make_jaxpr(case.fn)(*args)
    except Exception as e:  # trace failure is itself a finding
        report.findings.append(Finding(
            "overflow", case.label,
            f"trace failed: {type(e).__name__}: {e}"))
        return report
    interp = _Interp(report, case.label)
    outs = interp.eval_jaxpr(closed.jaxpr, closed.consts,
                             [a.absval() for a in case.args])
    for o in outs:                      # escaped deferred findings report here
        interp._settle(o)
    report.findings.sort(key=Finding.sort_key)
    report.assumed.sort()
    report.unknown_prims.sort()
    if interp.scopes:
        report.findings.append(Finding(
            "lane-domain", case.label,
            f"{len(interp.scopes)} require_range scope(s) never closed by "
            f"ensure_range"))
    return report


@dataclass
class MatrixResult:
    """Everything one full ops x widths analyzer run produced."""
    reports: list = field(default_factory=list)       # CaseReport
    skips: list = field(default_factory=list)         # (op, width, reason)
    gaps: list = field(default_factory=list)          # ops missing metadata

    @property
    def findings(self):
        return [f for r in self.reports for f in r.findings]

    @property
    def ok(self) -> bool:
        return not self.findings and not self.gaps


def run_matrix(ops=None, widths=None) -> MatrixResult:
    """Run the analyzer over registered ops x SUPPORTED_WIDTHS."""
    from repro.core.mitchell import SUPPORTED_WIDTHS
    from repro.kernels import registry

    widths = tuple(widths) if widths else tuple(sorted(SUPPORTED_WIDTHS))
    result = MatrixResult()
    for impl in registry.all_ops():
        if ops and impl.name not in ops:
            continue
        if impl.analysis is None:
            result.gaps.append(impl.name)
            continue
        for w in widths:
            cases = impl.analysis(w)
            if cases is None:
                result.skips.append((impl.name, w, "width not supported"))
                continue
            if isinstance(cases, str):
                result.skips.append((impl.name, w, cases))
                continue
            for case in cases:
                result.reports.append(check_case(case))
    result.reports.sort(key=lambda r: r.label)
    result.skips.sort()
    result.gaps.sort()
    return result


def verdict_for(op_name: str, width: int) -> str:
    """One-line analyzer verdict for (op, width) — used by hlo_inspect."""
    res = run_matrix(ops=[op_name], widths=[width])
    if op_name in res.gaps:
        return "no-analysis-metadata"
    if not res.reports and res.skips:
        return f"skipped: {res.skips[0][2]}"
    n = len(res.findings)
    if n:
        return f"UNSAFE: {n} finding(s) — run `python -m repro.analysis`"
    skipped = sum(1 for r in res.reports if "skipped" in r.note)
    proved = len(res.reports) - skipped
    return f"proved safe ({proved} case(s), {skipped} skipped)"


def to_json(result: MatrixResult, lint_findings=()) -> dict:
    return {
        "cases": [{
            "label": r.label,
            "ok": r.ok,
            "note": r.note,
            "requires_x64": r.requires_x64,
            "findings": [{
                "rule": f.rule, "message": f.message,
                "eqn": f.eqn, "source": f.source,
            } for f in r.findings],
            "assumed": list(r.assumed),
            "unknown_primitives": list(r.unknown_prims),
        } for r in result.reports],
        "skips": [{"op": o, "width": w, "reason": why}
                  for o, w, why in result.skips],
        "coverage_gaps": list(result.gaps),
        "lint": [{
            "rule": f.rule, "ctx": f.ctx, "message": f.message,
            "source": f.source,
        } for f in lint_findings],
    }


def render_text(result: MatrixResult, lint_findings=()) -> str:
    lines = ["simdive widthcheck report", "=" * 25, ""]
    n_ok = sum(1 for r in result.reports if r.ok and "skipped" not in r.note)
    n_skip = sum(1 for r in result.reports if "skipped" in r.note)
    n_bad = sum(1 for r in result.reports if not r.ok)
    lines.append(f"cases: {len(result.reports)}  proved: {n_ok}  "
                 f"skipped: {n_skip + len(result.skips)}  "
                 f"unsafe: {n_bad}  lint: {len(lint_findings)}")
    lines.append("")
    for r in result.reports:
        mark = "FAIL" if not r.ok else (
            "skip" if "skipped" in r.note else "  ok")
        note = f"  ({r.note})" if r.note else ""
        lines.append(f"[{mark}] {r.label}{note}")
        for f in r.findings:
            lines.append(f"    {f.render()}")
        for a in r.assumed:
            lines.append(f"    assumed contract: {a}")
        for u in r.unknown_prims:
            lines.append(f"    widened: {u}")
    if result.skips:
        lines.append("")
        lines.append("declared skips:")
        for o, w, why in result.skips:
            lines.append(f"  {o} w{w}: {why}")
    if result.gaps:
        lines.append("")
        lines.append("coverage gaps (registered ops without analysis "
                     "metadata):")
        for g in result.gaps:
            lines.append(f"  {g}")
    if lint_findings:
        lines.append("")
        lines.append("lint:")
        for f in lint_findings:
            lines.append(f"  {f.render()}")
    lines.append("")
    return "\n".join(lines)
