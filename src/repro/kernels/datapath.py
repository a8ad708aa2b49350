"""Composable SIMDive datapath stages — the one shared log front-end.

The paper's core claim (and RAPID's, for the pipelined variants) is that a
single Mitchell log datapath — LOD -> log conversion -> ternary add with a
64-region correction -> anti-log — serves multiplication, division, SISD and
SIMD modes alike; only the adder input wiring differs. This module is that
claim expressed as code: every kernel body (`elemwise`, `packed_simd`,
`logmatmul`) and every pure-jnp oracle (`ref`) composes the *same* stage
functions, so the datapath exists exactly once.

Stage map (FPGA block -> function):

    LOD + log conversion            lod_log
    region index + coefficient LUT  region_corr        (corr_lookup inside)
    ternary add + anti-log, mul     antilog_mul
    ternary add + anti-log, div     antilog_div
    fused correct + anti-log        log_mul / log_div  (one pass, RAPID)
    sign XOR network                sign_split / sign_join
    sub-word lane wiring            lane_expand / lane_repack
    whole SISD unit (Fig. 2b)       lane_op            (composes the above)

Every stage is plain traceable jnp on values already in registers/VMEM —
no jit, no pallas_call, no host logic — so identical code runs inside a
compiled Pallas kernel body, under the Pallas interpreter, and as the
bit-exact reference oracle. The underlying integer primitives come from
:mod:`repro.core.mitchell`; this module must never import
:mod:`repro.core.simdive` (which itself builds on these stages).
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.error_lut import build_table, region_index
from repro.core.fastpath import fastpath_enabled
from repro.faults.inject import apply_lane_faults, faults_enabled
from repro.core.mitchell import (
    frac_bits,
    mitchell_antilog_div,
    mitchell_antilog_mul,
    mitchell_log,
    work_dtype,
)

__all__ = [
    "fraction_mask",
    "lod_log",
    "log8_table",
    "corr_lookup",
    "region_corr",
    "split_tables",
    "op_table",
    "op_table_host",
    "antilog_mul",
    "antilog_div",
    "log_mul",
    "log_div",
    "sign_split",
    "sign_join",
    "lane_expand",
    "lane_repack",
    "lane_op",
    "to_float32",
    "I0",
]


# ------------------------------------------------------------- front end --
def fraction_mask(width: int, dtype=jnp.uint32):
    """Mask selecting the F-bit fraction field of a log value."""
    F = frac_bits(width)
    return (jnp.asarray(1, dtype) << jnp.asarray(F, dtype)) - jnp.asarray(1, dtype)


@lru_cache(maxsize=None)
def _log8_host():
    import numpy as np

    # host-side faithful LOD + log over the whole 8-bit lane domain; the
    # fast paths must never feed their own oracle table
    a = np.arange(256, dtype=np.int64)
    k = np.zeros(256, dtype=np.int64)
    for step in (4, 2, 1):
        m = (a >> k) >= (1 << step)
        k[m] += step
    F = frac_bits(8)
    return ((k << F) | ((a ^ (1 << k)) << (F - k))).astype(np.uint32)


def log8_table() -> jnp.ndarray:
    """256-entry LUT of the full width-8 log value ``L = (k << F) | x_fp``."""
    return jnp.asarray(_log8_host())


def lod_log(a: jnp.ndarray, width: int, *,
            in_kernel: bool = False, lut: bool = False) -> jnp.ndarray:
    """Stage 1: LOD + log conversion, ``L = (k << F) | x_fp``.

    Input must already be in the lane work dtype (uint32 for widths <= 16).

    Fast path (``in_kernel=False`` and fast paths enabled): the ``clz``
    LOD — one primitive instead of the 5-step masked shift cascade, and it
    stays inside XLA's fused elementwise loop. ``lut=True`` selects the
    256-entry width-8 LUT gather instead (the whole stage as one gather);
    it is bit-identical and kept as an available form, but measured
    *slower* composed on CPU XLA — the gather breaks elementwise fusion,
    which costs more than the cascade it saves (see kernels/README.md).
    Kernel bodies pass ``in_kernel=True`` and keep the Mosaic-safe
    masked-shift cascade (gathers/clz are host-cheap, not TPU-kernel-safe).

    Fault hook: site='log' upsets land on this stage's output register
    ``L`` (see :mod:`repro.faults.inject`); disarmed the hook is a no-op.
    """
    if in_kernel or not fastpath_enabled():
        L = mitchell_log(a, width, fast=False)
    elif lut and width == 8:
        L = log8_table()[a].astype(a.dtype)
    else:
        L = mitchell_log(a, width, fast=True)
    if faults_enabled():
        L = apply_lane_faults(L, site="log", width=width)
    return L


# ------------------------------------------------------------ correction --
def _static_zero_table(tab, in_kernel: bool) -> bool:
    """True when the coefficient table is a host-known all-zero constant
    (coeff_bits = 0, plain Mitchell) and we are outside a kernel body with
    fast paths on — the one predicate behind every skip-the-correction
    fast path (adding a zero coefficient is bit-invisible downstream)."""
    return (not in_kernel and fastpath_enabled()
            and not isinstance(tab, jax.core.Tracer) and not tab.any())


def _select_lookup(idx: jnp.ndarray, tab) -> jnp.ndarray:
    """``tab[idx]`` as a binary select tree over the table's constants.

    Kernel bodies get the coefficient table as a trace-time constant (it
    is fixed by the op's spec), so the lookup needs no gather and no
    (..., T) one-hot intermediate: one select per tree node, with every
    leaf a scalar. Subtrees whose entries are all equal collapse to their
    constant, so sparse tables cost less.
    """
    vals = np.asarray(tab).astype(np.int32)
    T = vals.shape[0]
    nbits = max(T - 1, 0).bit_length()
    bits = [(idx & np.int32(1 << b)) != 0 for b in range(nbits)]

    def node(lo: int, size: int, b: int):
        seg = vals[lo:lo + size]
        if (seg == seg[0]).all():
            return seg[0]
        half = size // 2
        return jnp.where(bits[b], node(lo + half, half, b - 1),
                         node(lo, half, b - 1))

    return jnp.broadcast_to(node(0, 1 << nbits, nbits - 1), idx.shape)


def corr_lookup(idx: jnp.ndarray, tab: jnp.ndarray, width: int, *,
                in_kernel: bool = False) -> jnp.ndarray:
    """Gather ``tab[idx]`` (tab: (T,) int32, idx: any shape int32) -> int32.

    Inside kernel bodies (``in_kernel=True``) the table is a trace-time
    constant and the lookup is a select tree (:func:`_select_lookup`):
    Mosaic has no vector gather, and a one-hot product would hold a
    (..., T) tile in VMEM. The faithful oracle (fast paths off) keeps the
    one-hot dot product — exact, since |coeff| < 2^14 << 2^24 (the f32
    integer-exact range). Otherwise a plain gather is exact and cheapest.
    """
    T = tab.shape[0]
    if _static_zero_table(tab, in_kernel):
        # the gather of a constant zero table is not XLA-foldable the way
        # the one-hot product is, so fold it here
        return jnp.zeros(idx.shape, jnp.int32)
    if in_kernel:
        return _select_lookup(idx, tab)
    if width <= 16 and not fastpath_enabled():
        onehot = (idx[..., None] == jnp.arange(T, dtype=jnp.int32)).astype(
            jnp.float32
        )
        vals = jnp.einsum(
            "...t,t->...", onehot, tab.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        return vals.astype(jnp.int32)
    return tab[idx]


def region_corr(la: jnp.ndarray, lb: jnp.ndarray, tab: jnp.ndarray,
                width: int, index_bits: int = 3,
                gate: jnp.ndarray | None = None, *,
                in_kernel: bool = False) -> jnp.ndarray:
    """Stage 2: region index from both log fractions + coefficient lookup.

    ``gate`` (optional bool array): zero-detection — a False lane gets a
    zero coefficient, mirroring the FPGA's zero-flag bypass of the LUT.
    """
    if _static_zero_table(tab, in_kernel):
        # all-zero table: skip the region index too (see corr_lookup)
        return jnp.zeros(jnp.broadcast_shapes(la.shape, lb.shape), jnp.int32)
    m = fraction_mask(width, la.dtype)
    idx = region_index(la & m, lb & m, width, index_bits)
    corr = corr_lookup(idx, tab, width, in_kernel=in_kernel)
    if gate is not None:
        corr = jnp.where(gate, corr, jnp.zeros_like(corr))
    return corr


def split_tables(tab: jnp.ndarray, index_bits: int, op: str):
    """Mixed-functionality table wiring: '[mul | div]' -> per-half views."""
    if op != "mixed":
        return tab, tab
    T = 1 << (2 * index_bits)
    return tab[:T], tab[T:]


def op_table_host(op: str, width: int, coeff_bits: int,
                  index_bits: int = 3) -> np.ndarray:
    """The coefficient table an op needs ('mixed' -> [mul|div]) as host
    numpy: kernel bodies bake it in as trace-time constants."""
    if op == "mixed":
        return np.concatenate([
            build_table("mul", width, coeff_bits, index_bits),
            build_table("div", width, coeff_bits, index_bits),
        ])
    return build_table(op, width, coeff_bits, index_bits)


def op_table(op: str, width: int, coeff_bits: int,
             index_bits: int = 3) -> jnp.ndarray:
    """Materialize the coefficient table an op needs ('mixed' -> [mul|div])."""
    return jnp.asarray(op_table_host(op, width, coeff_bits, index_bits))


# -------------------------------------------------------------- anti-log --
def antilog_mul(la: jnp.ndarray, lb: jnp.ndarray, width: int,
                corr: jnp.ndarray | None = None, round_out: bool = False,
                zero: jnp.ndarray | None = None, *,
                in_kernel: bool = False) -> jnp.ndarray:
    """Stage 3a: ternary add + product anti-log, with zero-flag bypass.

    ``zero`` marks lanes where either operand is 0 (x * 0 = 0).
    """
    p = mitchell_antilog_mul(la, lb, width, corr=corr, round_out=round_out,
                             fast=False if in_kernel else None)
    if zero is not None:
        p = jnp.where(zero, jnp.zeros_like(p), p)
    return p


def antilog_div(la: jnp.ndarray, lb: jnp.ndarray, width: int,
                corr: jnp.ndarray | None = None, frac_out: int = 0,
                round_out: bool = False,
                num_zero: jnp.ndarray | None = None,
                den_zero: jnp.ndarray | None = None, *,
                in_kernel: bool = False) -> jnp.ndarray:
    """Stage 3b: ternary subtract + quotient anti-log, with zero flags.

    x / 0 saturates to the all-ones bus value (divider-IP overflow-flag
    convention); 0 / x = 0 — applied in that order so 0 / 0 = 0.
    """
    q = mitchell_antilog_div(la, lb, width, corr=corr, frac_out=frac_out,
                             round_out=round_out,
                             fast=False if in_kernel else None)
    if den_zero is not None:
        q = jnp.where(den_zero, ~jnp.zeros_like(q), q)
    if num_zero is not None:
        q = jnp.where(num_zero, jnp.zeros_like(q), q)
    return q


# --------------------------------------------------------- fused log ops --
def log_mul(la: jnp.ndarray, lb: jnp.ndarray, tab: jnp.ndarray, width: int,
            index_bits: int = 3, round_out: bool = False,
            zero: jnp.ndarray | None = None, *,
            in_kernel: bool = False) -> jnp.ndarray:
    """Fused stages 2+3a: region lookup + ternary add + anti-log, one pass.

    The RAPID pipelining observation (arXiv:2206.13970): the correction
    gather and the anti-log shift read the *same* log words, so issuing
    them as one stage keeps the tile in registers/VMEM between them — the
    coefficient tensor is consumed by the ternary add inside the same
    expression instead of being materialized as a separate kernel stage.
    Bit-identical to ``region_corr`` followed by ``antilog_mul``.
    """
    corr = region_corr(la, lb, tab, width, index_bits,
                       gate=None if zero is None else ~zero,
                       in_kernel=in_kernel)
    if _static_zero_table(tab, in_kernel):
        corr = None          # skip the ternary add's widen/clip entirely
    return antilog_mul(la, lb, width, corr=corr, round_out=round_out,
                       zero=zero, in_kernel=in_kernel)


def log_div(la: jnp.ndarray, lb: jnp.ndarray, tab: jnp.ndarray, width: int,
            index_bits: int = 3, frac_out: int = 0, round_out: bool = False,
            num_zero: jnp.ndarray | None = None,
            den_zero: jnp.ndarray | None = None, *,
            in_kernel: bool = False) -> jnp.ndarray:
    """Fused stages 2+3b: region lookup + ternary subtract + anti-log.

    One-pass divider analogue of :func:`log_mul`; bit-identical to
    ``region_corr`` followed by ``antilog_div``.
    """
    gate = None
    if num_zero is not None or den_zero is not None:
        nz = jnp.zeros(jnp.broadcast_shapes(la.shape, lb.shape), bool)
        if num_zero is not None:
            nz = nz | num_zero
        if den_zero is not None:
            nz = nz | den_zero
        gate = ~nz
    corr = region_corr(la, lb, tab, width, index_bits, gate=gate,
                       in_kernel=in_kernel)
    if _static_zero_table(tab, in_kernel):
        corr = None
    return antilog_div(la, lb, width, corr=corr, frac_out=frac_out,
                       round_out=round_out, num_zero=num_zero,
                       den_zero=den_zero, in_kernel=in_kernel)


# ------------------------------------------------------------------ signs --
def sign_split(x: jnp.ndarray, width: int):
    """Signed int -> (unsigned magnitude clamped to the lane, sign {-1,+1}).

    The log datapath is unsigned; signs travel outside it and are XORed
    back on at the output, like every sign-magnitude log multiplier.
    """
    sign = jnp.where(x < 0, jnp.int32(-1), jnp.int32(1))
    lim = (1 << width) - 1
    if lim <= jnp.iinfo(x.dtype).max:
        # clamp in the signed domain before abs: no unsigned min (Mosaic
        # cannot lower one), and the most negative int still maps to the
        # lane maximum. A lane wider than the carrier needs no clamp.
        lim = jnp.asarray(lim, x.dtype)
        x = jnp.clip(x, -lim, lim)
    return jnp.abs(x).astype(jnp.uint32), sign


def sign_join(mag: jnp.ndarray, sign: jnp.ndarray) -> jnp.ndarray:
    """Reattach an XORed sign product to an unsigned datapath result."""
    return mag.astype(sign.dtype) * sign


# ------------------------------------------------------------ lane wiring --
def lane_expand(words: jnp.ndarray, width: int) -> list[jnp.ndarray]:
    """Split packed uint32 words into their sub-word lanes (little-endian).

    A word's nibbles *are* its lanes' nibbles, so this is one masked shift
    cascade over the whole tile — the software rendition of the FPGA's
    shared nibble LODs.
    """
    lpw = 32 // width
    mask = jnp.uint32((1 << width) - 1)
    return [(words >> jnp.uint32(width * i)) & mask for i in range(lpw)]


def lane_repack(lanes: list[jnp.ndarray], owidth: int) -> jnp.ndarray:
    """Repack 2w-bit lane results into uint32 words on the doubled bus.

    Little-endian lane order, interleaved along the last axis: for 8-bit
    inputs, lanes (0, 1) -> output word 2k and lanes (2, 3) -> word 2k+1.
    ``owidth >= 32`` degenerates to one result per output word.

    Fault hook: site='pack' upsets land on the packed output bus words
    (see :mod:`repro.faults.inject`); disarmed the hook is a no-op.
    """
    olpw = max(32 // owidth, 1)
    omask = jnp.uint32((1 << min(owidth, 32)) - 1)
    nw_out = len(lanes) // olpw
    words = []
    for j in range(nw_out):
        w = jnp.zeros_like(lanes[0])
        for i in range(olpw):
            w = w | ((lanes[j * olpw + i] & omask) << jnp.uint32(owidth * i))
        words.append(w)
    lead = lanes[0].shape[:-1]
    out = jnp.stack(words, axis=-1).reshape(*lead, -1)
    if faults_enabled():
        out = apply_lane_faults(out, site="pack", width=owidth)
    return out


# -------------------------------------------------------- composed SISD --
def lane_op(a: jnp.ndarray, b: jnp.ndarray, tab: jnp.ndarray, *, width: int,
            index_bits: int = 3, op: str = "mul", frac_out: int = 0,
            mode: jnp.ndarray | None = None,
            round_out: bool = False,
            in_kernel: bool = False) -> jnp.ndarray:
    """One full SIMDive SISD unit (Fig. 2b): the canonical stage composition.

    ``op``: 'mul' | 'div' | 'mixed'. For 'mixed', ``tab`` is the
    concatenated [mul | div] table pair (see :func:`op_table`) and ``mode``
    selects per element (nonzero => mul) — both halves share the LOD + log
    front-end exactly like the hardware shares everything but the adder's
    2's-complement input. Results come back in the lane work dtype;
    zero semantics: x*0 = 0, x/0 = max, 0/x = 0.

    ``in_kernel=True`` (Pallas kernel bodies) pins every stage to its
    Mosaic-safe faithful form; the default composes the bit-exact fast
    paths when enabled (see :mod:`repro.core.fastpath`).
    """
    if op not in ("mul", "div", "mixed"):
        raise ValueError(f"op must be 'mul' | 'div' | 'mixed', got {op!r}")
    dt = work_dtype(width)
    a = a.astype(dt)
    b = b.astype(dt)
    if in_kernel:
        tab = np.asarray(tab)    # a trace-time constant (see corr_lookup)
    la = lod_log(a, width, in_kernel=in_kernel)
    lb = lod_log(b, width, in_kernel=in_kernel)
    nz = (a != 0) & (b != 0)
    if op == "mul":
        # fused one-pass stage (gather folded into the anti-log add)
        return log_mul(la, lb, tab, width, index_bits,
                       round_out=round_out, zero=~nz, in_kernel=in_kernel)
    if op == "div":
        return log_div(la, lb, tab, width, index_bits, frac_out=frac_out,
                       round_out=round_out, num_zero=a == 0,
                       den_zero=b == 0, in_kernel=in_kernel)
    if _static_zero_table(tab, in_kernel):
        # drop the whole correction stage — corr=None is bit-identical to
        # adding a zero coefficient, and skips the ternary add's signed
        # widen/clip as well as the lookup
        cm = cd = None
    elif op == "mixed" and not in_kernel and fastpath_enabled():
        # selector fast path: the region index is op-independent, and the
        # unselected half's result is discarded by the final `where` — so
        # offset the index into the concatenated [mul | div] table by the
        # mode bit and pay for ONE correction lookup per element instead
        # of computing the unused half's correction too.
        m = fraction_mask(width, la.dtype)
        idx = region_index(la & m, lb & m, width, index_bits)
        T = 1 << (2 * index_bits)
        idx = idx + jnp.where(mode != 0, jnp.int32(0), jnp.int32(T))
        c = corr_lookup(idx, tab, width, in_kernel=in_kernel)
        c = jnp.where(nz, c, jnp.zeros_like(c))
        cm = cd = c
    else:
        tab_m, tab_d = split_tables(tab, index_bits, op)
        cm = region_corr(la, lb, tab_m, width, index_bits, gate=nz,
                         in_kernel=in_kernel)
        cd = region_corr(la, lb, tab_d, width, index_bits, gate=nz,
                         in_kernel=in_kernel)
    p = antilog_mul(la, lb, width, corr=cm, round_out=round_out,
                    zero=~nz, in_kernel=in_kernel)
    q = antilog_div(la, lb, width, corr=cd, frac_out=frac_out,
                    round_out=round_out, num_zero=a == 0,
                    den_zero=b == 0, in_kernel=in_kernel)
    return jnp.where(mode != 0, p, q)


# ---------------------------------------------------------- kernel wiring --
#: an int32 block index: under x64 a Python 0 traces as int64, which
#: Mosaic refuses in an index map
I0 = np.int32(0)


# ---------------------------------------------------------- conversions --
def to_float32(u: jnp.ndarray) -> jnp.ndarray:
    """Unsigned lanes -> float32, rounded once like ``u.astype(float32)``.

    Mosaic has no unsigned -> float cast, so a uint32 value is split into
    16-bit halves that convert exactly through int32; ``hi * 2^16`` is
    exact too, and the one rounding is the final add. uint64 lanes
    (width 32, never inside a TPU kernel) convert directly.
    """
    if u.dtype != jnp.uint32:
        return u.astype(jnp.float32)
    s16 = jnp.uint32(16)
    hi = (u >> s16).astype(jnp.int32).astype(jnp.float32)
    lo = (u & jnp.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    return hi * jnp.float32(65536.0) + lo
