"""Bit-exact fixed-point Mitchell logarithmic multiplier / divider.

This is the arithmetic contract of the SIMDive datapath (paper §3.1/§3.2),
reproduced exactly in vectorized integer JAX so that every error statistic in
the paper (Table 2 ARE/PRE, Fig. 1 heat maps) can be recomputed bit-for-bit.

Format, for lane width ``N`` (8 / 16 / 32):
  * operands are unsigned integers in [1, 2^N - 1]; zero is bypassed by a
    zero flag exactly like the FPGA zero-detection LUT,
  * ``k = floor(log2 A)`` (leading-one position), fraction ``x = A - 2^k``
    left-aligned into ``F = N - 1`` fractional bits,
  * log value ``L = (k << F) | x_fp``  (Q(.F) fixed point),
  * multiply: ``Ls = L1 + L2`` — the binary carry out of the fraction field
    realizes both cases of Eq. (5) automatically,
  * divide:  ``Ls = L1 - L2`` (signed) — the borrow realizes Eq. (6),
  * anti-log with hardware floor semantics:
    ``I = Ls >> F``, ``Xs = Ls & (2^F-1)``, ``result = (2^F + Xs) << I >> F``.

All intermediates fit uint32 for N <= 16 and uint64 for N = 32 (the 32-bit
datapath genuinely needs a 64-bit product, same as the FPGA's output bus).
uint64 paths require ``jax.config.update('jax_enable_x64', True)`` — call
:func:`repro.core.enable_x64` before using width-32 ops on CPU.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .annotations import ensure_range, require_range
from .fastpath import fastpath_enabled

__all__ = [
    "SUPPORTED_WIDTHS",
    "frac_bits",
    "work_dtype",
    "lane_max_float",
    "leading_one",
    "leading_one_cascade",
    "leading_one_clz",
    "mitchell_log",
    "mitchell_antilog_mul",
    "mitchell_antilog_div",
    "mitchell_mul",
    "mitchell_div",
]

SUPPORTED_WIDTHS = (8, 16, 32)


def frac_bits(width: int) -> int:
    """Fraction field width F of the log representation (= N - 1)."""
    if width not in SUPPORTED_WIDTHS:
        raise ValueError(f"width must be one of {SUPPORTED_WIDTHS}, got {width}")
    return width - 1


def work_dtype(width: int):
    """Unsigned working dtype wide enough for the full product."""
    if width <= 16:
        return jnp.uint32
    if not jax.config.read("jax_enable_x64"):
        raise RuntimeError(
            "width-32 Mitchell ops need uint64; call repro.core.enable_x64() first"
        )
    return jnp.uint64


def lane_max_float(width: int) -> float:
    """Largest float32 <= 2^width - 1: the safe clamp bound when quantizing
    floats into a width-bit lane.

    For width > 24 the obvious ``float32(2^width - 1)`` rounds *up* to
    2^width — one past the lane maximum — so a clip against it can admit an
    operand the log datapath's leading-one detector maps to ``k == width``,
    driving the fraction-alignment shift ``F - k`` negative (undefined).
    ``2^width - 2^(width-24)`` is the largest float32 below that (24-bit
    mantissa), and equals 2^width - 1 exactly for width <= 24.
    """
    if width not in SUPPORTED_WIDTHS:
        raise ValueError(f"width must be one of {SUPPORTED_WIDTHS}, got {width}")
    return float((1 << width) - (1 << max(width - 24, 0)))


def _signed(dtype):
    return jnp.int32 if dtype == jnp.uint32 else jnp.int64


def leading_one_cascade(a: jax.Array, width: int) -> jax.Array:
    """Hardware-faithful LOD: branch-free masked shift-accumulate cascade.

    This is the *reference* form (the software rendition of a priority
    LOD tree, ~3 VPU ops per cascade step); the segmented 4-bit LOD of
    the paper lives in :mod:`repro.core.lod` and is tested equivalent.
    """
    dt = a.dtype
    a = a.astype(jnp.uint32) if width <= 16 else a
    k = jnp.zeros(a.shape, jnp.uint32 if width <= 16 else a.dtype)
    v = a
    step = 16
    while step >= 1:
        if step < width:  # skip steps that cannot occur for this width
            mask = v >= jnp.asarray(1, v.dtype) << jnp.asarray(step, v.dtype)
            k = jnp.where(mask, k + jnp.asarray(step, k.dtype), k)
            v = jnp.where(mask, v >> jnp.asarray(step, v.dtype), v)
        step //= 2
    return k.astype(dt)


def leading_one_clz(a: jax.Array, width: int) -> jax.Array:
    """Fast-path LOD: one ``count-leading-zeros`` primitive.

    ``k = (nbits-1) - clz(a)`` for a > 0; the ``min`` clamps the a == 0
    case (clz == nbits) to k == 0, matching the cascade. Bit-identical to
    :func:`leading_one_cascade` over the full lane domain
    (exhaustively tested in tests/test_fastpath.py).
    """
    dt = a.dtype
    wdt = jnp.uint32 if width <= 16 else a.dtype
    v = a.astype(wdt) if width <= 16 else a
    nbits = 8 * jnp.dtype(v.dtype).itemsize
    clz = jax.lax.clz(v)
    top = jnp.asarray(nbits - 1, v.dtype)
    return (top - jnp.minimum(clz, top)).astype(dt)


def leading_one(a: jax.Array, width: int,
                fast: bool | None = None) -> jax.Array:
    """Position of the leading one bit of ``a`` (floor(log2 a)); 0 for a == 0.

    ``fast=None`` resolves from the global fast-path flag
    (:mod:`repro.core.fastpath`); ``fast=False`` forces the
    hardware-faithful cascade (Pallas kernel bodies do this — ``clz`` is
    not in the Mosaic-safe op set the kernels restrict themselves to).
    """
    if fast is None:
        fast = fastpath_enabled()
    if fast:
        return leading_one_clz(a, width)
    return leading_one_cascade(a, width)


def mitchell_log(a: jax.Array, width: int,
                 fast: bool | None = None) -> jax.Array:
    """Fixed-point approximate log2: ``L = (k << F) | ((a ^ 2^k) << (F - k))``.

    Input must already be cast to :func:`work_dtype`(width).
    """
    F = frac_bits(width)
    dt = a.dtype
    # analyzer contract: the packing below is disjoint only relationally
    # (frac < 2^(k+1) left-aligned by F - k), which the non-relational
    # interval x bits domain cannot see. The precondition is *checked*
    # (an operand past the lane maximum is a finding right here); the
    # postcondition is backed by the exhaustive bit-parity suites.
    a = require_range(
        a, hi=(1 << width) - 1, what=f"mitchell_log/{width} lane operand",
        assume=("lane-overlap",))
    k = leading_one(a, width, fast=fast)
    one = jnp.asarray(1, dt)
    frac = a ^ (one << k)                      # strip the leading one
    x_fp = frac << (jnp.asarray(F, dt) - k)    # left-align into F bits
    L = (k << jnp.asarray(F, dt)) | x_fp
    return ensure_range(
        L, hi=width * (1 << F) - 1,
        bits=(1 << (F + max((width - 1).bit_length(), 1))) - 1,
        what=f"mitchell_log/{width} log value")


def _pow2_f32(e: jax.Array) -> jax.Array:
    """Exact float32 power of two 2^e from an int32 exponent field.

    Built by packing ``e + 127`` straight into the f32 exponent bits —
    3 integer ops + a bitcast, no transcendental. Valid for
    e in [-126, 127]; callers clamp.
    """
    bits = (e.astype(jnp.int32) + jnp.int32(127)) << jnp.int32(23)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _antilog_floor_fast(ls: jax.Array, width: int,
                        round_out: bool = False) -> jax.Array:
    """Float32-exact fast path of :func:`_antilog_floor` (width <= 16).

    ``floor((2^F + Xs) * 2^(I-F))`` computed as one float multiply by an
    exact power of two + truncating convert. Exact because the mantissa
    has F+1 <= 17 significant bits (< 2^24, the f32 integer-exact range)
    and the scale is a power of two; the half-LSB rounding carry becomes
    ``+ 0.5`` before the floor (same value, proven exhaustively in
    tests/test_fastpath.py). Saturation is unchanged from the faithful
    path; the clamp of I below only protects the f32 exponent field on
    lanes the saturation ``where`` discards anyway.
    """
    F = frac_bits(width)
    dt = ls.dtype
    fF = jnp.asarray(F, dt)
    I = ls >> fF
    Xs = ls & ((jnp.asarray(1, dt) << fF) - jnp.asarray(1, dt))
    mant = ((jnp.asarray(1, dt) << fF) + Xs).astype(jnp.float32)
    Ic = jnp.minimum(I, jnp.asarray(2 * width, dt)).astype(jnp.int32)
    val = mant * _pow2_f32(Ic - jnp.int32(F))
    if round_out:
        # faithful path adds 1 << (shr-1) to the mantissa when I < F:
        # exactly + 0.5 at the truncated position
        val = val + jnp.where(I < fF, jnp.float32(0.5), jnp.float32(0))
    out = val.astype(dt)                       # truncating convert = floor
    over = I >= jnp.asarray(2 * width, dt)
    if 2 * width == 8 * jnp.dtype(dt).itemsize:
        max_out = ~jnp.asarray(0, dt)
    else:
        max_out = (jnp.asarray(1, dt) << jnp.asarray(2 * width, dt)) \
            - jnp.asarray(1, dt)
    return jnp.where(over, max_out, out)


def _antilog_floor(ls: jax.Array, width: int, round_out: bool = False,
                   fast: bool | None = None) -> jax.Array:
    """Anti-log: ``(2^F + Xs) << I >> F`` without overflow.

    ``ls`` is the (unsigned) summed log value. Handles I >= F by shifting the
    mantissa left by (I - F); I < F by shifting right, exactly the
    barrel-shifter behaviour of the datapath. ``round_out`` adds the half-LSB
    rounding bit at the truncated position (one extra carry-in in hardware);
    plain Mitchell keeps floor semantics.

    ``fast=None`` resolves the bit-exact float32 fast path from the global
    flag for widths <= 16; ``fast=False`` forces the shift ladder (kernel
    bodies, width 32, and the faithful mode).
    """
    if fast is None:
        fast = fastpath_enabled()
    if fast and width <= 16:
        return _antilog_floor_fast(ls, width, round_out=round_out)
    F = frac_bits(width)
    dt = ls.dtype
    # analyzer contract: the saturation select below caps the result at the
    # 2*width-bit bus maximum, but the interval domain loses the
    # mant * 2^shl correlation (worst mant and worst shl never coincide).
    # Precondition: ls is a summed pair of in-range log values plus a
    # sub-2^F correction; postcondition: the bus invariant, backed by the
    # exhaustive w8 / sampled w16+ bit-parity suites.
    ls = require_range(
        ls, hi=(1 << (F + 7)) - 1,
        what=f"antilog/{width} summed log")
    fF = jnp.asarray(F, dt)
    I = ls >> fF
    Xs = ls & ((jnp.asarray(1, dt) << fF) - jnp.asarray(1, dt))
    mant = (jnp.asarray(1, dt) << fF) + Xs     # 1.Xs, F+1 bits
    big = I >= fF
    shl = jnp.where(big, I - fF, jnp.asarray(0, dt))
    shr = jnp.where(big, jnp.asarray(0, dt), fF - I)
    if round_out:
        # 1 << (shr-1), and 0 when shr == 0 (nothing is truncated); no
        # unsigned max — Mosaic cannot lower one on uint32 lanes
        one = jnp.asarray(1, dt)
        mant = mant + ((one << shr) >> one)
    out = (mant << shl) >> shr
    # output-bus saturation: a corrected estimate can overshoot 2^(2*width)
    # even when the true product fits — the paper's §2 "overflow cases" in
    # constant-corrected designs. The hardware bus saturates, never wraps.
    over = I >= jnp.asarray(2 * width, dt)
    if 2 * width == 8 * jnp.dtype(dt).itemsize:
        max_out = ~jnp.asarray(0, dt)
    else:
        max_out = (jnp.asarray(1, dt) << jnp.asarray(2 * width, dt)) \
            - jnp.asarray(1, dt)
    return ensure_range(
        jnp.where(over, max_out, out), hi=(1 << (2 * width)) - 1,
        what=f"antilog/{width} product bus")


def mitchell_antilog_mul(l1: jax.Array, l2: jax.Array, width: int,
                         corr: jax.Array | None = None,
                         round_out: bool = False,
                         fast: bool | None = None) -> jax.Array:
    """Product anti-log of two log values (+ optional signed correction)."""
    dt = l1.dtype
    ls = l1 + l2
    if corr is not None:
        # correction is a signed fixed-point value at F-bit resolution,
        # added in the same "ternary add" as the fraction sum (paper §3.3).
        sdt = _signed(dt)
        # typed zero: a Python 0 is a weak int64 under x64, which a Pallas
        # TPU kernel body cannot convert
        ls = jnp.maximum(ls.astype(sdt) + corr.astype(sdt),
                         jnp.asarray(0, sdt)).astype(dt)
    return _antilog_floor(ls, width, round_out=round_out, fast=fast)


def _antilog_div_fast(ls: jax.Array, width: int, frac_out: int,
                      round_out: bool) -> jax.Array:
    """Float32-exact fast path of the quotient anti-log (width <= 16).

    ``floor((2^F + Xs) * 2^(I + frac_out - F))`` as one float multiply by
    an exact power of two + truncating convert; the rounding carry is
    ``+ 0.5`` before the floor. Exact because the mantissa has F+1 <= 17
    significant bits and, with the caller-checked ``frac_out`` bound, the
    result stays below 2^32 (the faithful uint32 path never wraps there
    either — sh <= frac_out + 1 for in-range log values).
    """
    F = frac_bits(width)
    sdt = ls.dtype                              # signed work dtype
    dt = jnp.uint32
    I = ls >> F
    Xs = ls & ((1 << F) - 1)
    mant = (Xs + (1 << F)).astype(jnp.float32)  # 1.Xs, always positive
    sh = (I + jnp.asarray(frac_out - F, sdt)).astype(jnp.int32)
    # exponent clamp only protects the f32 field: below -31 the faithful
    # path's 31-bit shift clip already floors the value to 0, and the
    # (+0.5 if round_out) term keeps flooring to 0 until sh == -17 at the
    # earliest, so clamped lanes are bit-identical by range.
    val = mant * _pow2_f32(jnp.clip(sh, -64, 64))
    if round_out:
        val = val + jnp.where(sh < 0, jnp.float32(0.5), jnp.float32(0))
    return val.astype(dt)                       # truncating convert = floor


def mitchell_antilog_div(l1: jax.Array, l2: jax.Array, width: int,
                         corr: jax.Array | None = None,
                         frac_out: int = 0,
                         round_out: bool = False,
                         fast: bool | None = None) -> jax.Array:
    """Quotient anti-log. Signed subtraction realizes Eq. (6)'s borrow case.

    The hardware quotient bus keeps fractional bits (the paper evaluates the
    16/8 divider against the *real-valued* quotient): the returned integer is
    ``round_down(Q * 2^frac_out)``. ``frac_out = 0`` gives integer floor
    division. Two's-complement arithmetic gives the positive remainder /
    floored integer part for free, which is exactly Eq. (6)'s borrow case
    (x1 - x2 < 0 with the exponent decremented).

    ``fast=None`` resolves the float32 fast path from the global flag; it
    engages only when the result provably fits the 32-bit bus
    (``width + frac_out <= 31``), else the shift ladder runs.
    """
    F = frac_bits(width)
    dt = l1.dtype
    sdt = _signed(dt)
    ls = l1.astype(sdt) - l2.astype(sdt)
    if corr is not None:
        ls = ls + corr.astype(sdt)
    if fast is None:
        fast = fastpath_enabled()
    if fast and width <= 16 and width + frac_out <= 31:
        return _antilog_div_fast(ls, width, frac_out, round_out).astype(dt)
    # signed floor / positive remainder: I = ls >> F (arithmetic), Xs >= 0
    I = ls >> F
    Xs = ls & ((1 << F) - 1)
    mant = (Xs + (1 << F)).astype(dt)          # 1.Xs, always positive
    sh = I + jnp.asarray(frac_out - F, sdt)    # total shift of the mantissa
    zero = jnp.asarray(0, sdt)
    nbits = jnp.asarray(63 if dt == jnp.uint64 else 31, sdt)
    pos = jnp.clip(sh, zero, nbits).astype(dt)
    negsh = jnp.clip(-sh, zero, nbits).astype(dt)
    if round_out:
        # 1 << (negsh-1) when sh < 0, else 0 (negsh == 0 there)
        one = jnp.asarray(1, dt)
        mant = mant + ((one << negsh) >> one)
    return jnp.where(sh >= 0, mant << pos, mant >> negsh)


def _prep(a, b, width):
    dt = work_dtype(width)
    return a.astype(dt), b.astype(dt)


@partial(jax.jit, static_argnames=("width",))
def mitchell_mul(a: jax.Array, b: jax.Array, width: int) -> jax.Array:
    """Plain Mitchell product (no correction). Zero operands give zero."""
    au, bu = _prep(a, b, width)
    la, lb = mitchell_log(au, width), mitchell_log(bu, width)
    p = mitchell_antilog_mul(la, lb, width)
    return jnp.where((au == 0) | (bu == 0), jnp.zeros_like(p), p)


@partial(jax.jit, static_argnames=("width", "frac_out"))
def mitchell_div(a: jax.Array, b: jax.Array, width: int,
                 frac_out: int = 0) -> jax.Array:
    """Plain Mitchell quotient ``round_down(a/b * 2^frac_out)``.

    ``frac_out=0`` is integer floor division; b == 0 returns the max value
    (divider IP overflow-flag convention).
    """
    au, bu = _prep(a, b, width)
    la, lb = mitchell_log(au, width), mitchell_log(bu, width)
    q = mitchell_antilog_div(la, lb, width, frac_out=frac_out)
    dt = q.dtype
    maxv = ~jnp.asarray(0, dt)
    q = jnp.where(bu == 0, maxv, q)
    return jnp.where(au == 0, jnp.zeros_like(q), q)
