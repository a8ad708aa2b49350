"""SIMDive arithmetic for the plain reference, written from the paper.

SIMDive (arXiv 2011.01148) multiplies and divides unsigned integers in the
log domain after Mitchell: an operand ``a = 2^k (1 + x)`` has the fixed-point
log ``L = k * 2^F + x * 2^F`` with ``F = width - 1`` fraction bits. A product
adds two logs, a quotient subtracts them, and the anti-log reads the sum back
as ``2^I (1 + X)``. SIMDive adds one correction coefficient to the log sum,
chosen by the top ``index_bits`` bits of each operand's fraction (8 x 8 = 64
regions for 3 bits): the region's mean of the ideal correction, kept to
``coeff_bits`` of precision. ``coeff_bits = 0`` is plain Mitchell.

Nothing here imports the program under test: the tables are computed from
the definitions below, and the matmul emulation is plain integer ``jnp``.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache

import numpy as np
import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class Lane:
    """One SIMDive lane operation: width, correction precision, rounding."""
    width: int
    coeff_bits: int
    index_bits: int = 3
    round_output: bool = True

    @property
    def frac(self) -> int:
        return self.width - 1


def _ideal_mul(x1, x2):
    # (1+x1)(1+x2) read back by the piecewise-linear anti-log
    s = (1.0 + x1) * (1.0 + x2)
    return np.where(s < 2.0, s - 1.0, 0.5 * s) - (x1 + x2)


def _ideal_div(x1, x2):
    r = (1.0 + x1) / (1.0 + x2)
    return np.where(r >= 1.0, r - 1.0, 2.0 * r - 2.0) - (x1 - x2)


@lru_cache(maxsize=None)
def correction_table(op: str, lane: Lane) -> np.ndarray:
    """Region-mean correction in units of 2^-F, quantized to the lane's
    coefficient precision: a step of 2^(F - 2 - coeff_bits) units (at least
    one unit), clipped to |c| < 2^(F-1). Index = (region of x1) * n + region
    of x2, with n = 2^index_bits regions per axis. The mean is taken over a
    32 x 32 midpoint grid in each region."""
    n = 1 << lane.index_bits
    if lane.coeff_bits <= 0:
        return np.zeros(n * n, np.int64)
    per = 32
    f = {"mul": _ideal_mul, "div": _ideal_div}[op]
    g = (np.arange(per, dtype=np.float64) + 0.5) / per
    means = np.empty((n, n))
    for i in range(n):
        for j in range(n):
            x1 = (i + g)[:, None] / n
            x2 = (j + g)[None, :] / n
            means[i, j] = f(x1, x2).mean()
    units = np.rint(means.ravel() * (1 << lane.frac))
    step = max(1, 1 << max(0, lane.frac - 2 - lane.coeff_bits))
    q = np.rint(units / step) * step
    lim = (1 << (lane.frac - 1)) - 1
    return np.clip(q, -lim, lim).astype(np.int64)


def _log_np(a: np.ndarray, width: int) -> np.ndarray:
    """Mitchell log of unsigned ints (a >= 1) as integers, F = width - 1."""
    F = width - 1
    a = a.astype(np.int64)
    k = np.floor(np.log2(np.maximum(a, 1))).astype(np.int64)
    # exact for the integer widths used here; guard float rounding at 2^k
    k = np.where((1 << (k + 1)) <= a, k + 1, k)
    k = np.where((1 << k) > a, k - 1, k)
    return (k << F) + ((a - (1 << k)) << (F - k))


@lru_cache(maxsize=None)
def product_table(lane: Lane) -> np.ndarray:
    """All products of two ``width``-bit magnitudes (width 8: 256 x 256),
    as the lane computes them; 0 times anything is 0."""
    if lane.width != 8:
        raise ValueError("the full product table is kept for width 8 only")
    a = np.arange(256, dtype=np.int64)
    La = _log_np(a, 8)[:, None]
    Lb = _log_np(a, 8)[None, :]
    F = lane.frac
    n = 1 << lane.index_bits
    sh = F - lane.index_bits
    tab = correction_table("mul", lane)
    ra = (La & ((1 << F) - 1)) >> sh
    rb = (Lb & ((1 << F) - 1)) >> sh
    ls = np.maximum(La + Lb + tab[ra * n + rb], 0)
    out = _antilog_np(ls, lane)
    out[0, :] = 0
    out[:, 0] = 0
    return out


def _antilog_np(ls, lane: Lane):
    F = lane.frac
    I = ls >> F
    mant = (1 << F) + (ls & ((1 << F) - 1))
    shr = np.maximum(F - I, 0)
    shl = np.maximum(I - F, 0)
    if lane.round_output:
        mant = mant + np.where(shr > 0, 1 << np.maximum(shr - 1, 0), 0)
    out = (mant << shl) >> shr
    return np.where(I >= 2 * lane.width, (1 << (2 * lane.width)) - 1, out)


# ----------------------------------------------------------------- matmul --
def _log8_table() -> np.ndarray:
    t = _log_np(np.arange(256), 8)
    t[0] = 0
    return t.astype(np.int32)


def emulated_matmul(qa, sa, qb, sb, lane: Lane, unroll: int = 8):
    """``sum_k sa*sb * P(qa[m,k], qb[k,n])`` in int32, P the lane's product.

    ``qa`` (M, K) and ``qb`` (K, N) are magnitudes in [0, 255], ``sa``/``sb``
    signs in {-1, +1}. The K sweep is a loop of ``unroll`` rank-1 updates, each
    one (M, N) integer pass: log sum, region correction, anti-log.
    """
    if lane.width != 8:
        raise ValueError("the reference matmul is written for width 8")
    M, K = qa.shape
    N = qb.shape[1]
    pad = (-K) % unroll
    if pad:
        qa = jnp.pad(qa, ((0, 0), (0, pad)))
        sa = jnp.pad(sa, ((0, 0), (0, pad)))
        qb = jnp.pad(qb, ((0, pad), (0, 0)))
        sb = jnp.pad(sb, ((0, pad), (0, 0)))
    F = lane.frac
    n = 1 << lane.index_bits
    sh = F - lane.index_bits
    log8 = jnp.asarray(_log8_table())
    La = log8[qa]
    Lb = log8[qb]
    # a zero magnitude contributes nothing: fold it into the sign
    sa = jnp.where(qa == 0, 0, sa).astype(jnp.int32)
    sb = jnp.where(qb == 0, 0, sb).astype(jnp.int32)
    tab = jnp.asarray(correction_table("mul", lane).reshape(n, n)
                      .astype(np.int32))
    ra = (La & ((1 << F) - 1)) >> sh            # (M, K) region of a
    rb = (Lb & ((1 << F) - 1)) >> sh            # (K, N) region of b
    # (n, M, K): the table's row for a's region, one plane per b region
    corr_a = jnp.stack([tab[:, j][ra] for j in range(n)])
    bits = [((rb >> b) & 1) == 1 for b in range(lane.index_bits)]
    nk = (K + pad) // unroll

    def body(i, acc):
        k0 = i * unroll
        la = jax.lax.dynamic_slice_in_dim(La, k0, unroll, 1)
        s_a = jax.lax.dynamic_slice_in_dim(sa, k0, unroll, 1)
        ca = jax.lax.dynamic_slice_in_dim(corr_a, k0, unroll, 2)
        lb = jax.lax.dynamic_slice_in_dim(Lb, k0, unroll, 0)
        s_b = jax.lax.dynamic_slice_in_dim(sb, k0, unroll, 0)
        bb = [jax.lax.dynamic_slice_in_dim(x, k0, unroll, 0) for x in bits]
        for u in range(unroll):
            level = [ca[j, :, u:u + 1] for j in range(n)]
            for b in range(lane.index_bits):
                sel = bb[b][u:u + 1]
                level = [jnp.where(sel, level[2 * t + 1], level[2 * t])
                         for t in range(len(level) // 2)]
            ls = la[:, u:u + 1] + lb[u:u + 1] + level[0]
            ls = jnp.maximum(ls, 0)
            I = ls >> F
            mant = (1 << F) + (ls & ((1 << F) - 1))
            shr = jnp.clip(F - I, 0, 31)
            shl = jnp.clip(I - F, 0, 31)
            if lane.round_output:
                mant = mant + ((1 << shr) >> 1)
            p = (mant << shl) >> shr
            p = jnp.where(I >= 2 * lane.width, (1 << (2 * lane.width)) - 1, p)
            acc = acc + p * (s_a[:, u:u + 1] * s_b[u:u + 1])
        return acc

    return jax.lax.fori_loop(0, nk, body, jnp.zeros((M, N), jnp.int32))


def divide(num, den, lane: Lane, frac_out: int):
    """``floor(num / den * 2^frac_out)`` through the lane's divider, with
    half-LSB rounding when the lane rounds. ``num`` >= 0, ``den`` >= 1,
    both integers below 2^width (int32 arrays)."""
    F = lane.frac
    n = 1 << lane.index_bits
    sh = F - lane.index_bits

    def log(a):
        a = jnp.maximum(a, 1).astype(jnp.int32)
        k = (31 - jax.lax.clz(a)).astype(jnp.int32)
        return (k << F) + ((a - (1 << k)) << (F - k))

    La, Lb = log(num), log(den)
    tab = jnp.asarray(correction_table("div", lane).astype(np.int32))
    idx = ((La & ((1 << F) - 1)) >> sh) * n + ((Lb & ((1 << F) - 1)) >> sh)
    ls = La - Lb + tab[idx]
    I = ls >> F                                  # arithmetic: floor
    mant = (1 << F) + (ls & ((1 << F) - 1))
    s = I + (frac_out - F)
    neg = jnp.clip(-s, 0, 31)
    if lane.round_output:
        mant = mant + ((1 << neg) >> 1)
    q = jnp.where(s >= 0, mant << jnp.clip(s, 0, 31), mant >> neg)
    return jnp.where(num == 0, 0, q)
