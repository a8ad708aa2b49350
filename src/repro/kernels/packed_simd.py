"""Packed sub-word SIMD kernel — 4x8-bit lanes per uint32 word (Fig. 2a).

This is the bandwidth-facing rendition of the paper's SIMD decomposition:
operands cross HBM *packed* (4 lane values per 32-bit word) and are only
expanded inside VMEM. For memory-bound layers this divides the memory
roofline term by ~4 — the TPU equivalent of the paper's "coalescing multiple
memory accesses".

The kernel body is pure wiring: :func:`repro.kernels.datapath.lane_expand`
splits the word tile into lanes, each lane runs the one shared SISD datapath
(:func:`~repro.kernels.datapath.lane_op` — identical composition to the
elemwise kernel and the oracle), and
:func:`~repro.kernels.datapath.lane_repack` interleaves the doubled-width
results back onto the output bus.

Outputs:
  * mul:  products are 16-bit, repacked 2 lanes/word -> (M, 2*Nw) words
  * div:  quotients at ``frac_out`` (<= 8) fractional bits, same packing
  * mixed: per-lane mode (Fig. 2a's one-hot Mul/Div signals), same packing
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.simdive import SimdiveSpec
from . import datapath as dp

__all__ = ["packed_pallas", "packed_word_op"]

DEFAULT_BLOCK = (64, 128)


def packed_word_op(aw, bw, tab, mode=None, *, spec: SimdiveSpec, op: str,
                   frac_out: int):
    """The packed kernel body as a pure word->word function: expand lanes,
    run the shared SISD datapath per lane, repack onto the doubled bus.

    Factored out of the Pallas kernel so the static analyzer
    (:mod:`repro.analysis.widthcheck`) traces exactly the arithmetic the
    kernel executes — lane isolation is *proved* on this function.
    """
    width = spec.width                      # 8 (4 lanes) or 16 (2 lanes)
    a_lanes = dp.lane_expand(aw, width)
    b_lanes = dp.lane_expand(bw, width)
    if op == "mixed":
        m_lanes = dp.lane_expand(mode, width)
    else:
        m_lanes = [None] * len(a_lanes)
    outs = [
        dp.lane_op(a, b, tab, width=width, index_bits=spec.index_bits,
                   op=op, frac_out=frac_out, mode=m,
                   round_out=spec.round_output, in_kernel=True)
        for a, b, m in zip(a_lanes, b_lanes, m_lanes)
    ]
    return dp.lane_repack(outs, 2 * width)


def _kernel(a_ref, b_ref, mode_ref, o_ref, *, tab, spec: SimdiveSpec,
            op: str, frac_out: int):
    mode = mode_ref[...] if op == "mixed" else None
    o_ref[...] = packed_word_op(a_ref[...], b_ref[...], tab, mode,
                                spec=spec, op=op, frac_out=frac_out)


@functools.partial(
    jax.jit, static_argnames=("spec", "op", "frac_out", "block", "interpret")
)
def packed_pallas(aw, bw, spec: SimdiveSpec, op: str = "mul", mode=None,
                  frac_out: int = 0, block=DEFAULT_BLOCK,
                  interpret: bool = True):
    """Packed-lane SIMDive over uint32 word tensors, fused in one kernel.

    ``aw, bw``: (M, Nw) uint32 packed operands. ``mode`` (mixed op): packed
    lane mask words, nonzero lane => mul. Returns (M, 2*Nw) uint32 words of
    2*width-bit lane results (products, or quotients at 2^frac_out scale).
    """
    assert aw.ndim == 2 and aw.shape == bw.shape and aw.dtype == jnp.uint32
    if spec.width == 8 and frac_out > 8:
        raise ValueError("frac_out > 8 overflows the 16-bit output lanes")
    M, Nw = aw.shape
    bm, bn = min(block[0], M), min(block[1], Nw)
    assert M % bm == 0 and Nw % bn == 0
    grid = (M // bm, Nw // bn)
    tab = dp.op_table_host(op, spec.width, spec.coeff_bits, spec.index_bits)
    if mode is None:
        mode = jnp.zeros_like(aw)
    kern = functools.partial(_kernel, tab=tab, spec=spec, op=op,
                             frac_out=frac_out)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, 2 * bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, 2 * Nw), jnp.uint32),
        name="packed_pallas",
        interpret=interpret,
    )(aw, bw, mode)
