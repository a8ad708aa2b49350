"""Fused SIMDive element-wise multiplier/divider — Pallas TPU kernel.

One `pallas_call` fuses the whole datapath — segmented LOD -> log conversion
-> region index -> coefficient add (the "ternary add") -> anti-log — for a
whole VMEM tile. This is the TPU rendition of the SIMDive SISD unit of
Fig. 2(b): on an FPGA the win is LUT/carry-chain reuse; here it is a single
HBM round-trip for the whole approximate op (vs. log/add/antilog as separate
XLA ops). The datapath itself is :func:`repro.kernels.datapath.lane_op` —
the same stage composition the oracle and every other kernel use.

Tiles are (block_m, block_n) in VMEM; the 64-entry coefficient table is
fixed by the spec, so it is baked into the kernel as constants (the lookup
is a select tree, see :func:`repro.kernels.datapath.corr_lookup`).
Mixed functionality (per-element mul/div mode, Fig. 2a) is the `mode`
variant: both datapath halves share the LOD + log stage, exactly like the
hardware shares everything but the adder's 2's-complement input.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.simdive import SimdiveSpec
from . import datapath as dp

__all__ = ["elemwise_pallas"]

DEFAULT_BLOCK = (256, 512)


def _kernel(a_ref, b_ref, mode_ref, o_ref, *, tab, spec: SimdiveSpec,
            op: str, frac_out: int):
    mode = mode_ref[...] if op == "mixed" else None
    out = dp.lane_op(
        a_ref[...], b_ref[...], tab, width=spec.width,
        index_bits=spec.index_bits, op=op, frac_out=frac_out, mode=mode,
        round_out=spec.round_output, in_kernel=True,
    )
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("spec", "op", "frac_out", "block", "interpret"),
)
def elemwise_pallas(a, b, spec: SimdiveSpec, op: str = "mul",
                    mode=None, frac_out: int = 0,
                    block=DEFAULT_BLOCK, interpret: bool = True):
    """2D-tiled fused SIMDive elementwise op. Inputs uint lanes, same shape.

    ``op``: 'mul' | 'div' | 'mixed' (mixed needs ``mode``: nonzero => mul).
    Arrays are treated as (M, N); callers reshape/pad (see ops.py).
    """
    assert a.ndim == 2 and a.shape == b.shape
    M, N = a.shape
    bm, bn = min(block[0], M), min(block[1], N)
    assert M % bm == 0 and N % bn == 0, "ops.py pads to block multiples"
    grid = (M // bm, N // bn)
    tab = dp.op_table_host(op, spec.width, spec.coeff_bits, spec.index_bits)
    if mode is None:
        mode = jnp.zeros_like(a)

    kern = functools.partial(_kernel, tab=tab, spec=spec, op=op,
                             frac_out=frac_out)
    out_dtype = a.dtype
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        name="elemwise_pallas",
        interpret=interpret,
    )(a, b, mode)
