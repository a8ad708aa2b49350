"""Approximate log-domain matmul — the SIMDive "compute hot-spot" kernel.

C[m,n] = sum_k  sign * SIMDive(|X[m,k]|, |W[k,n]|)

Two schedules over the same tile math (:func:`_chunk_sweep` — a
``fori_loop`` over ``k_unroll``-wide K chunks, each chunk one sign split +
LOD/log pass and ``k_unroll`` rank-1 steps through the fused
correct+anti-log stage :func:`datapath.log_mul`):

* ``pipeline_depth=0`` — grid (M/bm, N/bn, K/bk) with the K axis innermost
  ("arbitrary" semantics): Pallas streams the (bm, bk)/(bk, bn) operand
  tiles via BlockSpecs and the int32 output tile accumulates across the K
  steps.
* ``pipeline_depth=D>=1`` — RAPID-style software pipelining (arXiv:
  2206.13970): grid (M/bm, N/bn), operands stay in HBM, and the kernel
  drives its own DMA with D VMEM slots per operand — tile k+D-1's copy-in
  starts while tile k computes, so copy-in latency hides behind the
  log-domain sweep. D=1 is the serial copy-then-compute degenerate; D=2 is
  classic double buffering.

Layout: Mosaic cannot slice a value's lane axis at a traced offset, so the
chunk loop indexes leading axes only. ``w`` crosses HBM as (K/u, u, N)
(a free reshape); each x tile is staged chunk-major, (bk/u, bm, u), in a
VMEM scratch by static slices once per tile. Every intermediate is 2-D
(bm, bn), the layout Mosaic handles natively. ``k_unroll`` trades loop
trips for straight-line code per trip; it and ``pipeline_depth`` are
autotuned axes: the registry's block candidates carry them as 4th/5th
components (see ops.py).

VMEM per step: the (bm, bk) x tile and (bk/u, u, bn) w tile per pipeline
slot, the chunk-major x staging tile (its u-wide rows pad to 128 lanes),
and the (bm, bn) accumulator and rank-1 temporaries — a few MiB at
(128, 128, 128), inside the 16 MiB scoped budget.

Exactness contract: for width 8 the int32 accumulation is exact (products
< 2^16, K < 2^15) and the kernel must match ref.py bit-for-bit; width 16
accumulates in int32 too and is exact for K*max_product < 2^31 (callers
scale). Any ``k_unroll`` x ``pipeline_depth`` combination produces
bit-identical sums — int32 addition is associative (wrap-around included),
so both the chunked reduction and the pipelined K sweep are pure schedule
changes. This kernel exists because the *emulation* of the paper's
arithmetic must run at usable speed on TPU for accuracy studies; the
deployment path for weights is packed int8 + MXU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.simdive import SimdiveSpec
from . import datapath as dp

__all__ = ["logmatmul_pallas", "DEFAULT_K_UNROLL", "K_UNROLL_CANDIDATES",
           "PIPELINE_CANDIDATES"]

DEFAULT_BLOCKS = (128, 128, 128)  # (bm, bn, bk)
DEFAULT_K_UNROLL = 8
#: the autotune axes joined to the block candidates in ops.py
K_UNROLL_CANDIDATES = (1, 4, 8, 16)
PIPELINE_CANDIDATES = (0, 2, 4)


def _chunk_partial(xc, wc, tab, *, spec: SimdiveSpec):
    """int32 partial product-sum of one K chunk: (bm, u) x (u, bn).

    Sign split and LOD/log run once on the chunk's operands; then each of
    the u rank-1 steps pushes a (bm, 1) column against a (1, bn) row
    through the fused correct+anti-log stage (:func:`datapath.log_mul`),
    broadcasting to a (bm, bn) tile — 2-D values only, which is what
    Mosaic lays out natively. A zero operand is folded into the sign (a
    zero sign drops the product), so no zero mask is broadcast. Both
    kernel schedules and :func:`_tile_partial` run this one function, so
    bit-identity between them is structural.
    """
    width = spec.width
    xm, sx = dp.sign_split(xc, width)               # (bm, u) magnitudes
    wm, sw = dp.sign_split(wc, width)               # (u, bn)
    lx = dp.lod_log(xm, width, in_kernel=True)
    lw = dp.lod_log(wm, width, in_kernel=True)
    zero = jnp.int32(0)
    sx = jnp.where(xm == 0, zero, sx)
    sw = jnp.where(wm == 0, zero, sw)
    acc = jnp.zeros((xc.shape[0], wc.shape[1]), jnp.int32)
    for k in range(xc.shape[1]):
        p = dp.log_mul(lx[:, k:k + 1], lw[k:k + 1, :], tab, width,
                       spec.index_bits, round_out=spec.round_output,
                       in_kernel=True)              # (bm, bn)
        acc = acc + dp.sign_join(p, sx[:, k:k + 1] * sw[k:k + 1, :])
    return acc


def _tile_partial(x_tile, w_tile, tab, *, spec: SimdiveSpec, bk: int,
                  k_unroll: int):
    """int32 partial product-sum of one (bm, bk) x (bk, bn) tile pair, as
    a static sweep over ``k_unroll``-wide chunks — the kernels' arithmetic
    on plain values (the static analyzer proves the accumulator on it)."""
    acc = jnp.zeros((x_tile.shape[0], w_tile.shape[1]), jnp.int32)
    for k0 in range(0, bk, k_unroll):
        acc = acc + _chunk_partial(x_tile[:, k0:k0 + k_unroll],
                                   w_tile[k0:k0 + k_unroll], tab, spec=spec)
    return acc


def _stage_chunks(x_tile, xs_ref, u: int):
    """Lay a (bm, bk) x tile out chunk-major in VMEM: ``xs_ref[c]`` is
    columns ``[c*u, (c+1)*u)``. Static lane slices, once per tile — the
    chunk sweep then indexes a leading (untiled) axis, since Mosaic has no
    dynamic slice of a value's lane axis."""
    for c in range(xs_ref.shape[0]):
        xs_ref[c] = x_tile[:, c * u:(c + 1) * u]


def _chunk_sweep(xs_ref, w_chunks, tab, *, spec: SimdiveSpec):
    """Sum :func:`_chunk_partial` over the chunks of one tile pair:
    ``xs_ref``: (nc, bm, u), ``w_chunks``: (nc, u, bn)."""
    def body(c, acc):
        return acc + _chunk_partial(xs_ref[c], w_chunks[c], tab, spec=spec)

    shape = (xs_ref.shape[1], w_chunks.shape[2])
    return jax.lax.fori_loop(dp.I0, jnp.int32(xs_ref.shape[0]), body,
                             jnp.zeros(shape, jnp.int32))


def _kernel(x_ref, w_ref, o_ref, xs_ref, *, tab, spec: SimdiveSpec, u: int):
    _stage_chunks(x_ref[...], xs_ref, u)
    partial_sum = _chunk_sweep(xs_ref, w_ref, tab, spec=spec)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += partial_sum


def _kernel_pipelined(x_hbm, w_hbm, o_ref, *, tab, spec: SimdiveSpec,
                      bm: int, bn: int, bk: int, nk: int, u: int,
                      depth: int, in_dtype):
    """Depth-D schedule: operand tiles arrive by explicit double-buffered
    DMA while the previous tile's log-domain sweep computes.

    Warm-up starts tiles 0..D-2; loop step c starts tile c+D-1 into the
    slot tile c-1 just vacated ((c+D-1) % D == (c-1) % D), waits on tile
    c's slot, computes. D=1 degenerates to serial copy-then-compute.
    """
    i = pl.program_id(0)
    j = pl.program_id(1)
    nc = bk // u

    def body(x_sc, w_sc, xs, x_sem, w_sem):
        def dma(c, slot):
            # int32 offsets: under x64 a Python int lowers as an i64
            # index, which Mosaic refuses
            c, slot = jnp.int32(c), jnp.int32(slot)
            return (
                pltpu.make_async_copy(
                    x_hbm.at[pl.ds(i * bm, bm), pl.ds(c * bk, bk)],
                    x_sc.at[slot], x_sem.at[slot]),
                pltpu.make_async_copy(
                    w_hbm.at[pl.ds(c * nc, nc), pl.ds(dp.I0, u),
                             pl.ds(j * bn, bn)],
                    w_sc.at[slot], w_sem.at[slot]),
            )

        for c in range(min(depth - 1, nk)):       # warm-up: fill the slots
            for cp in dma(c, c % depth):
                cp.start()

        def step(c, acc):
            nxt = c + depth - 1

            @pl.when(nxt < nk)
            def _prefetch():
                for cp in dma(nxt, jax.lax.rem(nxt, jnp.int32(depth))):
                    cp.start()

            slot = jax.lax.rem(c, jnp.int32(depth))
            for cp in dma(c, slot):
                cp.wait()
            _stage_chunks(x_sc[slot], xs, u)
            return acc + _chunk_sweep(xs, w_sc.at[slot], tab, spec=spec)

        o_ref[...] = jax.lax.fori_loop(
            dp.I0, jnp.int32(nk), step, jnp.zeros((bm, bn), jnp.int32))

    pl.run_scoped(
        body,
        x_sc=pltpu.VMEM((depth, bm, bk), in_dtype),
        w_sc=pltpu.VMEM((depth, nc, u, bn), in_dtype),
        xs=pltpu.VMEM((nc, bm, u), in_dtype),
        x_sem=pltpu.SemaphoreType.DMA((depth,)),
        w_sem=pltpu.SemaphoreType.DMA((depth,)),
    )


@functools.partial(
    jax.jit,
    static_argnames=("spec", "blocks", "k_unroll", "pipeline_depth",
                     "interpret"),
)
def logmatmul_pallas(x, w, spec: SimdiveSpec, blocks=DEFAULT_BLOCKS,
                     k_unroll: int = DEFAULT_K_UNROLL,
                     pipeline_depth: int = 0,
                     interpret: bool = True):
    """(M,K) @ (K,N) with SIMDive scalar products; int32 result (no scales).

    ``x``, ``w`` are *signed* int32 with magnitudes < 2^width (quantization
    and scale bookkeeping live in ops.py / repro.core.approx).
    ``k_unroll`` chunks the in-tile K sweep; it is snapped down to a
    divisor of the (possibly shape-clamped) bk so every chunk is full.
    ``pipeline_depth >= 1`` switches to the explicit double-buffered DMA
    schedule (bit-identical output at any depth).
    """
    assert x.ndim == 2 and w.ndim == 2 and x.shape[1] == w.shape[0]
    M, K = x.shape
    N = w.shape[1]
    bm, bn, bk = (min(blocks[0], M), min(blocks[1], N), min(blocks[2], K))
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    u = math.gcd(max(int(k_unroll), 1), bk)
    tab = dp.op_table_host("mul", spec.width, spec.coeff_bits,
                           spec.index_bits)
    w3 = w.reshape(K // u, u, N)                   # chunk-major K rows
    if pipeline_depth:
        kern = functools.partial(
            _kernel_pipelined, tab=tab, spec=spec, bm=bm, bn=bn, bk=bk,
            nk=K // bk, u=u, depth=int(pipeline_depth), in_dtype=x.dtype)
        return pl.pallas_call(
            kern,
            grid=(M // bm, N // bn),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.HBM),
                pl.BlockSpec(memory_space=pltpu.HBM),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")
            ),
        )(x, w3)
    grid = (M // bm, N // bn, K // bk)
    kern = functools.partial(_kernel, tab=tab, spec=spec, u=u)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // u, u, bn), lambda i, j, k: (k, dp.I0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bk // u, bm, u), x.dtype)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(x, w3)
