"""Flash attention Pallas kernel with SIMDive divider normalization.

This is the perf-critical kernel the roofline analysis demands: the pure-XLA
online-softmax attention in models/layers.py materializes (qc, kc) score
tiles in HBM (1 GiB f32 tiles at train_4k scale — the dominant memory term,
see EXPERIMENTS.md §Perf iteration 1). This kernel keeps the score tile in
VMEM across the whole kv sweep: HBM traffic collapses to q/k/v reads + o
writes.

SIMDive tie-in (paper §3.2 divider): the final ``acc / l`` normalization
optionally runs through the *shared* log-domain datapath stages
(:mod:`repro.kernels.datapath`) inside the kernel — quantize the row to a
per-row shared exponent, then LOD -> log -> region-corrected ternary add ->
anti-log at ``frac_out`` fraction bits. One subtraction + table add + shift
replaces the float divide, exactly the paper's division-bearing-inner-loop
story. ``in_kernel=True`` pins the faithful Mosaic-safe stages; the host-side
oracle (:func:`flash_attention_ref`) composes the same stages with the PR 4
fast paths, bit-identical under ``SIMDIVE_FAITHFUL=1``.

Two schedules (RAPID, arXiv:2206.13970 — same datapath, new schedule):

* ``pipeline_depth=0`` — grid (BH, nq, nk) with the k axis innermost
  ("arbitrary"); Pallas streams k/v tiles via BlockSpecs and the online
  max/denominator/accumulator live in VMEM scratch across the nk steps.
* ``pipeline_depth=D>=1`` — grid (BH, nq); k/v stay in HBM and the
  kernel drives its own double-buffered DMA: D VMEM slots per operand, chunk
  c+D-1's copy-in starts while chunk c computes. D=1 degenerates to a serial
  copy-then-compute loop. Every depth is bit-identical to the depth-0 grid
  schedule — same float ops in the same order, only the copies move.

VMEM budget (defaults qc=kc=512, dh<=128): q tile 512*128*4B + D in-flight
k/v tiles 2*D*512*128*4B + scores 512*512*4B + acc 512*128*4B ~= 1.6 MiB at
D=1, +0.5 MiB per extra slot — comfortably resident (see kernels/README.md
§Pipelining for the budget math).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.error_lut import build_table
from repro.core.mitchell import lane_max_float, work_dtype
from repro.core.simdive import SimdiveSpec
from . import datapath as dp
from .registry import resolve_backend

__all__ = ["flash_attention_pallas", "flash_attention_ref", "softmax_div",
           "DEFAULT_DIV_SPEC", "DEFAULT_FRAC_OUT"]

#: divider config the attention op resolves to when no policy overrides it:
#: width 16 + frac_out 15 keeps every anti-log shift < 32 and stays inside
#: the f32-exact fast-path window (width + frac_out <= 31).
DEFAULT_DIV_SPEC = SimdiveSpec(width=16, coeff_bits=8, index_bits=3)
DEFAULT_FRAC_OUT = 15


def _div_table(width: int, coeff_bits: int, index_bits: int):
    """Divider correction table, built once per config (not per trace).

    ``build_table`` is host-cached numpy; converting here (rather than
    caching the jnp array) keeps the value safe to request from inside a
    jit trace — a cached tracer would leak across traces.
    """
    return jnp.asarray(build_table("div", width, coeff_bits, index_bits))


def softmax_div(acc, l, tab, *, width: int, index_bits: int = 3,
                frac_out: int = DEFAULT_FRAC_OUT, round_out: bool = True,
                in_kernel: bool = False):
    """Softmax normalization ``acc / l[..., None]`` on the SIMDive divider.

    ``acc``: (..., dh) float32 signed accumulator rows; ``l``: (...,) > 0
    denominators. Each row is quantized with a *per-row* shared exponent —
    ``top = max(rowmax|acc|, l)`` anchors the scale so both operands use the
    full ``width`` bits and the result is independent of how the rows were
    blocked (autotuning q/kv chunks cannot move the numerics). The quotient
    comes back at ``frac_out`` fraction bits and is folded back to float.

    ``in_kernel=True`` pins the faithful Mosaic-safe stages (Pallas kernel
    bodies); the default composes the PR 4 bit-exact fast paths when enabled.
    """
    num = jnp.abs(acc)
    den = jnp.maximum(l, 1e-30)[..., None]
    top = jnp.maximum(jnp.max(num, axis=-1, keepdims=True), den)
    ex = jnp.floor(jnp.log2(jnp.maximum(top, jnp.float32(1e-30))))
    sc = jnp.exp2(jnp.float32(width - 2) - ex)
    # NOT float32(2^width - 1): at width 32 that rounds up to 2^width, and a
    # clip against it admits an operand one past the lane maximum (the LOD
    # then yields k == width and the fraction shift F - k goes negative).
    # Found by repro.analysis.widthcheck (lane-domain, w32).
    lim = jnp.float32(lane_max_float(width))
    dt = work_dtype(width)
    # float -> signed -> unsigned: Mosaic has no float -> unsigned cast,
    # and every clipped value fits the signed carrier
    sdt = jnp.int32 if dt == jnp.uint32 else jnp.int64
    qn = jnp.clip(jnp.round(num * sc), 0.0, lim).astype(sdt).astype(dt)
    qd = jnp.clip(jnp.round(den * sc), 1.0, lim).astype(sdt).astype(dt)
    quot = dp.lane_op(qn, jnp.broadcast_to(qd, qn.shape), tab, width=width,
                      index_bits=index_bits, op="div", frac_out=frac_out,
                      round_out=round_out, in_kernel=in_kernel)
    out = dp.to_float32(quot) * jnp.float32(2.0 ** -frac_out)
    return jnp.where(acc < 0, -out, out)


def _online_step(q, k, v, m, l, acc, q0, k0, *, causal: bool, window: int,
                 kv_len: int, scale: float):
    """One (qc, kc) tile of the online softmax; pure function of the carry."""
    qc, kc = q.shape[0], k.shape[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale     # (qc, kc)
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (qc, kc), 0)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (qc, kc), 1)
    ok = kpos < kv_len
    if causal:
        ok &= kpos <= qpos
    if window:
        ok &= kpos > qpos - window
    s = jnp.where(ok, s, -jnp.inf)

    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    m_new = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_new[:, None])
    c = jnp.exp(m - m_new)
    l_new = l * c + jnp.sum(p, axis=-1)
    acc_new = acc * c[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _finalize_tile(acc, l, tab, *, approx_div: bool, spec: SimdiveSpec,
                   frac_out: int, out_dtype):
    l = jnp.maximum(l, 1e-30)
    if approx_div:
        out = softmax_div(acc, l, tab, width=spec.width,
                          index_bits=spec.index_bits, frac_out=frac_out,
                          round_out=spec.round_output, in_kernel=True)
    else:
        out = acc / l[:, None]
    return out.astype(out_dtype)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *, tab,
            nk: int, kc: int, causal: bool, window: int, scale: float,
            kv_len: int, q_offset: int, approx_div: bool,
            spec: SimdiveSpec, frac_out: int):
    """Depth-0 schedule: Pallas streams k/v tiles, carry lives in scratch."""
    kj = pl.program_id(2)
    qi = pl.program_id(1)
    qc = q_ref.shape[1]

    @pl.when(kj == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, -jnp.inf)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    m_new, l_new, acc_new = _online_step(
        q_ref[0], k_ref[0], v_ref[0], m_sc[...], l_sc[...], acc_sc[...],
        qi * qc + q_offset, kj * kc,
        causal=causal, window=window, kv_len=kv_len, scale=scale)
    m_sc[...] = m_new
    l_sc[...] = l_new
    acc_sc[...] = acc_new

    @pl.when(kj == nk - 1)
    def _fin():
        o_ref[0] = _finalize_tile(acc_sc[...], l_sc[...], tab,
                                  approx_div=approx_div, spec=spec,
                                  frac_out=frac_out, out_dtype=o_ref.dtype)


def _kernel_pipelined(q_ref, k_hbm, v_hbm, o_ref, *, tab,
                      nk: int, kc: int, depth: int, causal: bool,
                      window: int, scale: float, kv_len: int, q_offset: int,
                      approx_div: bool, spec: SimdiveSpec, frac_out: int,
                      kv_dtype):
    """Depth-D schedule: the kernel drives its own double-buffered k/v DMA.

    Warm-up starts chunks 0..D-2; loop step c starts chunk c+D-1 into the
    slot chunk c-1 just vacated ((c+D-1) % D == (c-1) % D), waits on chunk
    c's slot, computes. D=1 is the serial copy-then-compute degenerate.
    """
    b = pl.program_id(0)
    qi = pl.program_id(1)
    qc, dh = q_ref.shape[1], q_ref.shape[2]
    q = q_ref[0]

    def body(k_sc, v_sc, k_sem, v_sem):
        def dma(c, slot):
            # whole (kc, dh) rows, int32 offsets: no lane-axis slice (a
            # d_head below 128 is not lane-aligned) and no i64 index
            c, slot = jnp.int32(c), jnp.int32(slot)
            return (
                pltpu.make_async_copy(
                    k_hbm.at[b, pl.ds(c * kc, kc)], k_sc.at[slot],
                    k_sem.at[slot]),
                pltpu.make_async_copy(
                    v_hbm.at[b, pl.ds(c * kc, kc)], v_sc.at[slot],
                    v_sem.at[slot]),
            )

        for c in range(min(depth - 1, nk)):       # warm-up: fill the slots
            for cp in dma(c, c % depth):
                cp.start()

        def step(c, carry):
            m, l, acc = carry
            nxt = c + depth - 1

            @pl.when(nxt < nk)
            def _prefetch():
                for cp in dma(nxt, jax.lax.rem(nxt, jnp.int32(depth))):
                    cp.start()

            slot = jax.lax.rem(c, jnp.int32(depth))
            for cp in dma(c, slot):
                cp.wait()
            return _online_step(
                q, k_sc[slot][:, :dh], v_sc[slot][:, :dh], m, l, acc,
                qi * qc + q_offset, c * kc,
                causal=causal, window=window, kv_len=kv_len, scale=scale)

        m0 = jnp.full((qc,), -jnp.inf, jnp.float32)
        carry = (m0, jnp.zeros((qc,), jnp.float32),
                 jnp.zeros((qc, dh), jnp.float32))
        m, l, acc = jax.lax.fori_loop(dp.I0, jnp.int32(nk), step, carry)
        o_ref[0] = _finalize_tile(acc, l, tab,
                                  approx_div=approx_div, spec=spec,
                                  frac_out=frac_out, out_dtype=o_ref.dtype)

    dkv = k_hbm.shape[2]                  # d_head padded to the lane width
    pl.run_scoped(
        body,
        k_sc=pltpu.VMEM((depth, kc, dkv), kv_dtype),
        v_sc=pltpu.VMEM((depth, kc, dkv), kv_dtype),
        k_sem=pltpu.SemaphoreType.DMA((depth,)),
        v_sem=pltpu.SemaphoreType.DMA((depth,)),
    )


@functools.partial(
    jax.jit,
    static_argnames=("spec", "causal", "window", "q_chunk", "kv_chunk",
                     "pipeline_depth", "approx_div", "frac_out", "q_offset",
                     "kv_len", "interpret"),
)
def flash_attention_pallas(q, k, v, *, spec: SimdiveSpec = DEFAULT_DIV_SPEC,
                           causal=True, window=0, q_chunk=512, kv_chunk=512,
                           pipeline_depth=0, approx_div=False,
                           frac_out=DEFAULT_FRAC_OUT, q_offset=0,
                           kv_len=None, interpret=None):
    """q: (BH, Sq, dh); k, v: (BH, Skv, dh) — heads pre-flattened & matched
    (GQA callers repeat/reshape kv outside; the registry's ``attention`` op
    in ops.py does the padding/flattening bookkeeping). Returns (BH, Sq, dh).

    ``kv_len`` masks trailing kv padding (defaults to Skv); ``q_offset``
    shifts query positions for decode-style calls. ``interpret=None``
    resolves the backend like every other kernel: compiled on TPU hosts,
    interpret mode elsewhere.
    """
    if interpret is None:
        interpret = resolve_backend("auto") != "pallas-tpu"
    BH, Sq, dh = q.shape
    Skv = k.shape[1]
    if kv_len is None:
        kv_len = Skv
    qc = min(q_chunk, Sq)
    kc = min(kv_chunk, Skv)
    assert Sq % qc == 0 and Skv % kc == 0, "pad outside"
    nq, nk = Sq // qc, Skv // kc
    tab = dp.op_table_host("div", spec.width, spec.coeff_bits,
                           spec.index_bits)
    common = dict(tab=tab, nk=nk, kc=kc, causal=causal, window=window,
                  scale=dh ** -0.5, kv_len=kv_len, q_offset=q_offset,
                  approx_div=approx_div, spec=spec, frac_out=frac_out)
    if pipeline_depth:
        # the ring's DMA copies whole HBM rows, and Mosaic slices only
        # lane-aligned rows: k/v cross HBM zero-padded to the lane width,
        # and the kernel cuts each VMEM tile back to d_head
        dkv = dh + (-dh) % 128
        if dkv != dh:
            pad = ((0, 0), (0, 0), (0, dkv - dh))
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        kern = functools.partial(_kernel_pipelined, depth=int(pipeline_depth),
                                 kv_dtype=k.dtype, **common)
        return pl.pallas_call(
            kern,
            grid=(BH, nq),
            in_specs=[
                pl.BlockSpec((1, qc, dh), lambda b, i: (b, i, dp.I0)),
                pl.BlockSpec(memory_space=pltpu.HBM),
                pl.BlockSpec(memory_space=pltpu.HBM),
            ],
            out_specs=pl.BlockSpec((1, qc, dh), lambda b, i: (b, i, dp.I0)),
            out_shape=jax.ShapeDtypeStruct((BH, Sq, dh), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            name="flash_attention_pallas",
            interpret=interpret,
        )(q, k, v)
    kern = functools.partial(_kernel, **common)
    return pl.pallas_call(
        kern,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, qc, dh), lambda b, i, j: (b, i, dp.I0)),
            pl.BlockSpec((1, kc, dh), lambda b, i, j: (b, j, dp.I0)),
            pl.BlockSpec((1, kc, dh), lambda b, i, j: (b, j, dp.I0)),
        ],
        out_specs=pl.BlockSpec((1, qc, dh), lambda b, i, j: (b, i, dp.I0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qc,), jnp.float32),
            pltpu.VMEM((qc,), jnp.float32),
            pltpu.VMEM((qc, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_attention_pallas",
        interpret=interpret,
    )(q, k, v)


@functools.partial(
    jax.jit,
    static_argnames=("spec", "causal", "window", "approx_div", "frac_out",
                     "q_offset", "kv_len"),
)
def flash_attention_ref(q, k, v, *, spec: SimdiveSpec = DEFAULT_DIV_SPEC,
                        causal=True, window=0, approx_div=False,
                        frac_out=DEFAULT_FRAC_OUT, q_offset=0, kv_len=None):
    """Dense jnp oracle on the kernel's (BH, S, dh) contract.

    Exact softmax (not online), same masking semantics, and — under
    ``approx_div`` — the *same* divider stages as the kernel, composed with
    ``in_kernel=False`` so the PR 4 fast paths apply (bit-identical to the
    faithful stages, enforced by tests/test_fastpath.py). Memory is bounded
    by processing q in chunks: each step materializes (BH, qc, Skv), never
    the full score cube, so long-context conformance shapes stay cheap.
    """
    BH, Sq, dh = q.shape
    Skv = k.shape[1]
    if kv_len is None:
        kv_len = Skv
    qc = min(512, Sq)
    pad = (-Sq) % qc
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
    scale = dh ** -0.5
    kpos = jnp.arange(Skv)[None, :]
    tab = _div_table(spec.width, spec.coeff_bits, spec.index_bits)

    def chunk(i):
        qi = q[:, i * qc:(i + 1) * qc]
        s = jnp.einsum("bqd,btd->bqt", qi, k,
                       preferred_element_type=jnp.float32) * scale
        qpos = q_offset + i * qc + jnp.arange(qc)[:, None]
        ok = kpos < kv_len
        if causal:
            ok = ok & (kpos <= qpos)
        if window:
            ok = ok & (kpos > qpos - window)
        s = jnp.where(ok[None], s, -jnp.inf)
        m = jnp.max(s, axis=-1)
        m = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.exp(s - m[..., None])
        l = jnp.maximum(jnp.sum(p, axis=-1), 1e-30)
        acc = jnp.einsum("bqt,btd->bqd", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        if approx_div:
            out = softmax_div(acc, l, tab, width=spec.width,
                              index_bits=spec.index_bits, frac_out=frac_out,
                              round_out=spec.round_output, in_kernel=False)
        else:
            out = acc / l[..., None]
        return out.astype(q.dtype)

    out = jnp.concatenate([chunk(i) for i in range((Sq + pad) // qc)], axis=1)
    return out[:, :Sq]
