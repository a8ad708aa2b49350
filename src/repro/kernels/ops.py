"""Built-in SIMDive ops: registration + thin public entry points.

Each op registers two implementations with :mod:`repro.kernels.registry`:
a pure-jnp reference (the bit-exact oracle from ref.py) and, where one
exists, the Pallas kernel. The impls own shape normalization (flatten to
2D, pad to block multiples); everything else — backend resolution, block
autotuning, dispatch — lives in the registry.

The public wrappers (``simdive_elemwise`` / ``simdive_packed`` /
``simdive_matmul_int``) keep their historical signatures and are now
one-line shims over ``get_op``; model code (:mod:`repro.core.approx`)
dispatches through the registry directly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.domain import ArgSpec, TraceCase
from repro.core.fastpath import fastpath_enabled
from repro.core.simdive import SimdiveSpec, simdive_mul
from . import ref as _ref
from .elemwise import DEFAULT_BLOCK as ELEMWISE_BLOCK, elemwise_pallas
from .flash_attention import (
    DEFAULT_DIV_SPEC,
    DEFAULT_FRAC_OUT,
    flash_attention_pallas,
    flash_attention_ref,
)
from .logmatmul import (
    DEFAULT_BLOCKS as MATMUL_BLOCKS,
    DEFAULT_K_UNROLL,
    logmatmul_pallas,
)
from .packed_simd import DEFAULT_BLOCK as PACKED_BLOCK, packed_pallas
from .registry import get_op, register_op

__all__ = ["simdive_elemwise", "simdive_packed", "simdive_matmul_int",
           "simdive_attention"]


def _pad2d(x, bm, bn, fill=0):
    M, N = x.shape
    pm, pn = (-M) % bm, (-N) % bn
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)), constant_values=fill)
    return x


def _as2d(x):
    return x.reshape(1, -1) if x is not None and x.ndim != 2 else x


# --------------------------------------------------------------- elemwise --
def _elemwise_ref(a, b, *, spec, op="mul", mode=None, frac_out=0):
    shape = a.shape
    out = _ref.elemwise_ref(_as2d(a), _as2d(b), spec, op=op,
                            mode=_as2d(mode), frac_out=frac_out)
    return out.reshape(shape)


def _elemwise_pallas(a, b, *, spec, block, interpret, op="mul", mode=None,
                     frac_out=0):
    shape = a.shape
    a2, b2, m2 = _as2d(a), _as2d(b), _as2d(mode)
    M, N = a2.shape
    bm, bn = min(block[0], M), min(block[1], N)
    ap = _pad2d(a2, bm, bn)
    bp = _pad2d(b2, bm, bn, fill=1)     # avoid div-by-zero in the pad region
    mp = _pad2d(m2, bm, bn) if m2 is not None else None
    out = elemwise_pallas(ap, bp, spec, op=op, mode=mp, frac_out=frac_out,
                          block=(bm, bn), interpret=interpret)
    return out[:M, :N].reshape(shape)


# ----------------------------------------------------------------- packed --
def _packed_ref(aw, bw, *, spec, op="mul", mode=None, frac_out=0):
    shape = aw.shape
    out = _ref.packed_ref(_as2d(aw), _as2d(bw), spec, op=op,
                          mode=_as2d(mode), frac_out=frac_out)
    return out.reshape(*shape[:-1], 2 * shape[-1])


def _packed_pallas(aw, bw, *, spec, block, interpret, op="mul", mode=None,
                   frac_out=0):
    shape = aw.shape
    a2, b2, m2 = _as2d(aw), _as2d(bw), _as2d(mode)
    M, N = a2.shape
    bm, bn = min(block[0], M), min(block[1], N)
    ap = _pad2d(a2, bm, bn)
    # pad words with lanes == 1 to keep the div path well-defined
    one_word = sum(1 << (spec.width * i) for i in range(32 // spec.width))
    bp = _pad2d(b2, bm, bn, fill=one_word)
    mp = _pad2d(m2, bm, bn) if m2 is not None else None
    out = packed_pallas(ap, bp, spec, op=op, mode=mp, frac_out=frac_out,
                        block=(bm, bn), interpret=interpret)
    return out[:M, : 2 * N].reshape(*shape[:-1], 2 * shape[-1])


# ------------------------------------------------------------- matmul_int --
def _matmul_int_ref(x, w, *, spec):
    lead = x.shape[:-1]
    out = _ref.logmatmul_ref(x.reshape(-1, x.shape[-1]), w, spec)
    return out.reshape(*lead, w.shape[1])


def _split_matmul_block(block):
    """A matmul block is (bm, bn, bk), (bm, bn, bk, k_unroll) or
    (bm, bn, bk, k_unroll, pipeline_depth): the 4th component is the
    autotuned in-tile K chunk width and the 5th the double-buffer depth of
    the pipelined K sweep (see logmatmul.py). Shorter tuples stay accepted
    and mean the default unroll / the unpipelined grid schedule."""
    if len(block) == 5:
        return tuple(block[:3]), int(block[3]), int(block[4])
    if len(block) == 4:
        return tuple(block[:3]), int(block[3]), 0
    return tuple(block), DEFAULT_K_UNROLL, 0


def _matmul_int_pallas(x, w, *, spec, block, interpret):
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    M, K = x2.shape
    N = w.shape[1]
    (bm_, bn_, bk_), k_unroll, depth = _split_matmul_block(block)
    bm, bn, bk = min(bm_, M), min(bn_, N), min(bk_, K)
    xp = _pad2d(x2, bm, bk)
    wp = _pad2d(w, bk, bn)
    out = logmatmul_pallas(xp, wp, spec, blocks=(bm, bn, bk),
                           k_unroll=k_unroll, pipeline_depth=depth,
                           interpret=interpret)
    return out[:M, :N].reshape(*lead, N)


# ------------------------------------------------------------ matmul_emul --
def _matmul_emul_ref(qx, sx, qw, sw, *, spec, k_chunk=128):
    """Integer core of the model-facing emulated matmul: (M,K)x(K,N) with
    SIMDive scalar products, K-chunked so the (M, Kc, N) product tensor
    stays small; int64 accumulation (bit-exact seed semantics).

    Fast path (enabled, width <= 15): the sign is joined into the int32
    product — exact, since |product| < 2^(2*width) <= 2^30 — and the chunk
    is contracted straight to int64 via einsum's accumulator dtype, so no
    (M, Kc, N) *int64* tensor is ever materialized (the int32 one fuses
    with the reduction). Identical sums bit-for-bit: every addend is the
    same integer either way.
    """
    M, K = qx.shape
    N = qw.shape[1]
    pad = (-K) % k_chunk
    if pad:
        qx = jnp.pad(qx, ((0, 0), (0, pad)))
        sx = jnp.pad(sx, ((0, 0), (0, pad)), constant_values=1)
        qw = jnp.pad(qw, ((0, pad), (0, 0)))
        sw = jnp.pad(sw, ((0, pad), (0, 0)), constant_values=1)
    nc = (K + pad) // k_chunk
    qxc = qx.reshape(M, nc, k_chunk).transpose(1, 0, 2)
    sxc = sx.reshape(M, nc, k_chunk).transpose(1, 0, 2)
    qwc = qw.reshape(nc, k_chunk, N)
    swc = sw.reshape(nc, k_chunk, N)
    fast = fastpath_enabled() and 2 * spec.width <= 31

    def body(acc, inp):
        qxk, sxk, qwk, swk = inp
        p = simdive_mul(qxk[:, :, None], qwk[None, :, :], spec)  # (M,Kc,N)
        s = sxk[:, :, None] * swk[None, :, :]
        if fast:
            sp = p.astype(jnp.int32) * s
            acc = acc + jnp.einsum("mkn->mn", sp,
                                   preferred_element_type=jnp.int64)
        else:
            acc = acc + jnp.sum(p.astype(jnp.int64) * s.astype(jnp.int64),
                                axis=1)
        return acc, None

    acc0 = jnp.zeros((M, N), jnp.int64)
    acc, _ = jax.lax.scan(body, acc0, (qxc, sxc, qwc, swc))
    return acc


def _matmul_emul_pallas(qx, sx, qw, sw, *, spec, block, interpret,
                        k_chunk=128):
    """TPU path of the emulated matmul: recombine signs and run the tiled
    log-domain kernel. Accumulates in int32 (exact for width 8 / bounded K;
    the int64 reference is the accuracy-study oracle)."""
    del k_chunk  # the kernel's K-tiling replaces the host-side chunking
    x = qx.astype(jnp.int32) * sx
    w = qw.astype(jnp.int32) * sw
    return _matmul_int_pallas(x, w, spec=spec, block=block,
                              interpret=interpret).astype(jnp.int64)


# -------------------------------------------------------------- attention --
def _attention_ref(q, k, v, *, spec, causal=True, window=0, approx_div=True,
                   frac_out=DEFAULT_FRAC_OUT, q_offset=0):
    return flash_attention_ref(q, k, v, spec=spec, causal=causal,
                               window=window, approx_div=approx_div,
                               frac_out=frac_out, q_offset=q_offset)


def _split_attention_block(block):
    """An attention block is (q_chunk, kv_chunk) or (q_chunk, kv_chunk,
    pipeline_depth): the 3rd component selects the double-buffered kv-sweep
    schedule (see flash_attention.py)."""
    if len(block) == 3:
        return int(block[0]), int(block[1]), int(block[2])
    return int(block[0]), int(block[1]), 0


def _attention_pallas(q, k, v, *, spec, block, interpret, causal=True,
                      window=0, approx_div=True, frac_out=DEFAULT_FRAC_OUT,
                      q_offset=0):
    qc, kc, depth = _split_attention_block(block)
    BH, Sq, dh = q.shape
    Skv = k.shape[1]
    qc, kc = min(qc, Sq), min(kc, Skv)
    pq, pk = (-Sq) % qc, (-Skv) % kc
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    out = flash_attention_pallas(
        q, k, v, spec=spec, causal=causal, window=window, q_chunk=qc,
        kv_chunk=kc, pipeline_depth=depth, approx_div=approx_div,
        frac_out=frac_out, q_offset=q_offset, kv_len=Skv,
        interpret=interpret)
    return out[:, :Sq]


# ------------------------------------------------------------------- sqrt --
def _sqrt_ref(a, *, spec, frac_out=0):
    from repro.core.simdive import simdive_sqrt

    return simdive_sqrt(a, spec.width, frac_out=frac_out)


# ------------------------------------------------------- widthcheck meta --
# Analysis metadata for repro.analysis.widthcheck: per op and width, the
# pure traceable functions + abstract operand domains that *are* the
# arithmetic the backends execute (kernel bodies and faithful ref stages,
# not pallas_call wrappers). A returned string is a declared, auditable
# skip; None means the width is out of the op's domain.

_AN_IB = 3                                   # 64-region tables everywhere
#: coeff_bits exercised per width: the shipped BENCH/serve configs
#: (8b/cb6, 16b/cb8, 16b/cb0 zero-table, 32b/cb8)
_AN_COEFF = {8: (6,), 16: (8, 0), 32: (8,)}
_AN_DIV_FO = {8: 8, 16: 15, 32: 16}          # shipped div frac_out per width


def _lane_arg(width, shape=(8, 128)):
    dt = np.uint64 if width > 16 else np.uint32
    return ArgSpec(tuple(shape), dt, 0, (1 << width) - 1)


def _elemwise_analysis(width):
    from . import datapath as dp

    if width not in (8, 16, 32):
        return None
    cases = []
    fo_div = _AN_DIV_FO[width]
    for cb in _AN_COEFF[width]:
        for op, fo in (("mul", 0), ("div", fo_div), ("mixed", min(fo_div, 8))):
            tab = dp.op_table(op, width, cb, _AN_IB)
            for ik in (False, True):
                la = _lane_arg(width)
                args = (la, la)
                if op == "mixed":
                    args += (ArgSpec(la.shape, np.uint32, 0, 1),)

                def fn(a, b, m=None, *, _t=tab, _o=op, _f=fo, _k=ik):
                    return dp.lane_op(
                        a, b, _t, width=width, index_bits=_AN_IB, op=_o,
                        frac_out=_f, mode=m, round_out=True, in_kernel=_k)

                cases.append(TraceCase(
                    label=(f"elemwise/{op} w{width} cb{cb} fo{fo} "
                           f"{'kernel' if ik else 'ref'}"),
                    fn=fn, args=args, requires_x64=width > 16))
    return cases


def _packed_analysis(width):
    from .packed_simd import packed_word_op

    if width not in (8, 16):
        return ("packed lanes need >= 2 per 32-bit word; width 32 is the "
                "elemwise (full-word) path")
    cases = []
    cb = _AN_COEFF[width][0]
    word = ArgSpec((8, 64), np.uint32, 0, (1 << 32) - 1)
    for op, fo in (("mul", 0), ("div", 8), ("mixed", 8)):
        from . import datapath as dp
        tab = dp.op_table(op, width, cb, _AN_IB)
        spec = SimdiveSpec(width=width, coeff_bits=cb, index_bits=_AN_IB)
        args = (word, word) + ((word,) if op == "mixed" else ())

        def fn(aw, bw, mw=None, *, _t=tab, _s=spec, _o=op, _f=fo):
            return packed_word_op(aw, bw, _t, mw, spec=_s, op=_o, frac_out=_f)

        cases.append(TraceCase(
            label=f"packed/{op} w{width} cb{cb} fo{fo} kernel",
            fn=fn, args=args,
            note="ref path shares dp.lane_op (proved under elemwise)"))
    return cases


def _matmul_int_analysis(width):
    from . import datapath as dp
    from .logmatmul import _tile_partial

    if width == 8:
        cases = []
        cb = _AN_COEFF[8][0]
        tab = dp.op_table("mul", 8, cb, _AN_IB)
        spec = SimdiveSpec(width=8, coeff_bits=cb, index_bits=_AN_IB)
        lane = (1 << 8) - 1
        for K in (32, 128, 512):             # the BENCH K sweep
            x = ArgSpec((8, K), np.int32, -lane, lane)
            w = ArgSpec((K, 128), np.int32, -lane, lane)

            def fn(xt, wt, *, _t=tab, _s=spec, _k=K):
                return _tile_partial(xt, wt, _t, spec=_s, bk=_k, k_unroll=8)

            cases.append(TraceCase(
                label=f"matmul_int w8 cb{cb} K{K} kernel tile",
                fn=fn, args=(x, w),
                note="int32 accumulator; operands are lane-width "
                     "magnitudes with sign (sign_split clamps)"))
        return cases
    if width == 16:
        return ("int32 accumulator is exact only while K * max_product < "
                "2^31; callers scale operands per the logmatmul.py "
                "contract — not provable width-generically")
    if width == 32:
        return ("width-32 matmul is not shipped; the 64-bit product bus "
                "exceeds every accumulator the kernel offers")
    return None


def _matmul_emul_analysis(width):
    if width not in (8, 16):
        if width == 32:
            return ("width-32 emulated matmul is not shipped (64-bit "
                    "product bus exceeds the int64 accumulator)")
        return None
    lane = (1 << width) - 1
    spec = SimdiveSpec(width=width, coeff_bits=_AN_COEFF[width][0],
                       index_bits=_AN_IB)
    M, K, N = 8, 256, 16
    qx = ArgSpec((M, K), np.uint32, 0, lane)
    sx = ArgSpec((M, K), np.int32, -1, 1)
    qw = ArgSpec((K, N), np.uint32, 0, lane)
    sw = ArgSpec((K, N), np.int32, -1, 1)

    def fn(a, b, c, d, *, _s=spec):
        return _matmul_emul_ref(a, b, c, d, spec=_s)

    return [TraceCase(
        label=f"matmul_emul w{width} ref K{K}",
        fn=fn, args=(qx, sx, qw, sw),
        note="pallas path recombines signs into matmul_int (proved there)")]


def _attention_analysis(width):
    from .flash_attention import _div_table, softmax_div

    if width not in (8, 16, 32):
        return None
    cb = _AN_COEFF[width][0]
    tab = _div_table(width, cb, _AN_IB)
    fo = min(_AN_DIV_FO[width], 15)
    acc = ArgSpec((8, 64), np.float32, -1e30, 1e30)
    l = ArgSpec((8,), np.float32, 0.0, 1e30)
    cases = []
    for ik in (False, True):
        def fn(a, d, *, _t=tab, _k=ik):
            return softmax_div(a, d, _t, width=width, index_bits=_AN_IB,
                               frac_out=fo, round_out=True, in_kernel=_k)

        cases.append(TraceCase(
            label=(f"attention/softmax_div w{width} cb{cb} fo{fo} "
                   f"{'kernel' if ik else 'ref'}"),
            fn=fn, args=(acc, l), requires_x64=width > 16,
            note="float accumulator stages are out of integer scope; "
                 "the quantize-clip-divide ladder is what is proved"))
    return cases


def _sqrt_analysis(width):
    from repro.core.simdive import simdive_sqrt

    if width not in (8, 16, 32):
        return None
    cases = []
    for fo in (0, 8):
        def fn(a, *, _f=fo):
            return simdive_sqrt(a, width, frac_out=_f)

        cases.append(TraceCase(
            label=f"sqrt w{width} fo{fo} ref",
            fn=fn, args=(_lane_arg(width),), requires_x64=width > 16))
    return cases


# ----------------------------------------------------------- registration --
register_op(
    "elemwise",
    ref=_elemwise_ref,
    pallas=_elemwise_pallas,
    default_block=ELEMWISE_BLOCK,
    block_candidates=((128, 256), (256, 512)),
    analysis=_elemwise_analysis,
)
register_op(
    "packed",
    ref=_packed_ref,
    pallas=_packed_pallas,
    default_block=PACKED_BLOCK,
    block_candidates=((64, 128), (128, 128), (64, 256)),
    analysis=_packed_analysis,
)
# matmul blocks carry the k_unroll autotune axis as a 4th component and the
# pipeline_depth axis as a 5th (K_UNROLL_CANDIDATES / PIPELINE_CANDIDATES in
# logmatmul.py); shorter tuples stay accepted and mean the default unroll /
# the unpipelined grid schedule.
_MATMUL_CANDIDATES = (
    (128, 128, 128, 1),
    (128, 128, 128, 4),
    (128, 128, 128, 8),
    (128, 128, 128, 16),
    (64, 128, 256, 8),
    (128, 128, 128, 8, 2),
    (128, 128, 128, 8, 4),
    (64, 128, 256, 8, 2),
)
register_op(
    "matmul_int",
    ref=_matmul_int_ref,
    pallas=_matmul_int_pallas,
    default_block=MATMUL_BLOCKS + (DEFAULT_K_UNROLL,),
    block_candidates=_MATMUL_CANDIDATES,
    analysis=_matmul_int_analysis,
)
register_op(
    "matmul_emul",
    ref=_matmul_emul_ref,
    pallas=_matmul_emul_pallas,
    default_block=MATMUL_BLOCKS + (DEFAULT_K_UNROLL,),
    block_candidates=_MATMUL_CANDIDATES,
    analysis=_matmul_emul_analysis,
)
# attention blocks are (q_chunk, kv_chunk[, pipeline_depth]); the depth
# variants run the explicit double-buffered kv sweep (bit-identical output)
_ATTENTION_CANDIDATES = (
    (256, 256),
    (512, 512),
    (512, 512, 2),
    (1024, 512, 2),
)
register_op(
    "attention",
    ref=_attention_ref,
    pallas=_attention_pallas,
    default_block=(512, 512),
    block_candidates=_ATTENTION_CANDIDATES,
    analysis=_attention_analysis,
)
register_op("sqrt", ref=_sqrt_ref,   # Pallas impl: future PR, plugs in here
            analysis=_sqrt_analysis)


# ------------------------------------------------------------- public API --
def simdive_elemwise(a, b, spec: SimdiveSpec, op: str = "mul", mode=None,
                     frac_out: int = 0, backend: str = "auto", block=None):
    """Elementwise SIMDive mul/div/mixed over same-shape uint arrays."""
    return get_op("elemwise", spec, backend, block=block)(
        a, b, op=op, mode=mode, frac_out=frac_out)


def simdive_packed(aw, bw, spec: SimdiveSpec, op: str = "mul", mode=None,
                   frac_out: int = 0, backend: str = "auto", block=None):
    """Packed-lane SIMDive over uint32 word tensors (last dim = words)."""
    return get_op("packed", spec, backend, block=block)(
        aw, bw, op=op, mode=mode, frac_out=frac_out)


def simdive_matmul_int(x, w, spec: SimdiveSpec, backend: str = "auto",
                       blocks=None):
    """Signed int32 (…,K) @ (K,N) with SIMDive products (int32 result)."""
    return get_op("matmul_int", spec, backend, block=blocks)(x, w)


def simdive_attention(q, k, v, spec: SimdiveSpec | None = None, *,
                      causal: bool = True, window: int = 0,
                      approx_div: bool = True,
                      frac_out: int = DEFAULT_FRAC_OUT, q_offset: int = 0,
                      backend: str = "auto", block=None):
    """Flash attention with the SIMDive softmax divider.

    q: (BH, Sq, dh); k, v: (BH, Skv, dh) — heads pre-flattened & matched
    (GQA callers repeat/reshape kv outside; models/layers.py does this).
    ``spec`` picks the divider config (defaults to the width-16 attention
    divider); padding to chunk multiples happens inside.
    """
    spec = DEFAULT_DIV_SPEC if spec is None else spec
    return get_op("attention", spec, backend, block=block)(
        q, k, v, causal=causal, window=window, approx_div=approx_div,
        frac_out=frac_out, q_offset=q_offset)
