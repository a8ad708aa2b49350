"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle.

Everything is integer arithmetic — assertions are bit-for-bit equality.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import SimdiveSpec, pack
from repro.kernels import simdive_elemwise, simdive_matmul_int, simdive_packed
from repro.kernels import datapath as dp
from repro.kernels.logmatmul import (_chunk_partial, _split_lookup,
                                     _w_stage, logmatmul_pallas)
from repro.kernels.ref import logmatmul_ref

RNG = np.random.default_rng(7)

SPECS = [
    SimdiveSpec(width=8, coeff_bits=6),
    SimdiveSpec(width=8, coeff_bits=0, round_output=False),   # plain Mitchell
    SimdiveSpec(width=16, coeff_bits=6),
    SimdiveSpec(width=16, coeff_bits=8, index_bits=4),
]


def _uints(shape, width, lo=0):
    return jnp.asarray(
        RNG.integers(lo, 1 << width, size=shape, dtype=np.uint32)
    )


@pytest.mark.parametrize("spec", SPECS, ids=str)
@pytest.mark.parametrize("shape,block", [
    ((8, 128), (8, 128)),      # exact fit
    ((37, 300), (16, 128)),    # padding on both axes
    ((1, 7), (8, 128)),        # smaller than one block
    ((130, 130), (64, 64)),    # multi-block with remainder
])
@pytest.mark.parametrize("op", ["mul", "div", "mixed"])
def test_elemwise_matches_ref(spec, shape, block, op):
    a = _uints(shape, spec.width)
    b = _uints(shape, spec.width, lo=1)
    mode = _uints(shape, 1)
    kw = dict(spec=spec, op=op, mode=mode, frac_out=4)
    got = simdive_elemwise(a, b, backend="pallas", block=block, **kw)
    want = simdive_elemwise(a, b, backend="ref", **kw)
    assert got.dtype == want.dtype
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("width", [8, 16])
@pytest.mark.parametrize("shape,block", [
    ((4, 16), (4, 16)),
    ((9, 30), (4, 16)),        # padded
])
@pytest.mark.parametrize("op", ["mul", "div", "mixed"])
def test_packed_matches_ref(width, shape, block, op):
    spec = SimdiveSpec(width=width, coeff_bits=6)
    lpw = 32 // width
    lanes = (shape[0], shape[1] * lpw)
    aw = pack(_uints(lanes, width), width)
    bw = pack(_uints(lanes, width, lo=1), width)
    mw = pack(_uints(lanes, 1), width)
    kw = dict(spec=spec, op=op, mode=mw, frac_out=4)
    got = simdive_packed(aw, bw, backend="pallas", block=block, **kw)
    want = simdive_packed(aw, bw, backend="ref", **kw)
    assert got.shape == (shape[0], 2 * shape[1])
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("spec", SPECS[:3], ids=str)
@pytest.mark.parametrize("mkn,blocks", [
    ((16, 24, 16), (16, 16, 24)),
    ((20, 72, 33), (16, 16, 24)),    # padding every axis
    ((8, 8, 8), (8, 8, 8)),
    ((33, 50, 17), (16, 32, 32)),
])
def test_logmatmul_matches_ref(spec, mkn, blocks):
    M, K, N = mkn
    hi = min(1 << spec.width, 1 << 10)  # keep int32 accumulation exact
    x = jnp.asarray(RNG.integers(-hi + 1, hi, size=(M, K), dtype=np.int32))
    w = jnp.asarray(RNG.integers(-hi + 1, hi, size=(K, N), dtype=np.int32))
    got = simdive_matmul_int(x, w, spec, backend="pallas", blocks=blocks)
    want = simdive_matmul_int(x, w, spec, backend="ref")
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("k_unroll", [1, 8])
@pytest.mark.parametrize("depth", [1, 2, 4])
def test_logmatmul_pipeline_bit_identity(k_unroll, depth):
    """The double-buffered K sweep at any depth x unroll returns the
    depth-0 BlockSpec result bitwise (int32 accumulation, same op order);
    the 5-tuple block encoding carries both knobs through the registry."""
    spec = SimdiveSpec(width=8, coeff_bits=6)
    M, K, N = 24, 96, 40                     # padding on every axis
    hi = 1 << 8
    x = jnp.asarray(RNG.integers(-hi + 1, hi, size=(M, K), dtype=np.int32))
    w = jnp.asarray(RNG.integers(-hi + 1, hi, size=(K, N), dtype=np.int32))
    base = simdive_matmul_int(x, w, spec, backend="pallas",
                              blocks=(16, 16, 16, k_unroll, 0))
    got = simdive_matmul_int(x, w, spec, backend="pallas",
                             blocks=(16, 16, 16, k_unroll, depth))
    want = simdive_matmul_int(x, w, spec, backend="ref")
    assert np.array_equal(np.asarray(base), np.asarray(want))
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("coeff_bits", [6, 0])
@pytest.mark.parametrize("depth", [0, 2])
@pytest.mark.parametrize("k_unroll", [1, 8])
def test_logmatmul_every_w8_pair(coeff_bits, depth, k_unroll):
    """Every signed w8 operand pair, zero included, through the kernel:
    each x column and each w row holds all of [-255, 255] (in a rotated
    order per k), so every product of every rank-1 step position meets
    every pair of region bits; the int32 sums must equal ref.py's."""
    spec = SimdiveSpec(width=8, coeff_bits=coeff_bits)
    vals = np.concatenate([np.arange(-255, 256), [0]]).astype(np.int32)
    x = np.stack([np.roll(vals, 61 * k) for k in range(8)], axis=1)
    w = np.stack([np.roll(vals, 37 * k) for k in range(8)], axis=0)
    x, w = jnp.asarray(x), jnp.asarray(w)                # (512, 8), (8, 512)
    got = logmatmul_pallas(x, w, spec, blocks=(256, 256, 8),
                           k_unroll=k_unroll, pipeline_depth=depth)
    want = logmatmul_ref(x, w, spec)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("bm", [8, 128])
def test_split_lookup_is_the_table(bm):
    """The split lookup returns ``tab[(rx << ib) | rw]`` for every product
    of every step, on an asymmetric table (the mul table is symmetric, so
    a transposed split would pass the parity tests unseen)."""
    u, bn = 8, 128
    spec = SimdiveSpec(width=8, coeff_bits=6)
    ib = spec.index_bits
    rng = np.random.default_rng(bm)
    tab = rng.integers(-40, 40, size=1 << 2 * ib).astype(np.int32)
    xc = rng.integers(-255, 256, size=(bm, u)).astype(np.int32)
    wc = rng.integers(-255, 256, size=(u, bn)).astype(np.int32)
    lw, _, leaves = _w_stage(jnp.asarray(wc), tab, spec=spec)
    lx = dp.lod_log(jnp.asarray(np.abs(xc), jnp.uint32), 8, in_kernel=True)
    coeff = _split_lookup(lx, leaves, tab, (bm, bn), width=8, index_bits=ib)
    rx = (np.asarray(lx) & 127) >> 4            # F = 7 fraction bits
    rw = (np.asarray(lw) & 127) >> 4
    for k in range(u):
        want = tab[(rx[:, k:k + 1] << ib) | rw[k:k + 1, :]]
        assert np.array_equal(np.asarray(coeff(k)), want)


def _tile_selects(fn, shape, *args):
    """``select_n`` equations of ``fn``'s jaxpr (nested jaxprs included)
    whose output has the product tile's ``shape``."""
    def count(jaxpr):
        n = 0
        for eqn in jaxpr.eqns:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                n += count(sub)
            n += (eqn.primitive.name == "select_n"
                  and eqn.outvars[0].aval.shape == shape)
        return n

    return count(jax.make_jaxpr(fn)(*args).jaxpr)


@pytest.mark.parametrize("coeff_bits,lookup_per_step", [(6, 7), (0, 0)])
def test_logmatmul_lookup_selects_per_step(coeff_bits, lookup_per_step):
    """The region-coefficient lookup costs at most 2^index_bits - 1 = 7
    selects per rank-1 step on the (bm, bn) product tile (the 64-leaf
    tree cost 62), none for the all-zero table; the rest of the step's
    selects are the anti-log's and the sign join's, counted on their own."""
    spec = SimdiveSpec(width=8, coeff_bits=coeff_bits)
    bm, u, bn = 16, 8, 256                  # three distinct 2-D shapes
    tile = (bm, bn)
    tab = dp.op_table_host("mul", 8, coeff_bits, spec.index_bits)
    lx = jnp.zeros((bm, 1), jnp.uint32)
    lw = jnp.zeros((1, bn), jnp.uint32)

    def step(lx, lw, corr, sign):
        p = dp.antilog_mul(lx, lw, 8, corr=corr, round_out=spec.round_output,
                           in_kernel=True)
        return dp.sign_join(p, sign)

    rest = _tile_selects(step, tile, lx, lw, jnp.zeros(tile, jnp.int32),
                         jnp.zeros(tile, jnp.int32))
    chunk = _tile_selects(
        lambda x, w: _chunk_partial(x, _w_stage(w, tab, spec=spec), tab,
                                    spec=spec),
        tile, jnp.zeros((bm, u), jnp.int32), jnp.zeros((u, bn), jnp.int32))
    lookup = chunk - u * rest
    assert lookup <= u * lookup_per_step
    assert (lookup > 0) == (coeff_bits > 0)


def test_logmatmul_close_to_exact():
    """End-to-end sanity: SIMDive matmul ~1% of the exact integer matmul."""
    spec = SimdiveSpec(width=8, coeff_bits=6)
    x = jnp.asarray(RNG.integers(-255, 256, size=(32, 128), dtype=np.int32))
    w = jnp.asarray(RNG.integers(-255, 256, size=(128, 16), dtype=np.int32))
    got = np.asarray(simdive_matmul_int(x, w, spec, backend="pallas",
                                        blocks=(16, 16, 32))).astype(np.float64)
    t = np.asarray(x.astype(np.int64) @ w.astype(np.int64)).astype(np.float64)
    denom = np.maximum(np.abs(t), np.abs(t).mean())
    assert np.median(np.abs(got - t) / denom) < 0.02


def test_leading_dims_flattened():
    spec = SimdiveSpec(width=8, coeff_bits=6)
    a = _uints((2, 3, 40), 8)
    b = _uints((2, 3, 40), 8, lo=1)
    got = simdive_elemwise(a, b, spec, backend="pallas", block=(8, 128))
    want = simdive_elemwise(a, b, spec, backend="ref")
    assert got.shape == (2, 3, 40)
    assert np.array_equal(np.asarray(got), np.asarray(want))
