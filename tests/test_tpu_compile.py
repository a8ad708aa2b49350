"""The serve path's Pallas kernels compile for a described TPU v5e.

No chip is attached: the TPU compiler builds each kernel for a v5e that is
only described (``jax.experimental.topologies``), at the widths the
smollm-360m serve path runs (K=960, N=2560, d_head 64). A kernel the chip's
compiler refuses — an op Mosaic cannot lower, an unaligned slice, a tile
past the VMEM budget — fails here instead of on the chip. Nothing runs, so
these tests say nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every test worker imports this
file. The tests run under the conftest's x64 setting.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.simdive import SimdiveSpec
from repro.kernels import get_op

W8 = SimdiveSpec(width=8, coeff_bits=6, index_bits=3)
W16 = SimdiveSpec(width=16, coeff_bits=8, index_bits=3)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _assert_kernel(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("spec,op,frac_out", [(W8, "mul", 0),
                                              (W16, "div", 15)],
                         ids=["w8-mul", "w16-div"])
def test_elemwise_compiles(one_chip, spec, op, frac_out):
    def fn(a, b):
        return get_op("elemwise", spec, "pallas-tpu")(a, b, op=op,
                                                       frac_out=frac_out)

    lanes = ((1024, 2560), jnp.uint32)
    _assert_kernel(one_chip, fn, lanes, lanes)


def test_packed_w8_compiles(one_chip):
    def fn(a, b):
        return get_op("packed", W8, "pallas-tpu")(a, b, op="mul")

    words = ((1024, 640), jnp.uint32)
    _assert_kernel(one_chip, fn, words, words)


@pytest.mark.parametrize("depth", [0, 2])
def test_logmatmul_compiles(one_chip, depth):
    def fn(x, w):
        return get_op("matmul_int", W8, "pallas-tpu",
                      block=(128, 128, 128, 8, depth))(x, w)

    _assert_kernel(one_chip, fn, ((512, 960), jnp.int32),
                   ((960, 2560), jnp.int32))


@pytest.mark.parametrize("depth", [0, 2])
@pytest.mark.parametrize("d_head", [64, 128])
def test_attention_compiles(one_chip, d_head, depth):
    def fn(q, k, v):
        return get_op("attention", W16, "pallas-tpu",
                      block=(256, 256, depth))(q, k, v, causal=True,
                                               approx_div=True)

    qkv = ((16, 1024, d_head), jnp.bfloat16)
    _assert_kernel(one_chip, fn, qkv, qkv, qkv)
