"""The reference's SIMDive arithmetic, written from the paper, against the
program's datapath (the reference itself imports nothing of the program)."""
import numpy as np
import jax.numpy as jnp
import pytest

from bench.references.simdive import (Lane, correction_table, divide,
                                      emulated_matmul, product_table)


@pytest.mark.parametrize("op, width, cb", [("mul", 8, 6), ("div", 16, 6),
                                           ("mul", 8, 0), ("mul", 8, 4)])
def test_correction_tables_match_the_program(op, width, cb):
    from repro.core.error_lut import build_table_clean
    mine = correction_table(op, Lane(width, cb))
    assert np.array_equal(mine, build_table_clean(op, width, cb))


@pytest.mark.parametrize("cb, rnd", [(6, True), (0, False)])
def test_every_w8_product_matches_the_program(cb, rnd):
    from repro.core.simdive import SimdiveSpec, simdive_mul
    a = np.arange(256)
    A, B = np.meshgrid(a, a, indexing="ij")
    want = np.asarray(simdive_mul(jnp.asarray(A, jnp.uint32),
                                  jnp.asarray(B, jnp.uint32),
                                  SimdiveSpec(8, cb, 3, rnd)))
    assert np.array_equal(product_table(Lane(8, cb, 3, rnd)), want)


def test_emulated_matmul_is_the_product_table_sum():
    rng = np.random.default_rng(0)
    qa, qb = rng.integers(0, 256, (5, 37)), rng.integers(0, 256, (37, 11))
    sa, sb = rng.choice([-1, 1], (5, 37)), rng.choice([-1, 1], (37, 11))
    lane = Lane(8, 6)
    P = product_table(lane)
    want = np.einsum("mk,kn,mkn->mn", sa, sb, P[qa[:, :, None], qb[None]])
    got = emulated_matmul(*(jnp.asarray(x, jnp.int32)
                            for x in (qa, sa, qb, sb)), lane)
    assert np.array_equal(np.asarray(got), want)


@pytest.mark.parametrize("cb, rnd", [(6, True), (0, False)])
def test_w16_divider_matches_the_program(cb, rnd):
    from repro.core.simdive import SimdiveSpec, simdive_div
    rng = np.random.default_rng(1)
    num = rng.integers(0, 2 ** 15, 4000)
    den = rng.integers(1, 2 ** 15, 4000)
    got = divide(jnp.asarray(num, jnp.int32), jnp.asarray(den, jnp.int32),
                 Lane(16, cb, 3, rnd), 15)
    want = simdive_div(jnp.asarray(num, jnp.uint32),
                       jnp.asarray(den, jnp.uint32), SimdiveSpec(16, cb, 3, rnd),
                       frac_out=15)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_scale_groups_of_one_row_are_the_per_row_scale():
    from bench.references.dense import _linear
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(6, 40)) * np.arange(1, 7)[:, None],
                    jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(40, 9)), jnp.float32)
    lane = Lane(8, 6)
    per_row = _linear(x, w, lane)
    assert np.array_equal(_linear(x, w, lane, jnp.arange(6), 6), per_row)
    # one group: the whole call shares the largest row's scale
    shared = _linear(x, w, lane, jnp.zeros(6, jnp.int32), 1)
    assert np.array_equal(shared[5], per_row[5])
    assert not np.array_equal(shared[0], per_row[0])
