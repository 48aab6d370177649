"""Problem generators: deterministic in the seed, and the operator the
configuration states."""

import numpy as np
import pytest

from bench import draw, harness
from bench.configs import cg_poisson7, cg_poisson7_ref, dgemm_ref


@pytest.fixture(autouse=True)
def _x64():
    import jax
    jax.config.update("jax_enable_x64", True)


BIG = 2 ** 31 + 12345     # seeds past 32 bits


def _draw(seed):
    return [np.asarray(a) for a in
            draw.normal_f64(draw.seed_key(seed), ((16, 8), (8, 4)))]


def test_same_seed_same_operands():
    for x, y in zip(_draw(BIG), _draw(BIG)):
        assert x.dtype == np.float64
        np.testing.assert_array_equal(x, y)


def test_other_seed_other_operands():
    for x, y in zip(_draw(BIG), _draw(BIG + 1)):
        assert not np.array_equal(x, y)


def test_operands_use_float64_bits():
    a = _draw(7)[0]
    assert np.any(a != a.astype(np.float32).astype(np.float64))
    assert abs(float(np.mean(a))) < 1.0 and 0.3 < float(np.std(a)) < 3.0


def test_host_draws_follow_the_seed():
    x = harness.rng_for(BIG, 1).integers(0, 1 << 30, 8)
    y = harness.rng_for(BIG, 1).integers(0, 1 << 30, 8)
    z = harness.rng_for(BIG, 2).integers(0, 1 << 30, 8)
    np.testing.assert_array_equal(x, y)
    assert not np.array_equal(x, z)


def test_bands_draw_one_index_per_band():
    rows = dgemm_ref.bands(np.random.default_rng(1), 300, 128)
    assert len(rows) == 3
    assert [r // 128 for r in rows] == [0, 1, 2]
    assert list(dgemm_ref.bands(np.random.default_rng(1), 8, 128)) == list(range(8))


@pytest.mark.parametrize("n", [3, 5])
def test_poisson_bell_is_the_7_point_operator(n):
    val, col = cg_poisson7.poisson_bell(n)
    assert val.shape == col.shape == (n ** 3, 7) and col.dtype == np.int32
    x = np.random.default_rng(n).standard_normal(n ** 3)
    ell = np.sum(val * x[col], axis=-1)
    np.testing.assert_allclose(ell, cg_poisson7_ref.apply(x, n), rtol=0, atol=1e-13)
    # Symmetric, with 6 on the diagonal and -1 for each interior neighbour.
    dense = np.zeros((n ** 3, n ** 3))
    np.add.at(dense, (np.arange(n ** 3)[:, None], col), val)
    np.testing.assert_array_equal(dense, dense.T)
    assert np.all(np.diag(dense) == 6.0)


def test_reference_cg_reduces_the_residual():
    n = 6
    b = np.random.default_rng(3).standard_normal(n ** 3)
    x = cg_poisson7_ref.cg(b, n, 40)
    r = b - cg_poisson7_ref.apply(x, n)
    assert np.linalg.norm(r) < 1e-8 * np.linalg.norm(b)
