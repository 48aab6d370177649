import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import splitting as S
from repro.core.moduli import DEFAULT_MODULI, SPLIT_RADIX


RNG = np.random.default_rng(42)


def _rand_ints(shape, bits):
    lim = 2 ** bits
    return RNG.integers(-lim + 1, lim, size=shape).astype(np.float64)


def test_split_hi_lo_exact_roundtrip():
    xi = jnp.asarray(_rand_ints((64, 64), 52))
    hi, lo = S.split_hi_lo(xi)
    assert hi.dtype == jnp.int32 and lo.dtype == jnp.int32
    back = S.merge_hi_lo(hi, lo)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(xi))
    # lo is balanced: |lo| <= 2^25
    assert np.abs(np.asarray(lo)).max() <= SPLIT_RADIX // 2


def test_residues_hilo_matches_int64_oracle():
    xi = jnp.asarray(_rand_ints((128,), 52))
    got = np.asarray(S.residues_from_hilo(*S.split_hi_lo(xi), DEFAULT_MODULI))
    want = np.asarray(S.residues_direct(xi, DEFAULT_MODULI))
    np.testing.assert_array_equal(got, want)


def test_residues_are_balanced_int8():
    xi = jnp.asarray(_rand_ints((256,), 52))
    res = np.asarray(S.residues_from_hilo(*S.split_hi_lo(xi), DEFAULT_MODULI))
    assert res.dtype == np.int8
    for i, m in enumerate(DEFAULT_MODULI):
        assert res[i].min() >= -(m // 2)
        assert res[i].max() <= (m - 1) // 2
        # residue congruent to the original value
        np.testing.assert_array_equal(
            np.mod(res[i].astype(object) - np.asarray(xi).astype(object), m), 0)


def test_scale_to_int_bounds_and_exactness():
    x = jnp.asarray(RNG.standard_normal((32, 100)) * 10.0 ** RNG.integers(-8, 8, (32, 1)))
    for p in (24, 53):
        xi, shift = S.scale_to_int(x, p, axis=-1)
        assert np.abs(np.asarray(xi)).max() < 2.0 ** p
        assert np.asarray(xi).max() >= 2.0 ** (p - 2)  # scaling actually fills payload
        # xi is integer valued
        np.testing.assert_array_equal(np.asarray(xi), np.round(np.asarray(xi)))
        # pow2 rescale recovers x to within the rounding of (4): the error is
        # *absolute* on the per-row integer grid, 0.5 * 2^-shift_i (App. C).
        back = np.asarray(xi) * 2.0 ** (-np.asarray(shift)[:, None].astype(np.float64))
        atol = 0.5 * 2.0 ** (-np.asarray(shift)[:, None].astype(np.float64))
        assert np.all(np.abs(back - np.asarray(x)) <= atol * (1 + 1e-12))


def test_scale_to_int_zero_rows():
    x = jnp.zeros((4, 8))
    xi, shift = S.scale_to_int(x, 53, axis=-1)
    assert np.all(np.asarray(xi) == 0)
    assert np.all(np.isfinite(np.asarray(shift)))


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_ldexp_same_bits_as_jnp_ldexp(dtype):
    """The exact scaling that XLA:TPU lowers gives jnp.ldexp's bits on the
    CPU for zero and normal x and any shift: results that stay normal, that
    fall below the normal range, and that overflow."""
    fi = np.finfo(dtype)
    emax = fi.maxexp - 1
    sig = RNG.choice([-1.0, 1.0], 20000) * RNG.uniform(1.0, 2.0, 20000)
    x = np.ldexp(sig, RNG.integers(fi.minexp, emax - 1, 20000)).astype(dtype)
    x[:100] = 0.0
    assert np.all((np.abs(x) >= fi.tiny) | (x == 0)) and np.all(np.isfinite(x))
    e = RNG.integers(-3 * emax, 3 * emax, 20000).astype(np.int32)
    got = np.asarray(S.ldexp(jnp.asarray(x), jnp.asarray(e)))
    want = np.asarray(jnp.ldexp(jnp.asarray(x), jnp.asarray(e)))
    assert got.dtype == dtype
    np.testing.assert_array_equal(got.view(f"u{got.itemsize}"),
                                  want.view(f"u{want.itemsize}"))


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_pow2_exact_over_normal_range(dtype):
    fi = np.finfo(dtype)
    e = np.arange(fi.minexp, fi.maxexp, dtype=np.int32)
    np.testing.assert_array_equal(np.asarray(S.pow2(jnp.asarray(e), dtype)),
                                  np.ldexp(np.ones(e.shape, dtype), e))


def test_apply_unscale_exact_pow2():
    c = jnp.asarray(RNG.standard_normal((8, 8)))
    sr = jnp.asarray(RNG.integers(-10, 10, 8), dtype=jnp.int32)
    sc = jnp.asarray(RNG.integers(-10, 10, 8), dtype=jnp.int32)
    out = np.asarray(S.apply_unscale(c, sr, sc))
    want = np.asarray(c) * 2.0 ** (-(np.asarray(sr)[:, None] + np.asarray(sc)[None, :]))
    np.testing.assert_array_equal(out, want)  # power-of-two scaling is exact
