"""Compensated reductions (repro.core.compensated): Neumaier sum, Dot2, nrm2."""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend, numerics
from repro.core import compensated as C

RNG = np.random.default_rng(5)


def test_eft_reexports_are_the_numerics_primitives():
    assert C.two_sum is numerics.two_sum
    assert C.two_prod is numerics.two_prod
    assert C.fast_two_sum is numerics.fast_two_sum


def test_neumaier_recovers_cancellation_kahan_misses():
    """The classic Kahan failure case: a huge term arriving after small ones."""
    x = jnp.asarray([1.0, 1e100, 1.0, -1e100])
    assert float(C.neumaier_sum(x)) == 2.0


def test_neumaier_matches_fsum_ill_conditioned():
    vals = list(RNG.standard_normal(500) * 10.0 ** RNG.integers(-12, 12, 500))
    exact = math.fsum(vals)
    got = float(C.neumaier_sum(jnp.asarray(vals)))
    scale = math.fsum(abs(v) for v in vals)
    assert abs(got - exact) <= 4 * 2.0 ** -53 * scale


def test_neumaier_sum_axis():
    x = jnp.asarray(RNG.standard_normal((4, 64)))
    got = np.asarray(C.neumaier_sum(x, axis=-1))
    np.testing.assert_allclose(got, np.sum(np.asarray(x), axis=-1), rtol=1e-14)


def test_compensated_dot_twice_working_precision_f32():
    n = 4096
    x = RNG.standard_normal(n).astype(np.float32)
    y = RNG.standard_normal(n).astype(np.float32)
    exact = float(np.dot(x.astype(np.float64), y.astype(np.float64)))
    comp = float(C.compensated_dot(jnp.asarray(x), jnp.asarray(y)))
    plain = float(jnp.dot(jnp.asarray(x), jnp.asarray(y)))
    assert abs(comp - exact) <= abs(plain - exact)
    assert abs(comp - exact) <= 64 * abs(exact) * 2 ** -24 + 1e-6


def test_compensated_norm_matches_f64_oracle():
    x = RNG.standard_normal(2048).astype(np.float32)
    exact = float(np.linalg.norm(x.astype(np.float64)))
    got = float(C.compensated_norm(jnp.asarray(x)))
    assert abs(got - exact) <= 4 * exact * 2 ** -24


def test_compensated_norm_overflow_underflow_safe():
    big = jnp.asarray([1e200, 1e200, -1e200])
    assert np.isfinite(float(C.compensated_norm(big)))
    np.testing.assert_allclose(float(C.compensated_norm(big)),
                               1e200 * np.sqrt(3.0), rtol=1e-12)
    tiny = jnp.asarray([1e-300, 2e-300])
    np.testing.assert_allclose(float(C.compensated_norm(tiny)),
                               np.sqrt(5.0) * 1e-300, rtol=1e-12)
    assert float(C.compensated_norm(jnp.zeros(8))) == 0.0


@pytest.mark.parametrize("scale", [1.0, 1e200, 1e-200])
def test_compensated_norm_float32_pair_path(monkeypatch, scale):
    """The path taken where float64 is a float32 pair (XLA:TPU): no 64-bit
    bit fields, the scale from log2 of the largest magnitude.  On the CPU it
    must keep the IEEE path's contract: no overflow or flush at extreme
    scales, per-axis results, zeros and non-finite flags."""
    monkeypatch.setattr(backend, "float64_is_f32_pair", lambda: True)
    x = RNG.standard_normal((3, 1000))
    want = np.sqrt([math.fsum(v * v for v in row) for row in x]) * scale
    got = np.asarray(C.compensated_norm(jnp.asarray(x * scale), axis=1))
    np.testing.assert_allclose(got, want, rtol=4 * 2.0 ** -53)
    assert float(C.compensated_norm(jnp.zeros(8))) == 0.0
    assert float(C.compensated_norm(jnp.asarray([1.0, np.inf]))) == np.inf
    assert math.isnan(float(C.compensated_norm(jnp.asarray([np.nan, 1.0]))))


def test_neumaier_vs_fsum_property():
    hyp = pytest.importorskip("hypothesis",
                              reason="optional dep: pip install -e .[test]")
    given, settings, st = hyp.given, hyp.settings, hyp.strategies

    @settings(max_examples=50, deadline=None)
    @given(st.lists(st.floats(min_value=-1e15, max_value=1e15,
                              allow_nan=False, allow_infinity=False,
                              width=64),
                    min_size=1, max_size=64))
    def check(vals):
        """Neumaier summation tracks math.fsum to ~2 ulp of the term scale."""
        exact = math.fsum(vals)
        got = float(C.neumaier_sum(jnp.asarray(vals, jnp.float64)))
        scale = math.fsum(abs(v) for v in vals)
        assert abs(got - exact) <= 4 * 2.0 ** -53 * scale + 5e-324

    check()


# ---------------------------------------------------------------------------
# Blocked fast path vs the retained scan references (cross-implementation
# parity: exact or <= 1 ulp, asserted)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("n", [1, 7, 256, 1000, 4096])
def test_blocked_dot_matches_scan_reference(dtype, n):
    x = jnp.asarray(RNG.standard_normal(n).astype(dtype))
    y = jnp.asarray(RNG.standard_normal(n).astype(dtype))
    blocked = float(C.compensated_dot(x, y))
    scan = float(C.compensated_dot_scan(x, y))
    assert abs(blocked - scan) <= np.spacing(np.abs(scan).astype(dtype))


@pytest.mark.parametrize("block", [1, 3, 64, 4096, 10000])
def test_blocked_sum_matches_scan_any_block(block):
    vals = RNG.standard_normal(1000) * 10.0 ** RNG.integers(-10, 10, 1000)
    x = jnp.asarray(vals)
    blocked = float(C.neumaier_sum(x, block=block))
    scan = float(C.neumaier_sum_scan(x))
    exact = math.fsum(vals.tolist())
    scale = math.fsum(np.abs(vals).tolist())
    # Both land within the Sum2 bound of fsum; and within 1 ulp of each other.
    assert abs(blocked - exact) <= 4 * 2.0 ** -53 * scale
    assert abs(blocked - scan) <= np.spacing(abs(scan))


def test_batched_axis_variants_match_1d_loops():
    x = jnp.asarray(RNG.standard_normal((5, 300)))
    y = jnp.asarray(RNG.standard_normal((5, 300)))
    got = np.asarray(C.compensated_dot(x, y, axis=1))
    want = np.asarray([float(C.compensated_dot(x[i], y[i])) for i in range(5)])
    np.testing.assert_array_equal(got, want)

    got0 = np.asarray(C.neumaier_sum(x, axis=0))
    want0 = np.asarray([float(C.neumaier_sum(x[:, j])) for j in range(300)])
    np.testing.assert_array_equal(got0, want0)

    gotn = np.asarray(C.compensated_norm(x, axis=1))
    wantn = np.asarray([float(C.compensated_norm(x[i])) for i in range(5)])
    np.testing.assert_array_equal(gotn, wantn)


def test_dot_shape_mismatch_raises():
    with pytest.raises(ValueError, match="shapes differ"):
        C.compensated_dot(jnp.ones(4), jnp.ones(5))


def test_block_override_does_not_change_result_beyond_ulp():
    x = jnp.asarray(RNG.standard_normal(4096), jnp.float64)
    y = jnp.asarray(RNG.standard_normal(4096), jnp.float64)
    ref = float(C.compensated_dot(x, y, block=512))
    for block in (97, 256, 1024):
        got = float(C.compensated_dot(x, y, block=block))
        assert abs(got - ref) <= np.spacing(abs(ref))


# ---------------------------------------------------------------------------
# compensated_norm edge cases: denormal, huge, zero, non-finite
# ---------------------------------------------------------------------------

def test_norm_denormal_only_f32():
    """XLA CPU flushes denormal operands to zero (DAZ) — the bit-field scaling
    must recover the exact norm where plain arithmetic returns 0."""
    x = jnp.asarray([1e-40, 2e-40], jnp.float32)
    want = np.float32(math.hypot(float(x[0]), float(x[1])))
    assert float(C.compensated_norm(x)) == want
    assert want > 0.0
    # the single smallest denormal comes back exactly
    tiny = jnp.asarray([np.float32(1e-45)], jnp.float32)
    assert float(C.compensated_norm(tiny)) == float(tiny[0])


def test_norm_denormal_only_f64():
    x = jnp.asarray([5e-324, 1e-310], jnp.float64)
    got = float(C.compensated_norm(x))
    want = math.hypot(5e-324, 1e-310)
    assert got == want


def test_norm_huge_does_not_overflow():
    x = jnp.asarray([1e200, -1e200, 1e200], jnp.float64)
    np.testing.assert_allclose(float(C.compensated_norm(x)),
                               math.sqrt(3.0) * 1e200, rtol=1e-15)
    xf = jnp.asarray([1e38, 1e38], jnp.float32)
    np.testing.assert_allclose(float(C.compensated_norm(xf)),
                               np.float32(math.sqrt(2.0) * 1e38), rtol=1e-6)


def test_norm_mixed_magnitudes_track_hypot():
    x = jnp.asarray([1e-300, 1.0, 1e300], jnp.float64)
    np.testing.assert_allclose(float(C.compensated_norm(x)), 1e300, rtol=1e-15)


@pytest.mark.parametrize("vals,want", [
    ([1.0, np.inf], np.inf),
    ([1.0, -np.inf], np.inf),
    ([np.inf, -np.inf], np.inf),
])
def test_norm_inf_contaminated(vals, want):
    got = float(C.compensated_norm(jnp.asarray(vals, jnp.float64)))
    assert got == want


@pytest.mark.parametrize("vals", [[np.nan], [1.0, np.nan], [np.inf, np.nan]])
def test_norm_nan_dominates(vals):
    assert math.isnan(float(C.compensated_norm(jnp.asarray(vals))))


def test_norm_genuine_overflow_is_inf():
    x = jnp.asarray([1.7e308, 1.7e308], jnp.float64)
    assert float(C.compensated_norm(x)) == np.inf


def test_norm_unsupported_dtype_raises():
    with pytest.raises(TypeError, match="unsupported dtype"):
        C.compensated_norm(jnp.asarray([1, 2], jnp.bfloat16))


def test_norm_property_vs_hypot():
    hyp = pytest.importorskip("hypothesis",
                              reason="optional dep: pip install -e .[test]")
    given, settings, st = hyp.given, hyp.settings, hyp.strategies

    @settings(max_examples=80, deadline=None)
    @given(st.lists(st.floats(allow_nan=False, allow_infinity=False,
                              allow_subnormal=True, width=32),
                    min_size=1, max_size=32))
    def check(vals):
        """||x||_2 tracks math.hypot (correctly-rounded f64 oracle) to <= 2
        ulp across zero, denormal, and huge-magnitude f32 operands."""
        x = jnp.asarray(vals, jnp.float32)
        got = float(C.compensated_norm(x))
        want = np.float32(math.hypot(*(float(v) for v in np.asarray(x))))
        if np.isinf(want):
            assert got >= np.finfo(np.float32).max
        else:
            assert abs(got - want) <= 2 * np.spacing(want, dtype=np.float32)

    check()


def test_norm_property_vs_hypot_f64():
    hyp = pytest.importorskip("hypothesis",
                              reason="optional dep: pip install -e .[test]")
    given, settings, st = hyp.given, hyp.settings, hyp.strategies

    @settings(max_examples=80, deadline=None)
    @given(st.lists(st.floats(allow_nan=False, allow_infinity=False,
                              allow_subnormal=True, width=64),
                    min_size=1, max_size=32))
    def check(vals):
        got = float(C.compensated_norm(jnp.asarray(vals, jnp.float64)))
        want = math.hypot(*vals)
        if math.isinf(want):
            assert got >= np.finfo(np.float64).max
        else:
            assert abs(got - want) <= 2 * np.spacing(want)

    check()
