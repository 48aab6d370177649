import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend
from repro.core import moduli as M
from repro.core import ozaki2

U64 = 2.0 ** -53
RNG = np.random.default_rng(7)


def _relerr(c, a, b):
    """Componentwise error relative to |A||B| (the §2.5 error measure)."""
    denom = np.abs(a) @ np.abs(b) + 1e-300
    return np.max(np.abs(c - a @ b) / denom)


@pytest.mark.parametrize("substrate", ["int8", "fp8"])
@pytest.mark.parametrize("mkn", [(8, 16, 8), (32, 64, 24), (17, 130, 9), (64, 1024, 32)])
def test_accuracy_well_conditioned(substrate, mkn):
    m, k, n = mkn
    a = RNG.standard_normal((m, k))
    b = RNG.standard_normal((k, n))
    plan = ozaki2.make_plan(k, substrate=substrate)
    c = np.asarray(ozaki2.emulated_matmul(jnp.asarray(a), jnp.asarray(b), plan))
    # Paper §2.5: observed error within 2–10 u for bounded condition numbers.
    assert _relerr(c, a, b) <= 16 * U64


@pytest.mark.parametrize("substrate", ["int8", "fp8"])
def test_accuracy_wide_dynamic_range(substrate):
    """App. C / [32]: error bounded by u|A||B| plus the Phase-1 quantisation term.

    For rows/cols with heterogeneous magnitudes the ⌊D A⌉ rounding of eq. (4)
    contributes E_A = 0.5·2^{-shift_A} per element; the a-priori componentwise bound
    is |C - AB| <= c₁·u·(|A||B|) + c₂·(E_A|B| + |A|E_B + k·E_A E_B).
    """
    k = 256
    a = RNG.standard_normal((16, k)) * np.exp(2 * RNG.standard_normal((16, k)))
    b = RNG.standard_normal((k, 12)) * np.exp(2 * RNG.standard_normal((k, 12)))
    plan = ozaki2.make_plan(k, substrate=substrate)
    c = np.asarray(ozaki2.emulated_matmul(jnp.asarray(a), jnp.asarray(b), plan))

    from repro.core import splitting
    _, sa = splitting.scale_to_int(jnp.asarray(a), plan.payload_bits, axis=-1)
    _, sb = splitting.scale_to_int(jnp.asarray(b), plan.payload_bits, axis=0)
    ea = 0.5 * 2.0 ** (-np.asarray(sa, np.float64))       # per-row abs rounding
    eb = 0.5 * 2.0 ** (-np.asarray(sb, np.float64))       # per-col abs rounding
    quant = (ea[:, None] * np.sum(np.abs(b), axis=0)[None, :]
             + np.sum(np.abs(a), axis=1)[:, None] * eb[None, :]
             + k * ea[:, None] * eb[None, :])
    bound = 8 * U64 * (np.abs(a) @ np.abs(b)) + 2.0 * quant
    assert np.all(np.abs(c - a @ b) <= bound)


def test_int8_and_fp8_substrates_bit_identical():
    """Both substrates compute the same exact modular products -> identical output."""
    k = 192
    a = jnp.asarray(RNG.standard_normal((24, k)))
    b = jnp.asarray(RNG.standard_normal((k, 16)))
    c_int8 = ozaki2.emulated_matmul(a, b, ozaki2.make_plan(k, substrate="int8"))
    c_fp8 = ozaki2.emulated_matmul(a, b, ozaki2.make_plan(k, substrate="fp8"))
    np.testing.assert_array_equal(np.asarray(c_int8), np.asarray(c_fp8))


def test_exact_on_small_integer_matrices():
    """CRT roundtrip: products of smallish integers are recovered EXACTLY."""
    k = 64
    a = jnp.asarray(RNG.integers(-1000, 1000, (16, k)).astype(np.float64))
    b = jnp.asarray(RNG.integers(-1000, 1000, (k, 8)).astype(np.float64))
    plan = ozaki2.make_plan(k)
    c = np.asarray(ozaki2.emulated_matmul(a, b, plan))
    want = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    np.testing.assert_array_equal(c, want)


def test_garner_against_python_bigint():
    """Vectorised balanced Garner == exact CRT with arbitrary-precision ints."""
    plan = ozaki2.make_plan(4096)  # r = 16
    gc = plan.garner
    vals = np.concatenate([
        RNG.integers(-(10 ** 15), 10 ** 15, 64),
        np.array([0, 1, -1, 2 ** 40, -(2 ** 40)]),
    ])
    # residues as the modular matmul would produce them (balanced)
    cres = np.stack([
        np.array([M.balanced(int(v), m) for v in vals], np.int32)
        for m in plan.moduli
    ])
    got = np.asarray(ozaki2.garner_reconstruct(jnp.asarray(cres), plan))
    np.testing.assert_array_equal(got, vals.astype(np.float64))


@pytest.mark.parametrize("k", [64, 4096])   # r = 15 and r = 16 moduli
def test_float32_pair_epilogue(monkeypatch, k):
    """Where float64 is a float32 pair (XLA:TPU) the digits run the
    double-single Horner: finite for the largest prefix products of a
    16-modulus plan (~2**116, where a Veltkamp split overflows float32) and
    accurate to the ~45 bits that double-single carries."""
    monkeypatch.setattr(backend, "float64_is_f32_pair", lambda: True)
    a = RNG.standard_normal((12, k))
    b = RNG.standard_normal((k, 6))
    c = np.asarray(ozaki2.emulated_matmul(jnp.asarray(a), jnp.asarray(b),
                                          ozaki2.make_plan(k)))
    assert np.all(np.isfinite(c))
    assert _relerr(c, a, b) <= 2.0 ** -44


def test_modular_matmul_congruence():
    """C^(i) ≡ ÃB̃ (mod m_i) for every modulus, both substrates."""
    k = 128
    a = jnp.asarray(RNG.standard_normal((8, k)))
    b = jnp.asarray(RNG.standard_normal((k, 8)))
    for substrate in ("int8", "fp8"):
        plan = ozaki2.make_plan(k, substrate=substrate)
        ares, _ = ozaki2.decompose(a, plan, scale_axis=-1)
        bres, _ = ozaki2.decompose(b, plan, scale_axis=0)
        cres = np.asarray(ozaki2.modular_matmul(ares, bres, plan))
        ai = np.asarray(ares, np.int64)
        bi = np.asarray(bres, np.int64)
        for i, m in enumerate(plan.moduli):
            want = (ai[i] @ bi[i]) % m
            got = cres[i] % m
            np.testing.assert_array_equal(got, want)
            # balanced representatives
            assert cres[i].min() >= -(m // 2) and cres[i].max() <= (m - 1) // 2


def test_long_contraction_chunking():
    """k beyond the int32-safe chunk still gives FP64-grade accuracy."""
    k = (1 << 17) + 1024  # forces the chunked path
    a = RNG.standard_normal((4, k))
    b = RNG.standard_normal((k, 4))
    plan = ozaki2.make_plan(k)
    c = np.asarray(ozaki2.emulated_matmul(jnp.asarray(a), jnp.asarray(b), plan))
    assert _relerr(c, a, b) <= 64 * U64


def test_hilo_and_direct_paths_identical():
    k = 96
    a = jnp.asarray(RNG.standard_normal((8, k)))
    b = jnp.asarray(RNG.standard_normal((k, 8)))
    plan = ozaki2.make_plan(k)
    c1 = ozaki2.emulated_matmul(a, b, plan, via_hilo=True)
    c2 = ozaki2.emulated_matmul(a, b, plan, via_hilo=False)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


def test_batched():
    a = jnp.asarray(RNG.standard_normal((3, 8, 32)))
    b = jnp.asarray(RNG.standard_normal((3, 32, 8)))
    plan = ozaki2.make_plan(32)
    c = np.asarray(ozaki2.emulated_matmul_batched(a, b, plan))
    want = np.einsum("bij,bjk->bik", np.asarray(a), np.asarray(b))
    denom = np.einsum("bij,bjk->bik", np.abs(np.asarray(a)), np.abs(np.asarray(b)))
    assert np.max(np.abs(c - want) / denom) <= 16 * U64


def test_reduced_r_degrades_gracefully():
    """§2.4 sensitivity: fewer moduli -> smaller payload -> larger (bounded) error."""
    k = 256
    a = jnp.asarray(RNG.standard_normal((16, k)))
    b = jnp.asarray(RNG.standard_normal((k, 16)))
    errs = []
    for r in (8, 10, 12, 14):
        plan = ozaki2.make_plan(k, r=r)
        c = np.asarray(ozaki2.emulated_matmul(a, b, plan))
        errs.append(_relerr(c, np.asarray(a), np.asarray(b)))
    assert errs == sorted(errs, reverse=True) or errs[-1] <= errs[0]
    assert errs[0] <= 2.0 ** -20  # r=8 still ~fp32-grade
    assert errs[-1] <= 16 * U64


def test_plan_alpha():
    p = ozaki2.make_plan(4096, substrate="int8")
    assert p.alpha == p.r
    p8 = ozaki2.make_plan(4096, substrate="fp8")
    assert p8.alpha == 3 * p8.r
