"""§7.1(a) integration: CG with Ozaki-II SpMV + compensated dots."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.hpc import spmv_formats
from repro.hpc.cg import cg_solve, cg_solve_bell


def test_blocked_ell_roundtrip():
    dense = spmv_formats.laplacian_1d(32)
    val, col = spmv_formats.to_blocked_ell(dense, bw=4)
    # reconstruct
    back = np.zeros_like(dense)
    for i in range(32):
        for s in range(4):
            back[i, col[i, s]] += val[i, s]
    np.testing.assert_array_equal(back, dense)
    assert spmv_formats.padding_ratio(val) == pytest.approx(128 / 94, rel=0.01)


def test_bell_rejects_overfull_rows():
    dense = np.ones((4, 8))
    with pytest.raises(ValueError):
        spmv_formats.to_blocked_ell(dense, bw=4)


def test_cg_native_converges():
    dense = spmv_formats.laplacian_2d(8, 8)
    rng = np.random.default_rng(0)
    b = jnp.asarray(rng.standard_normal(64))
    res = cg_solve(lambda x: jnp.asarray(dense) @ x, b, tol=1e-10)
    assert res.converged
    x = np.asarray(res.x)
    np.testing.assert_allclose(dense @ x, np.asarray(b), atol=1e-8)


def test_cg_with_ozaki_spmv_matches_native():
    """The paper's claim: the emulated path changes nothing for the solver.

    mode="xla" pins the matvec to the bit-identical jnp reference route
    (route-independent result; the interpret-mode Pallas path, with its
    multi-minute XLA compile at the default plan, is covered by the slow
    parity test in test_kernels.py — pinning keeps the CI
    REPRO_DISPATCH=pallas leg off that compile).
    """
    dense = spmv_formats.laplacian_2d(8, 8)
    val, col = spmv_formats.to_blocked_ell(dense, bw=8)
    rng = np.random.default_rng(1)
    b = jnp.asarray(rng.standard_normal(64))
    ref = cg_solve(lambda x: jnp.asarray(dense) @ x, b, tol=1e-10)
    emu = cg_solve_bell(jnp.asarray(val), jnp.asarray(col), b, tol=1e-10,
                        mode="xla")
    assert emu.converged
    assert abs(emu.iters - ref.iters) <= 1   # convergence history preserved
    np.testing.assert_allclose(np.asarray(emu.x), np.asarray(ref.x),
                               rtol=0, atol=1e-8)


def test_cg_residual_history_monotonic_tail():
    dense = spmv_formats.laplacian_1d(48)
    b = jnp.asarray(np.random.default_rng(2).standard_normal(48))
    res = cg_solve(lambda x: jnp.asarray(dense) @ x, b, tol=1e-10, maxiter=200)
    assert res.converged
    assert res.history[-1] < 1e-10


def test_cg_records_plain_and_compensated_histories():
    """Both residual histories cover every iterate and measure the same r."""
    dense = spmv_formats.laplacian_1d(32)
    b = jnp.asarray(np.random.default_rng(3).standard_normal(32))
    res = cg_solve(lambda x: jnp.asarray(dense) @ x, b, tol=1e-10)
    assert len(res.history_plain) == len(res.history) == res.iters + 1
    # In f64 the two agree to rounding; they are distinct computations.
    np.testing.assert_allclose(res.history_plain, res.history, rtol=1e-10)
    # Opt-out drops the shadow reduction entirely.
    quiet = cg_solve(lambda x: jnp.asarray(dense) @ x, b, tol=1e-10,
                     record_plain=False)
    assert quiet.history_plain == [] and quiet.converged


def test_cg_compensated_vs_plain_delta_observable_f32():
    """In f32 the plain-dot residual history drifts from the compensated one
    by far more than f64 roundoff — the §7.1(a) delta, made visible."""
    dense = jnp.asarray(spmv_formats.laplacian_2d(8, 8), jnp.float32)
    b = jnp.asarray(np.random.default_rng(4).standard_normal(64), jnp.float32)
    res = cg_solve(lambda x: dense @ x, b, tol=1e-6, maxiter=80)
    deltas = [abs(p - c) / max(c, 1e-30)
              for p, c in zip(res.history_plain, res.history)]
    # same quantity ...
    assert max(deltas) < 1e-2
    # ... but the plain-f32 reductions are visibly off the compensated ones
    # (the compensated dot carries ~2^-48; plain f32 only ~2^-24·n).
    assert max(deltas) > 2.0 ** -24


def test_cg_iteration_counts_unchanged_by_blocked_eft():
    """The blocked-EFT swap must not move CG's trajectory: driving the
    recurrence with the element-wise scan reference (the pre-blocking
    implementation) yields the same iteration count and the same residual
    history to a few ulps."""
    from repro.core import compensated

    dense = jnp.asarray(spmv_formats.laplacian_2d(8, 8))
    b = jnp.asarray(np.random.default_rng(5).standard_normal(64))
    blocked = cg_solve(lambda x: dense @ x, b, tol=1e-10, maxiter=200,
                       record_plain=False)
    scan = cg_solve(lambda x: dense @ x, b, tol=1e-10, maxiter=200,
                    dot=compensated.compensated_dot_scan,
                    record_plain=False)
    assert blocked.converged and scan.converged
    assert blocked.iters == scan.iters
    np.testing.assert_allclose(blocked.history, scan.history, rtol=1e-12)


# ---------------------------------------------------------------------------
# Banded operators: the SpMV reads x by static shifts
# ---------------------------------------------------------------------------

def _banded(m, n, offsets, rng):
    """(m, bw) Blocked-ELL of a banded m x n matrix; slots past the matrix's
    edge hold value 0 and point at random columns."""
    r = np.arange(m)[:, None]
    col = r + np.asarray(offsets)
    inside = (col >= 0) & (col < n)
    val = np.where(inside, rng.standard_normal((m, len(offsets))), 0.0)
    col = np.where(inside, col, rng.integers(0, n, col.shape)).astype(np.int32)
    return val, col


def _off_diagonal():
    val, col = spmv_formats.laplacian_3d_bell(3)
    col[13, 4] += 1                      # one nonzero leaves its diagonal
    return val, col


def _random_sparse():
    rng = np.random.default_rng(4)
    return rng.standard_normal((40, 5)), rng.integers(0, 40, (40, 5)).astype(np.int32)


@pytest.mark.parametrize("operator, want", [
    (lambda: spmv_formats.laplacian_3d_bell(3), (0, -9, 9, -3, 3, -1, 1)),
    (lambda: spmv_formats.laplacian_3d_bell(1), (0, 0, 0, 0, 0, 0, 0)),
    (lambda: _banded(20, 30, (-3, 0, 7), np.random.default_rng(5)), (-3, 0, 7)),
    (lambda: spmv_formats.to_blocked_ell(spmv_formats.laplacian_1d(12), 4), None),
    (_random_sparse, None),
    (_off_diagonal, None),
], ids=["poisson3d", "all-zero-slots", "zero-slots-anywhere", "left-packed",
        "random", "one-off-diagonal"])
def test_band_offsets(operator, want):
    val, col = operator()
    assert spmv_formats.band_offsets(val, col) == want
    assert spmv_formats.band_offsets(jnp.asarray(val), jnp.asarray(col)) == want


def test_band_offsets_of_tracers_is_none():
    val, col = spmv_formats.laplacian_3d_bell(2)
    seen = []
    jax.jit(lambda v, c: seen.append(spmv_formats.band_offsets(v, c)) or v)(val, col)
    assert seen == [None]


def test_cg_bell_band_shifts_bit_identical(monkeypatch):
    """CG on the 3-D Poisson operator with the pallas SpMV: the iterates of
    the static-shift path are those of the gather, bit for bit."""
    from repro.core import ozaki2
    from repro.obs import telemetry as obs

    val, col = (jnp.asarray(t) for t in spmv_formats.laplacian_3d_bell(3))
    b = jnp.asarray(np.random.default_rng(6).standard_normal(27))
    plan = ozaki2.make_plan(7, payload_bits=24, margin_bits=4)
    kw = dict(plan=plan, mode="pallas", tol=0.0, maxiter=6)
    obs.reset()
    with obs.telemetry_scope("counters"):
        shifted = cg_solve_bell(val, col, b, **kw)
        monkeypatch.setattr(spmv_formats, "band_offsets", lambda *a: None)
        gathered = cg_solve_bell(val, col, b, **kw)
    assert obs.cache_snapshot()["spmv_band"] == (6, 6)   # 6 matvecs each
    obs.reset()
    np.testing.assert_array_equal(np.asarray(shifted.x), np.asarray(gathered.x))
    assert shifted.history == gathered.history


def test_cg_bell_vmem_gather_bit_identical(monkeypatch):
    """CG on NPB's class-S matrix with the pallas SpMV: the iterates of the
    in-kernel gather of x from VMEM are those of XLA's gather, bit for bit."""
    from repro.core import ozaki2
    from repro.hpc import npb_cg
    from repro.kernels import ozaki_spmv
    from repro.obs import telemetry as obs

    val, col = (jnp.asarray(t) for t in
                spmv_formats.csr_to_blocked_ell(*npb_cg.makea("S")))
    b = jnp.ones(val.shape[0])
    plan = ozaki2.make_plan(val.shape[1], payload_bits=24, margin_bits=4)
    kw = dict(plan=plan, mode="pallas", tol=0.0, maxiter=3)
    obs.reset()
    try:
        with obs.telemetry_scope("counters"):
            in_kernel = cg_solve_bell(val, col, b, **kw)
            monkeypatch.setattr(ozaki_spmv, "X_VMEM_MAX", 0)
            ozaki_spmv.spmv_bell.clear_cache()       # the route is traced in
            gathered = cg_solve_bell(val, col, b, **kw)
        assert obs.cache_snapshot()["spmv_vmem"] == (3, 3)   # 3 matvecs each
    finally:
        ozaki_spmv.spmv_bell.clear_cache()
        obs.reset()
    np.testing.assert_array_equal(np.asarray(in_kernel.x), np.asarray(gathered.x))
    assert in_kernel.history == gathered.history


def test_cg_from_zero_skips_the_product_with_zero():
    """With x0 None the solve starts from r = b, one SpMV fewer than from an
    explicit x0 = 0, and its iterates are those of b - A 0, bit for bit."""
    from repro.core import dispatch

    val, col = (jnp.asarray(t) for t in spmv_formats.laplacian_3d_bell(4))
    b = jnp.asarray(np.random.default_rng(8).standard_normal(64))
    calls = []

    def matvec(x):
        calls.append(1)
        return dispatch.spmv(val, col, x)
    kw = dict(tol=0.0, maxiter=8, record_plain=True)
    skipped = cg_solve(matvec, b, **kw)
    n_skipped = len(calls)
    explicit = cg_solve(matvec, b, x0=jnp.zeros_like(b), **kw)
    assert (n_skipped, len(calls) - n_skipped) == (8, 9)
    np.testing.assert_array_equal(np.asarray(skipped.x), np.asarray(explicit.x))
    assert skipped.history == explicit.history
    assert skipped.history_plain == explicit.history_plain
