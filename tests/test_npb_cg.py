"""NPB CG (``repro.hpc.npb_cg``): NPB's generator, its verification values
through ``cg_solve_bell``, and one outer step against a plain float64 CSR
reference; the CSR to Blocked-ELL builder it runs on."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.hpc import npb_cg, spmv_formats


@pytest.fixture(autouse=True)
def _x64():
    jax.config.update("jax_enable_x64", True)


def _random_sparse(rng, m, n, density):
    dense = rng.standard_normal((m, n)) * (rng.random((m, n)) < density)
    rowptr = np.r_[0, np.cumsum(np.count_nonzero(dense, axis=1))]
    rows, cols = np.nonzero(dense)
    return dense, rowptr, cols.astype(np.int32), dense[rows, cols]


def _ell_by_rows(dense, bw):
    """The layout row by row: each row's nonzeros in column order, padded
    slots value 0 at the row itself, or at the largest nonzero column where
    the row number is past it."""
    m, _ = dense.shape
    last = int(np.nonzero(dense)[1].max(initial=0))
    val = np.zeros((m, bw))
    col = np.zeros((m, bw), np.int32)
    for i in range(m):
        nz = np.nonzero(dense[i])[0]
        val[i, :len(nz)] = dense[i, nz]
        col[i] = min(i, last)
        col[i, :len(nz)] = nz
    return val, col


@pytest.mark.parametrize("m,n,density,seed", [
    (16, 16, 0.3, 0), (40, 40, 0.1, 1), (33, 20, 0.5, 2), (20, 33, 0.2, 6),
    (8, 8, 0.0, 3)])
def test_csr_to_blocked_ell_matches_dense_builder(m, n, density, seed):
    dense, rowptr, col, val = _random_sparse(np.random.default_rng(seed), m, n,
                                             density)
    bw = max(1, int(np.diff(rowptr).max()))
    want_val, want_col = _ell_by_rows(dense, bw)
    for got_val, got_col in (spmv_formats.csr_to_blocked_ell(rowptr, col, val, bw),
                             spmv_formats.to_blocked_ell(dense, bw)):
        assert got_val.shape == got_col.shape == (m, bw)
        assert got_col.dtype == np.int32
        np.testing.assert_array_equal(got_val, want_val)
        np.testing.assert_array_equal(got_col, want_col)
        assert got_col.max() < n                  # every slot reads a column


def test_csr_to_blocked_ell_width_defaults_to_longest_row():
    _, rowptr, col, val = _random_sparse(np.random.default_rng(4), 30, 30, 0.2)
    got_val, _ = spmv_formats.csr_to_blocked_ell(rowptr, col, val)
    assert got_val.shape[1] == np.diff(rowptr).max()


def test_csr_to_blocked_ell_rejects_overfull_rows():
    _, rowptr, col, val = _random_sparse(np.random.default_rng(5), 6, 6, 1.0)
    with pytest.raises(ValueError, match="> bw=5"):
        spmv_formats.csr_to_blocked_ell(rowptr, col, val, bw=5)


def test_makea_is_symmetric_with_shifted_diagonal():
    c = npb_cg.CLASSES["S"]
    rowptr, col, val = npb_cg.makea("S")
    assert rowptr.shape == (c.na + 1,) and rowptr[-1] == col.size == val.size
    assert rowptr[-1] == 78148
    rows = np.repeat(np.arange(c.na), np.diff(rowptr))
    assert np.all(np.diff(col)[np.diff(rows) == 0] > 0)        # sorted, unique
    dense = np.zeros((c.na, c.na))
    dense[rows, col] = val
    # Symmetric up to the rounding of v_r * (size * v_c) against v_c * (size * v_r).
    np.testing.assert_allclose(dense, dense.T, rtol=0, atol=1e-14)
    # The diagonal: RCOND - SHIFT plus the outer products' squares, at least
    # vector i's 0.5^2 scaled by RCOND^(i/na).
    assert np.all(np.diag(dense) >= c.rcond - c.shift + 0.25 * c.rcond)


@pytest.mark.parametrize("cls", ["S", "W"])
def test_run_reproduces_npb_verification(cls):
    """NPB's published zeta after the class's outer steps, at NPB's 1e-10:
    the test that ``makea`` is NPB's generator."""
    res = npb_cg.run(cls)
    assert res.verified, res
    assert res.rel_err <= npb_cg.VERIFY_TOL
    assert len(res.rnorms) == npb_cg.CLASSES[cls].niter


def _csr_step(rowptr, col, val, x, shift, iters, dtype):
    """NPB's outer step in plain NumPy on CSR, in ``dtype``."""
    val, x = val.astype(dtype), x.astype(dtype)

    def apply(v):
        return np.add.reduceat(val * v[col], rowptr[:-1])
    z, r = np.zeros_like(x), x
    p, rho = r, r @ r
    for _ in range(iters):
        q = apply(p)
        alpha = rho / (p @ q)
        z, r = z + alpha * p, r - alpha * q
        rho, rho0 = r @ r, rho
        p = r + (rho / rho0) * p
    rnorm = np.linalg.norm(x - apply(z))
    return z / np.linalg.norm(z), shift + 1 / (x @ z), rnorm


def test_power_step_matches_float64_csr_reference():
    c = npb_cg.CLASSES["S"]
    rowptr, col, val = npb_cg.makea("S")
    a_val, a_col = (jnp.asarray(t) for t in
                    spmv_formats.csr_to_blocked_ell(rowptr, col, val))
    x0 = np.random.default_rng(7).random(c.na) + 0.5
    x, zeta, rnorm = npb_cg.power_step(a_val, a_col, jnp.asarray(x0), c.shift)
    want_x, want_zeta, want_rnorm = _csr_step(rowptr, col, val, x0, c.shift, 25,
                                              np.float64)

    def errs(x_, zeta_):
        return (np.linalg.norm(np.asarray(x_) - want_x) / np.linalg.norm(want_x),
                abs(float(zeta_) - want_zeta) / abs(want_zeta))
    z_err, zeta_err = errs(x, zeta)
    assert z_err <= 1e-12 and zeta_err <= 1e-13, (z_err, zeta_err)
    assert float(rnorm) == pytest.approx(want_rnorm, rel=0.5)   # ~1e-13 residual
    # Float32 arithmetic fails both bounds.
    f32 = errs(*_csr_step(rowptr, col, val, x0, c.shift, 25, np.float32)[:2])
    assert f32[0] > 1e-12 and f32[1] > 1e-13, f32
