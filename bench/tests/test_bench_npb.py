"""The ``cg_npb`` cell on the CPU at class S: the work function by hand, the
reference against a dense product, a sound run correct, the steps carried
and reset over more than NPB's niter, the control and a planted fault not
correct, and a matrix unlike NPB's verified build refused."""

import jax
import numpy as np
import pytest

from bench import harness
from bench.configs import cg_npb_ref

CLASS_S = {"class": "S", "na": 1400, "nonzer": 7, "shift": 10, "rcond": 0.1,
           "cg_iterations": 25, "check_steps": 2}
SEED = 2 ** 31 + 4242


def run_npb(**kw):
    return harness.run("cg_npb.class_b", SEED, 0.5, False, require_chip=False,
                       traffic=CLASS_S, **kw)


def test_npb_iteration_work_by_hand():
    # 10 rows, 37 nonzeros: SpMV 2*37, dots 2*2*10, axpys 3*2*10; the nonzeros'
    # values and columns 37*(8+4) bytes, x, r, p read and written 6*8*10.
    flops, bytes_ = cg_npb_ref.work(10, 37)
    assert flops == 74 + 40 + 60
    assert bytes_ == 37 * 12 + 480


def test_reference_apply_is_the_csr_product():
    rng = np.random.default_rng(3)
    dense = rng.standard_normal((9, 9)) * (rng.random((9, 9)) < 0.4)
    dense[4] = 0.0                                        # an empty row
    rows, cols = np.nonzero(dense)
    rowptr = np.r_[0, np.cumsum(np.count_nonzero(dense, axis=1))]
    x = rng.standard_normal(9)
    got = cg_npb_ref.apply(rowptr, cols, dense[rows, cols], x)
    np.testing.assert_allclose(got, dense @ x, rtol=0, atol=1e-14)


def test_sound_npb_run_is_correct():
    res = run_npb()
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["compared"]) == {"z_rel_err", "zeta_rel_err"}
    assert set(res["metrics"]) == {"cg_iter_ms", "setup_s"}


def test_npb_control_is_not_correct():
    res = run_npb(control=True)
    assert not res["correct"], res["compared"]


def test_npb_dropped_slot_is_not_correct(monkeypatch):
    """One ELL slot of every row dropped from the operator the program gets;
    the reference keeps the CSR matrix."""
    from repro.hpc import spmv_formats

    build = spmv_formats.csr_to_blocked_ell

    def dropped(*a, **kw):
        val, col = build(*a, **kw)
        val = val.copy()
        val[:, 1] = 0.0
        return val, col

    jax.clear_caches()
    res = run_npb(patch=lambda: monkeypatch.setattr(
        spmv_formats, "csr_to_blocked_ell", dropped))
    assert not res["correct"], res["compared"]


def test_npb_traffic_must_be_the_class():
    with pytest.raises(ValueError, match="shift"):
        harness.run("cg_npb.class_b", SEED, 0.5, False, require_chip=False,
                    traffic={**CLASS_S, "shift": 12})



def test_npb_steps_carry_x_and_reset_after_niter():
    """Class S's 15 steps and two more, every one checked: each step starts
    from the last one's x, and step 15 from x = 1 again, as step 0 did."""
    harness.configure_jax()
    spec = harness.Spec("cg_npb.class_b")
    niter = 15
    cell = spec.cell_module().Cell(spec.config, {**CLASS_S, "check_steps": 99},
                                   SEED)
    cell.setup()
    cell.warm()
    for i in range(niter + 2):
        assert cell.step(i) == 25
    kept = sorted(k for k, _, _ in cell.kept.items)
    assert kept == sorted(list(range(niter)) + [0, 1])
    carried = {}
    for k, x, _ in cell.kept.items:
        carried.setdefault(k, []).append(np.asarray(x))
    # After the reset, steps 0 and 1 repeat the first two to the bit.
    for k in (0, 1):
        np.testing.assert_array_equal(carried[k][0], carried[k][1])
    limits = spec.limits
    compared = cell.check()
    assert all(v <= limits[k]["limit"] for k, v in compared.items()), compared


def test_npb_matrix_unlike_the_verified_build_is_refused(monkeypatch):
    """One value of NPB's matrix changed by an ulp, on both sides alike: the
    reference refuses it before it replays a step."""
    from repro.hpc import npb_cg

    makea = npb_cg.makea

    def nudged(cls):
        rowptr, col, val = makea(cls)
        val = val.copy()
        val[7] = np.nextafter(val[7], np.inf)
        return rowptr, col, val

    with pytest.raises(ValueError, match="SHA-256"):
        run_npb(patch=lambda: monkeypatch.setattr(npb_cg, "makea", nudged))
