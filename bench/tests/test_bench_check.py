"""The check that decides ``correct``, driven through a whole run on the CPU
at tiny sizes: a sound run passes; the control (the reference in float32 in
the program's place) and each fault a cell can have, planted in the timed
path, fail.  The run skips only the harness's look for a chip."""

import jax
import jax.numpy as jnp
import pytest

from bench import harness

TINY = {
    "dgemm.square": {"m": 256, "k": 256, "n": 256, "a_pool": 2, "b_pool": 2,
                     "check_calls": 2, "check_band": 128},
    "cg_poisson7.n128": {"grid": 12, "set_iterations": 20, "rhs_pool": 2,
                         "check_sets": 2},
}
ALTER = 1 + 2.0 ** -24     # a relative change of one float32 rounding


def run(cell, seed=2 ** 31 + 7, **kw):
    return harness.run(cell, seed, 0.5, False, require_chip=False,
                       traffic=TINY[cell], **kw)


def _wrap(monkeypatch, module, name, make):
    monkeypatch.setattr(module, name, make(getattr(module, name)))


def answer_altered_gemm(monkeypatch):
    from repro.core import dispatch
    _wrap(monkeypatch, dispatch, "matmul",
          lambda f: lambda a, b, **kw: f(a, b, **kw) * ALTER)


def half_batch_gemm(monkeypatch):
    """Half of the right-hand sides left out (their columns zero)."""
    from repro.core import dispatch

    def make(f):
        def half(a, b, **kw):
            h = b.shape[1] // 2
            out = f(a, b[:, :h], **kw)
            return jnp.concatenate([out, jnp.zeros_like(out)], axis=1)
        return half
    _wrap(monkeypatch, dispatch, "matmul", make)


def answer_altered_spmv(monkeypatch):
    from repro.core import dispatch
    _wrap(monkeypatch, dispatch, "spmv",
          lambda f: lambda *a, **kw: f(*a, **kw) * ALTER)


def step_unchanged_cg(monkeypatch):
    """One CG step that returns its state unchanged: the set's last
    iteration is skipped, and the set still reports all of them."""
    from repro.hpc import cg

    def make(f):
        def solve(*a, maxiter, **kw):
            res = f(*a, maxiter=maxiter - 1, **kw)
            res.iters = maxiter
            return res
        return solve
    _wrap(monkeypatch, cg, "cg_solve_bell", make)


FAULTS = [
    ("dgemm.square", answer_altered_gemm),
    ("dgemm.square", half_batch_gemm),
    ("cg_poisson7.n128", answer_altered_spmv),
    ("cg_poisson7.n128", step_unchanged_cg),
]


@pytest.mark.parametrize("cell", sorted(TINY))
def test_sound_run_is_correct(cell):
    res = run(cell)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "compared"
    assert "setup_s" in res["metrics"]


@pytest.mark.parametrize("cell", sorted(TINY))
def test_control_is_not_correct(cell):
    res = run(cell, control=True)
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_fault_is_not_correct(cell, fault, monkeypatch):
    jax.clear_caches()
    res = run(cell, patch=lambda: fault(monkeypatch))
    assert not res["correct"], res["compared"]


def test_traced_run_without_a_chip_reads_no_device_metric():
    """The trace path runs end to end; with no device plane in the trace
    every reader returns nothing, and nothing reads 0."""
    res = harness.run("dgemm.square", 11, 0.3, True, require_chip=False,
                      traffic=TINY["dgemm.square"])
    assert res["correct"]
    assert res["metrics"] == {}
    assert res["device"]["busy_s"] == 0.0 and res["device"]["window_s"] > 0
    assert res["breakdown"] == {"device_ops": [], "idle_gaps": []}


def test_failing_call_is_counted_and_not_correct(monkeypatch):
    from bench.configs import dgemm

    def broken(self, i):
        raise RuntimeError("planted")
    monkeypatch.setattr(dgemm.Cell, "step", broken)
    res = run("dgemm.square")
    assert res["failed"] == 1 and res["attempted"] == 1
    assert not res["correct"] and res["compared"] == {}
    assert list(res["metrics"]) == ["setup_s"]
