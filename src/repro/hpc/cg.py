"""Conjugate Gradient with the paper's post-FP64 kernel stack (§7.1(a)).

The audit's recipe for iterative solvers on FP64-starved hardware:
  * the SpMV (the dominant cost) runs through the fused Ozaki-II Blocked-ELL
    kernel at FP64-equivalent accuracy,
  * the BLAS-1 reductions (dot products, norms) run on the healthy vector pipe
    with compensated accumulation (``repro.core.compensated``) — "B300's FP32
    pipe is well above the BLAS-1 memory-roof requirement; not binding",
  * no iterative-refinement outer loop is needed: the emulated SpMV inherits
    the componentwise error bound of the emulated GEMM (§2.5).

The residual recurrence is driven by the compensated reductions; alongside it
the solver records the same quantities re-computed with plain working-precision
dots (``history_plain``) so the accuracy delta of the compensated path is
directly observable (tests/test_hpc_cg.py).

``cg_solve`` is generic over the matvec; ``cg_solve_bell`` wires in the
Blocked-ELL SpMV kernel and ``cg_solve_dense`` the dispatch-routed dense GEMV.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp

from repro.core import compensated, dispatch, ozaki2
from repro.hpc import spmv_formats
from repro.obs import spans, telemetry as obs


@dataclasses.dataclass
class CGResult:
    x: jax.Array
    iters: int
    residual: float
    converged: bool
    history: list                 # compensated relative-residual recurrence
    history_plain: list = dataclasses.field(default_factory=list)
    # same reductions in plain working precision (observability, not control)


def cg_solve(matvec: Callable[[jax.Array], jax.Array], b: jax.Array,
             x0: Optional[jax.Array] = None, tol: float = 1e-10,
             maxiter: int = 500,
             dot: Callable = compensated.compensated_dot,
             norm: Callable = compensated.compensated_norm,
             record_plain: bool = True) -> CGResult:
    """Textbook CG; compensated reductions drive the recurrence and the stop
    test, a plain-dot shadow history records what uncompensated working
    precision would have reported for the same iterates.  ``record_plain=False``
    drops the shadow reduction (one extra O(n) dot + host sync per iteration)
    for production solves that never read it.  With ``x0`` None the solve
    starts from x = 0 and r = b, with no product of A and zero."""
    if x0 is None:
        x, r = jnp.zeros_like(b), b
    else:
        x, r = x0, b - matvec(x0)
    p = r
    rs = dot(r, r)
    bnorm = norm(b)
    bnorm_plain = jnp.sqrt(jnp.dot(b, b)) if record_plain else None

    history: List[float] = [_read(jnp.sqrt(rs) / bnorm)]
    history_plain: List[float] = []
    # Residual-trace telemetry: one event per recorded residual (iteration 0
    # included), so convergence trajectories are observable alongside the
    # per-op seam events the matvec itself records.
    obs.record_event("solver.cg", dims=b.shape, iter=0, rel_residual=history[0])
    if record_plain:
        history_plain.append(_read(jnp.sqrt(jnp.dot(r, r)) / bnorm_plain))
    it = 0
    for it in range(1, maxiter + 1):
        with spans.span("cg.iter"):
            ap = matvec(p)
            alpha = rs / dot(p, ap)
            x = x + alpha * p
            r = r - alpha * ap
            rs_new = dot(r, r)
            history.append(_read(jnp.sqrt(rs_new) / bnorm))
            obs.record_event("solver.cg", dims=b.shape, iter=it,
                             rel_residual=history[-1])
            if record_plain:
                history_plain.append(_read(jnp.sqrt(jnp.dot(r, r)) / bnorm_plain))
            if history[-1] < tol:
                return CGResult(x, it, history[-1], True, history, history_plain)
            p = r + (rs_new / rs) * p
            rs = rs_new
    return CGResult(x, it, history[-1], False, history, history_plain)


def _read(v: jax.Array) -> float:
    """A device scalar read on the host: the loop waits here for the device,
    so each read is a ``sync`` span."""
    with spans.span("sync"):
        return float(v)


def cg_solve_bell(a_val: jax.Array, a_col: jax.Array, b: jax.Array,
                  plan: Optional[ozaki2.Plan] = None, out_rep: str = "f64",
                  mode: Optional[str] = None, **kw) -> CGResult:
    """CG with the Ozaki-II Blocked-ELL SpMV as the matvec, dispatch-routed.

    The plan resolves once from the dispatch cache (not per iteration); the
    SpMV route follows ``mode`` / ``mode_scope`` / ``REPRO_DISPATCH`` like
    every multiplication behind the seam — the sparse-LA dwarf's §7.1(a)
    recipe with the emulated kernel as a uniformly-routed drop-in.  A banded
    operator is found once, before the loop (``spmv_formats.band_offsets``),
    and every matvec then reads x by static shifts instead of a gather.
    """
    if plan is None:
        plan = dispatch.get_plan(a_val.shape[1], margin_bits=4)
    offsets = spmv_formats.band_offsets(a_val, a_col)

    def matvec(x):
        return dispatch.spmv(a_val, a_col, x, plan=plan, out_rep=out_rep,
                             mode=mode, offsets=offsets)
    return cg_solve(matvec, b, **kw)


def cg_solve_dense(a: jax.Array, b: jax.Array,
                   plan: Optional[ozaki2.Plan] = None,
                   mode: Optional[str] = None, **kw) -> CGResult:
    """CG on a dense SPD matrix with the emulated matvec routed through the
    dispatch layer (XLA reference or fused Pallas GEMM per ``mode`` /
    ``REPRO_DISPATCH``) — the §7.1(a) recipe for dense operators."""
    if plan is None:
        plan = dispatch.get_plan(a.shape[-1], margin_bits=4)

    def matvec(x):
        return dispatch.matmul(a, x[:, None], plan=plan, mode=mode)[:, 0]
    return cg_solve(matvec, b, **kw)
