"""NAS Parallel Benchmarks CG (NPB 3.4, ``CG/cg.f``) on the emulated stack.

NPB CG estimates the smallest eigenvalue of a large sparse symmetric matrix
by inverse power iteration.  Each outer step solves A z = x with 25
unpreconditioned CG iterations from z = 0 (``conj_grad``), then

    rnorm = ||x - A z||,  zeta = SHIFT + 1 / (x . z),  x = z / ||z||.

The matrix is NPB's (``makea``): the sum of NA outer products of random
sparse vectors, the i-th scaled by RCOND^(i/NA), with RCOND - SHIFT added on
the diagonal.  Its random numbers are NPB's ``randlc``, x <- 5^13 x mod 2^46,
computed exactly in Python integers.  ``run`` is NPB's main program and
checks zeta against the published value at NPB's relative tolerance 1e-10
(Bailey et al., NASA RNR-94-007; NPB 3.4).

The solve runs through ``cg.cg_solve_bell`` (the dispatch-routed Blocked-ELL
SpMV and compensated dots), and the outer step's dots and norms through the
same compensated reductions, where NPB's are plain.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compensated, dispatch
from repro.hpc import cg, spmv_formats
from repro.obs import spans

RCOND = 0.1
VERIFY_TOL = 1e-10        # NPB's relative tolerance on zeta
CG_ITERS = 25             # NPB's cgitmax

_AMULT = 5 ** 13          # randlc's multiplier, 1220703125
_MOD = 1 << 46
_TRAN = 314159265         # the seed of NPB CG's stream


@dataclasses.dataclass(frozen=True)
class NPBClass:
    name: str
    na: int               # rows of A
    nonzer: int           # nonzeros of each random vector before vecset
    niter: int            # outer iterations
    shift: float
    zeta_verify: float    # published zeta after niter iterations
    rcond: float = RCOND


CLASSES: Dict[str, NPBClass] = {c.name: c for c in (
    NPBClass("S", 1400, 7, 15, 10.0, 8.5971775078648),
    NPBClass("W", 7000, 8, 15, 12.0, 10.362595087124),
    NPBClass("A", 14000, 11, 15, 20.0, 17.130235054029),
    NPBClass("B", 75000, 13, 75, 60.0, 22.712745482631),
    NPBClass("C", 150000, 15, 75, 110.0, 28.973605592845),
)}


def _vectors(c: NPBClass) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """NPB's ``sprnvc`` and ``vecset`` for each outer vector i: (rows (na, L),
    values (na, L), lengths (na,)), L = nonzer + 1, 0-based rows, slots past a
    vector's length unused.  Vector i draws nonzer distinct rows (a value,
    then a row int(nn1 * draw), drawn in pairs; a row past na or repeated is
    drawn again), then has 0.5 at row i, in place or appended."""
    n, nz = c.na, c.nonzer
    nn1 = 1
    while nn1 < n:
        nn1 *= 2
    rows = np.zeros((n, nz + 1), np.int64)
    vals = np.zeros((n, nz + 1))
    lengths = np.zeros(n, np.int64)
    x = _AMULT * _TRAN % _MOD     # NPB draws once before makea
    for i in range(n):
        iv: List[int] = []
        v: List[float] = []
        while len(iv) < nz:
            x = _AMULT * x % _MOD
            elt = x
            x = _AMULT * x % _MOD
            r = (nn1 * x) >> 46
            if r < n and r not in iv:
                iv.append(r)
                v.append(elt / _MOD)
        if i in iv:
            v[iv.index(i)] = 0.5
        else:
            iv.append(i)
            v.append(0.5)
        lengths[i] = len(iv)
        rows[i, :len(iv)] = iv
        vals[i, :len(iv)] = v
    return rows, vals, lengths


def makea(cls) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """NPB's matrix of class ``cls`` (a name or an ``NPBClass``) as CSR:
    (rowptr (na + 1,) int64, col int32, val float64), columns ascending in
    each row.  NPB's ``sparse``: outer vector i adds v_r * (size_i * v_c) at
    each (r, c) of its rows, plus RCOND - SHIFT at (i, i), with size_i =
    RCOND^(1/na) multiplied i times; duplicates are summed in NPB's order
    (i, then r, then c)."""
    c = CLASSES[cls] if isinstance(cls, str) else cls
    n = c.na
    rows, vals, lengths = _vectors(c)
    ratio = c.rcond ** (1.0 / n)
    size = np.empty(n)
    s = 1.0
    for i in range(n):
        size[i] = s
        s *= ratio
    length = rows.shape[1]
    used = np.arange(length) < lengths[:, None]                 # (n, L)
    scale = size[:, None] * vals                                # row's scale
    va = vals[:, None, :] * scale[:, :, None]                   # (n, r, c)
    r = np.broadcast_to(rows[:, :, None], va.shape)
    cc = np.broadcast_to(rows[:, None, :], va.shape)
    diag = (r == cc) & (r == np.arange(n)[:, None, None])
    va = np.where(diag, va + c.rcond - c.shift, va)
    keep = (used[:, :, None] & used[:, None, :]).reshape(-1)
    key = (r.reshape(-1) * n + cc.reshape(-1))[keep]
    va = va.reshape(-1)[keep]
    order = np.argsort(key, kind="stable")                      # NPB's order
    key, va = key[order], va[order]
    start = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    seg = np.diff(np.r_[start, key.size])
    val = va[start].copy()
    for k in range(1, int(seg.max())):                          # left to right
        more = seg > k
        val[more] += va[start[more] + k]
    key = key[start]
    rowptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(key // n, minlength=n), out=rowptr[1:])
    return rowptr, (key % n).astype(np.int32), val


def power_step(a_val: jax.Array, a_col: jax.Array, x: jax.Array, shift: float,
               cg_iters: int = CG_ITERS):
    """One outer step of NPB's inverse power iteration: (x_next, zeta, rnorm),
    zeta and rnorm as device scalars.  ``conj_grad`` is ``cg_solve_bell``
    from z = 0 with right-hand side x; rnorm = ||x - A z|| takes one more
    SpMV, which reads x by shifts when the operator is banded, as the solve's
    do.  The step is the host span ``repro.npb.outer``."""
    with spans.span("npb.outer"):
        z = cg.cg_solve_bell(a_val, a_col, x, tol=0.0, maxiter=cg_iters,
                             record_plain=False).x
        az = dispatch.spmv(a_val, a_col, z,
                           offsets=spmv_formats.band_offsets(a_val, a_col))
        rnorm = compensated.compensated_norm(x - az)
        zeta = shift + 1.0 / compensated.compensated_dot(x, z)
        x_next = (1.0 / compensated.compensated_norm(z)) * z
    return x_next, zeta, rnorm


@dataclasses.dataclass
class Verification:
    cls: str
    niter: int
    zeta: float
    rnorms: List[float]
    step_s: List[float]     # host seconds of each counted step, to its reads
    rel_err: float          # |zeta - zeta_verify| / zeta_verify
    verified: bool          # niter is the class's and rel_err <= VERIFY_TOL


def run(cls, niter: Optional[int] = None) -> Verification:
    """NPB's main program: the matrix in Blocked-ELL on the default device (bw
    the longest row), one untimed outer step from x = 1, x reset to 1, then
    ``niter`` steps (the class's by default); zeta of the last step checked
    against the published value."""
    c = CLASSES[cls] if isinstance(cls, str) else cls
    niter = c.niter if niter is None else niter
    a_val, a_col = (jnp.asarray(t) for t in
                    spmv_formats.csr_to_blocked_ell(*makea(c)))
    ones = jnp.ones(c.na, a_val.dtype)
    power_step(a_val, a_col, ones, c.shift)
    x = ones
    zeta, rnorms, step_s = float("nan"), [], []
    for _ in range(niter):
        t0 = time.perf_counter()
        x, zeta_d, rnorm_d = power_step(a_val, a_col, x, c.shift)
        zeta = float(zeta_d)
        rnorms.append(float(rnorm_d))
        step_s.append(time.perf_counter() - t0)
    err = abs(zeta - c.zeta_verify) / c.zeta_verify
    return Verification(c.name, niter, zeta, rnorms, step_s, err,
                        niter == c.niter and err <= VERIFY_TOL)
