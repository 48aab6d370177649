"""Plain reference of the ``cg_npb`` configuration, and its FP64 work.

The reference imports nothing of the program.  It reads the CSR arrays of
NPB's matrix that the cell holds (row pointers, columns, values), never the
Blocked-ELL arrays the program is given, and applies A row by row in
float64 NumPy.  Those arrays come from the program's generator, so first
``check_matrix`` holds them to the SHA-256 that the configuration gives for
the class: the digest of the build whose zeta passed NPB's own verification
(``cg_npb.json``, ``csr_sha256``).  A generator that drew, scaled or summed
differently fails there, whatever the two sides would then agree on.  From x = 1 it replays NPB CG's outer steps (``cg.f``):

    conj_grad: z = 0, r = x, p = r, rho = r.r;  25 times:
        q = A p, alpha = rho / p.q, z += alpha p, r -= alpha q,
        rho' = r.r, p = r + (rho' / rho) p
    zeta = shift + 1 / x.z, x = z / ||z||

(NPB's rnorm = ||x - A z|| is not compared: it is a residual of about
1e-13, where rounding is the answer.)

The numbers compared, over the checked steps: ``z_rel_err``, the relative
2-norm distance of the program's next x (z normalised) from the
reference's, and ``zeta_rel_err``, |zeta - zeta_ref| / |zeta_ref|.
``control`` runs the same code in float32.
"""

from __future__ import annotations

import hashlib
from typing import Dict, Iterable, Tuple

import numpy as np


def work(rows: int, nnz: int) -> Tuple[float, float]:
    """FP64 work of one CG iteration: (flops, bytes).  The SpMV's 2 * nnz
    operations over the true nonzeros (not the padded slots), 2 dots and 3
    axpys of 2 * rows each; the values (8 B) and int32 columns (4 B) of the
    nonzeros read once, and x, r and p each read once and written once (8 B
    a value).  So no implementation that streams the operator moves less."""
    flops = 2.0 * nnz + 2 * 2.0 * rows + 3 * 2.0 * rows
    bytes_ = nnz * (8.0 + 4.0) + 6 * 8.0 * rows
    return flops, bytes_


def digest(rowptr: np.ndarray, col: np.ndarray, val: np.ndarray) -> str:
    """SHA-256 of the CSR arrays as little-endian int64, int32 and float64."""
    h = hashlib.sha256()
    for a, t in ((rowptr, "<i8"), (col, "<i4"), (val, "<f8")):
        h.update(np.ascontiguousarray(a, t).tobytes())
    return h.hexdigest()


def check_matrix(rowptr, col, val, want: str) -> None:
    """Raise unless the CSR arrays are the verified build of NPB's matrix."""
    got = digest(rowptr, col, val)
    if got != want:
        raise ValueError(f"NPB matrix: SHA-256 {got}, the verified build's is "
                         f"{want}")


def apply(rowptr: np.ndarray, col: np.ndarray, val: np.ndarray,
          x: np.ndarray) -> np.ndarray:
    """A x from CSR, in val's and x's precision: each row's products summed."""
    prod = val * x[col]
    out = np.zeros(rowptr.shape[0] - 1, prod.dtype)
    full = np.diff(rowptr) > 0
    out[full] = np.add.reduceat(prod, rowptr[:-1][full])
    return out


def conj_grad(rowptr, col, val, x: np.ndarray, iters: int) -> np.ndarray:
    """NPB's ``conj_grad``: ``iters`` CG iterations on A z = x from z = 0."""
    z = np.zeros_like(x)
    r = x
    p = r
    rho = r @ r
    for _ in range(iters):
        q = apply(rowptr, col, val, p)
        alpha = rho / (p @ q)
        z = z + alpha * p
        r = r - alpha * q
        rho_new = r @ r
        p = r + (rho_new / rho) * p
        rho = rho_new
    return z


def replay(rowptr, col, val, shift: float, iters: int, steps: Iterable[int],
           dtype=np.float64) -> Dict[int, Tuple[np.ndarray, float]]:
    """NPB's outer steps from x = 1, in ``dtype``: for each step k (0 the
    first) of ``steps``, the next x and zeta."""
    steps = set(steps)
    val = val.astype(dtype)
    x = np.ones(rowptr.shape[0] - 1, dtype)
    out = {}
    for k in range(max(steps, default=-1) + 1):
        z = conj_grad(rowptr, col, val, x, iters)
        zeta = dtype(shift) + dtype(1) / (x @ z)
        x = (dtype(1) / np.sqrt(z @ z)) * z
        if k in steps:
            out[k] = (x.astype(np.float64), float(zeta))
    return out


def rel_err(x: np.ndarray, want: np.ndarray) -> float:
    """||x - want||_2 / ||want||_2; NaN in x gives NaN."""
    return float(np.linalg.norm(x - want) / np.linalg.norm(want))


def control(rowptr, col, val, shift: float, iters: int, steps: Iterable[int]):
    """The control: the reference in the program's place, in float32 (the
    precision below float64), on the host.  Its SpMV sums each row in float32
    as the float64 reference does in float64; the device has no fast general
    gather, which is what the cell measures."""
    return replay(rowptr, col, val, shift, iters, steps, np.float32)
