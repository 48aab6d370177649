"""Sparse-matrix format tooling for the Blocked-ELL SpMV kernel (paper §5.4).

``csr_to_blocked_ell`` converts a CSR matrix, and ``to_blocked_ell`` a small
dense one, to the (values, columns) padded layout; ``padding_ratio`` is Appendix
D's ρ_pad — the lower bound on the TME β for the SpMV kernel;
``band_offsets`` finds the diagonal each slot holds when the operator is
banded, so the kernel can read x by static shifts instead of a gather.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def to_blocked_ell(dense: np.ndarray, bw: int) -> Tuple[np.ndarray, np.ndarray]:
    """Dense (M, N) -> (values (M, bw), columns (M, bw)), for small operators:
    the nonzeros of each row in column order, as CSR, through
    ``csr_to_blocked_ell``."""
    rows, cols = np.nonzero(dense)
    rowptr = np.r_[0, np.cumsum(np.bincount(rows, minlength=dense.shape[0]))]
    return csr_to_blocked_ell(rowptr, cols.astype(np.int32), dense[rows, cols],
                              bw)


def csr_to_blocked_ell(rowptr: np.ndarray, col: np.ndarray, val: np.ndarray,
                       bw: Optional[int] = None) -> Tuple[np.ndarray, np.ndarray]:
    """CSR (``rowptr`` (M + 1,), ``col``, ``val``) -> (values (M, bw), columns
    (M, bw)), vectorised.  ``bw`` defaults to the longest row; raises if a
    row has more than ``bw`` entries.  Slot k of a row holds its k-th stored
    entry.  Padded slots have value 0 and point at the row itself (an x the
    row of a square operator reads anyway), or, in a row whose number is past
    the largest stored column, at that column: a column of the matrix
    whatever its shape."""
    rowptr = np.asarray(rowptr, np.int64)
    col = np.asarray(col)
    val = np.asarray(val)
    m = rowptr.shape[0] - 1
    lengths = np.diff(rowptr)
    longest = int(lengths.max(initial=0))
    if bw is None:
        bw = longest
    if longest > bw:
        row = int(np.argmax(lengths))
        raise ValueError(f"row {row} has {longest} > bw={bw} entries")
    rows = np.repeat(np.arange(m), lengths)
    slots = np.arange(rowptr[-1]) - rowptr[rows]
    pad = np.minimum(np.arange(m), int(col.max(initial=0))).astype(np.int32)
    out_val = np.zeros((m, bw), val.dtype)
    out_col = np.repeat(pad[:, None], bw, axis=1)
    out_val[rows, slots] = val
    out_col[rows, slots] = col
    return out_val, out_col


def laplacian_1d(n: int) -> np.ndarray:
    return (np.diag(2.0 * np.ones(n)) - np.diag(np.ones(n - 1), 1)
            - np.diag(np.ones(n - 1), -1))


def laplacian_2d(nx: int, ny: int) -> np.ndarray:
    """5-point 2-D Laplacian, (nx*ny, nx*ny) SPD."""
    n = nx * ny
    a = np.zeros((n, n))
    for i in range(nx):
        for j in range(ny):
            k = i * ny + j
            a[k, k] = 4.0
            for di, dj in ((1, 0), (-1, 0), (0, 1), (0, -1)):
                ii, jj = i + di, j + dj
                if 0 <= ii < nx and 0 <= jj < ny:
                    a[k, ii * ny + jj] = -1.0
    return a


def laplacian_3d_bell(n: int) -> Tuple[np.ndarray, np.ndarray]:
    """7-point -Δ_h on an n^3 grid (zero Dirichlet), Blocked-ELL with bw = 7:
    slot 0 the diagonal (6), slots 1-6 the neighbours (-1) along axes 0, 1, 2
    at -1 then +1; slots past the boundary point at the row itself with
    value 0.  Banded: ``band_offsets`` gives (0, -n², n², -n, n, -1, 1)."""
    rows = np.arange(n ** 3)
    val = np.zeros((n ** 3, 7))
    col = np.repeat(rows[:, None], 7, axis=1).astype(np.int32)
    val[:, 0] = 6.0
    coord = (rows // (n * n), rows // n % n, rows % n)
    slot = 1
    for ax, stride in enumerate((n * n, n, 1)):
        for d in (-1, 1):
            inside = (coord[ax] + d >= 0) & (coord[ax] + d < n)
            col[inside, slot] = rows[inside] + d * stride
            val[inside, slot] = -1.0
            slot += 1
    return val, col


def padding_ratio(val: np.ndarray) -> float:
    """Appendix D ρ_pad: stored slots / actual nonzeros (>= 1)."""
    stored = val.size
    actual = int(np.count_nonzero(val))
    return stored / max(actual, 1)


@jax.jit
def _slot_offset_range(a_val: jax.Array, a_col: jax.Array) -> jax.Array:
    """(2, bw) int32: per slot, the least and the greatest ``col - row`` over
    the rows whose value is nonzero (int32 max and min where there is none)."""
    d = a_col.astype(jnp.int32) - jnp.arange(a_col.shape[0], dtype=jnp.int32)[:, None]
    nz = a_val != 0
    big = jnp.iinfo(jnp.int32)
    return jnp.stack([jnp.min(jnp.where(nz, d, big.max), axis=0),
                      jnp.max(jnp.where(nz, d, big.min), axis=0)])


def band_offsets(a_val, a_col) -> Optional[Tuple[int, ...]]:
    """The diagonal offset of each Blocked-ELL slot, when every slot holds one.

    Slot k has offset ``off_k`` when every row r with ``a_val[r, k] != 0``
    reads column ``r + off_k``; a row's zero-valued slots may point anywhere,
    and a slot with no nonzero takes offset 0.  Returns the offsets when every
    slot has one, else None: a general sparse matrix, or
    ``to_blocked_ell``'s left-packed rows.  One device reduction and one host
    read of 2·bw integers, so call it once per operator, not per product; on
    tracers (the structure is not known) it returns None.
    """
    if isinstance(a_val, jax.core.Tracer) or isinstance(a_col, jax.core.Tracer):
        return None
    lo, hi = np.asarray(_slot_offset_range(jnp.asarray(a_val), jnp.asarray(a_col)))
    if np.any(lo < hi):
        return None
    return tuple(int(o) if o == h else 0 for o, h in zip(lo, hi))
