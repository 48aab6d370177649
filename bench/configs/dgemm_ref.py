"""Plain reference of the ``dgemm`` configuration, and its FP64 work.

The reference imports nothing of the program and takes nothing it made: it
reads the operands the benchmark drew and the answers the program returned,
both as host float64 arrays.  The product is accumulated in long double
(64-bit significand; the oracle's own error, about log2(k) * 2^-64 of
|A||B|, is some thousand times below float64's rounding).

The number compared is the componentwise error relative to |A||B|, the
measure of the CPU tests' GEMM bound (``tests/test_kernels``):

    max over checked (i, j) of |C_ij - (A B)_ij| / (|A| |B|)_ij
"""

from __future__ import annotations

from typing import Tuple

import numpy as np


def work(m: int, k: int, n: int) -> Tuple[float, float]:
    """FP64 work of one C = A B: (flops, bytes).  2mnk operations; 8 bytes
    for every float64 element of A, B and C, each moved once."""
    return 2.0 * m * n * k, 8.0 * (m * k + k * n + m * n)


def bands(rng: np.random.Generator, size: int, band: int) -> np.ndarray:
    """One index drawn in every band of ``band`` consecutive indices, so a
    check of rows x columns touches every band x band tile of C."""
    if size <= band:
        return np.arange(size)
    return np.array([lo + int(rng.integers(0, min(band, size - lo)))
                     for lo in range(0, size, band)])


def max_err(c: np.ndarray, a_rows: np.ndarray, b_cols: np.ndarray) -> float:
    """max |c - a_rows @ b_cols| / (|a_rows| @ |b_cols|) over the block; NaN
    anywhere in ``c`` gives NaN."""
    ld = np.longdouble
    want = a_rows.astype(ld) @ b_cols.astype(ld)
    scale = np.abs(a_rows) @ np.abs(b_cols)
    err = np.abs(c.astype(ld) - want) / scale
    return float(np.max(err))


def control(a, b):
    """The control: the reference in the program's place, computed in the
    precision below float64 (float32, full float32 passes on a TPU).  Takes
    and returns device arrays."""
    import jax
    import jax.numpy as jnp
    c = jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    return c.astype(jnp.float64)
