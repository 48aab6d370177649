"""Plain reference of the ``cg_poisson7`` configuration, and its FP64 work.

The reference imports nothing of the program and takes nothing it made: it
reads the right-hand sides the benchmark drew and the iterates the program
returned.  It applies the operator as the 7-point stencil on the n^3 grid,
not through the Blocked-ELL arrays the program is given, and runs textbook
CG from x0 = 0 for the same number of iterations:

    r = b, p = r, rs = r.r;  each iteration:
    ap = A p, alpha = rs / p.ap, x += alpha p, r -= alpha ap,
    rs' = r.r, p = r + (rs' / rs) p

The number compared is the relative distance of the program's iterate from
the reference's, ||x - x_ref||_2 / ||x_ref||_2, over the checked sets.
``apply`` and ``cg`` take the array module, so the control (``control``)
is the same code in float32 on the device.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np


def work(rows: int, bw: int) -> Tuple[float, float]:
    """FP64 work of one CG iteration: (flops, bytes).  The SpMV's 2 * rows *
    bw operations, 2 dots and 3 axpys of 2 * rows each; the ELL values
    (8 B) and int32 column indices (4 B) read once, and x, r and p each
    read once and written once (8 B a value).  Each array counts once, so
    no implementation that streams the operator from memory moves less."""
    flops = 2.0 * rows * bw + 2 * 2.0 * rows + 3 * 2.0 * rows
    bytes_ = rows * bw * (8.0 + 4.0) + 6 * 8.0 * rows
    return flops, bytes_


def apply(u, n: int, xp=np):
    """-Laplacian_h u on the n^3 grid with a zero Dirichlet boundary:
    6 u minus the six neighbours (``u`` flat, C order)."""
    g = u.reshape(n, n, n)
    q = xp.pad(g, 1)
    nb = (q[2:, 1:-1, 1:-1] + q[:-2, 1:-1, 1:-1] + q[1:-1, 2:, 1:-1]
          + q[1:-1, :-2, 1:-1] + q[1:-1, 1:-1, 2:] + q[1:-1, 1:-1, :-2])
    return (6 * g - nb).reshape(-1)


def cg(b, n: int, iters: int, xp=np):
    """``iters`` iterations of textbook CG from x0 = 0, in b's dtype."""
    x = xp.zeros_like(b)
    r = b
    p = r
    rs = xp.sum(r * r)
    for _ in range(iters):
        ap = apply(p, n, xp)
        alpha = rs / xp.sum(p * ap)
        x = x + alpha * p
        r = r - alpha * ap
        rs_new = xp.sum(r * r)
        p = r + (rs_new / rs) * p
        rs = rs_new
    return x


def rel_err(x: np.ndarray, want: np.ndarray) -> float:
    """||x - want||_2 / ||want||_2; NaN in x gives NaN."""
    return float(np.linalg.norm(x - want) / np.linalg.norm(want))


def control(b, n: int, iters: int):
    """The control: the reference in the program's place, in float32 (the
    precision below float64), on the device.  Takes a device array, returns
    a host float64 array."""
    import jax.numpy as jnp
    return np.asarray(cg(b.astype(jnp.float32), n, iters, jnp), np.float64)
