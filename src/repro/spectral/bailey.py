"""Bailey four-step FFT factorisation over the dispatch seam (Part 2, §3).

For composite n = n1·n2 the DFT factors into two passes of batched *small*
dense DFT GEMMs around a diagonal twiddle scaling and a transpose:

    X[k2·n1 + k1] = Σ_j2 omega_n2^(j2·k2) · omega_n^(j2·k1)
                        · Σ_j1 omega_n1^(j1·k1) x[j1·n2 + j2]

  1. view x as an (n1, n2) matrix (row-major),
  2. DFT each column — one (n1, n1) GEMM over n2·batch stacked columns,
  3. scale by the twiddle table W[k1, j2] = omega_n^(±k1·j2) (elementwise,
     working precision — the one non-GEMM arithmetic stage),
  4. transpose and DFT each row — one (n2, n2) GEMM over n1·batch columns,
  5. read the output transposed.

Both GEMM passes recurse through ``dft_stacked``, so large lengths factor all
the way down to DENSE_MAX-sized dense operators and *every* multiplication in
the subsystem flows through ``repro.core.dispatch``.  Prime lengths fall back
to the dense operator (bounded by ``dft.DENSE_HARD_MAX``).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import backend
from repro.spectral import dft


def choose_factors(n: int) -> Optional[Tuple[int, int]]:
    """Balanced factorisation n = n1·n2 with n1 <= n2, or None if n is prime.

    n1 is the largest divisor at or below sqrt(n), which keeps both GEMM passes
    near the square (minimum total MACs ~ 8·n·(n1 + n2)·batch).
    """
    for d in range(int(math.isqrt(n)), 1, -1):
        if n % d == 0:
            return d, n // d
    return None


def dft_stacked(x: jax.Array, inverse: bool = False,
                mode: Optional[str] = None) -> jax.Array:
    """Unnormalised DFT along axis 0 of a complex (n, batch) stack.

    Dense single-GEMM below ``dft.DENSE_MAX`` (and for prime n); Bailey
    four-step with recursive factor transforms above it.
    """
    x = jnp.asarray(x)
    yr, yi = dft_stacked_parts(jnp.real(x).astype(backend.working_float()),
                               jnp.imag(x).astype(backend.working_float()),
                               inverse=inverse, mode=mode)
    return jax.lax.complex(yr, yi)


def dft_stacked_parts(xr: jax.Array, xi: jax.Array, inverse: bool = False,
                      mode: Optional[str] = None) -> Tuple[jax.Array, jax.Array]:
    """``dft_stacked`` on the real and imaginary parts, each (n, batch) real.

    The recursion carries the two parts as real arrays: XLA:TPU emulates
    complex128 on float64 pairs and refuses some of its reshapes, so the
    transforms keep complex numbers at their API boundary only.
    """
    n, batch = xr.shape
    if n <= 1:
        return xr, xi
    factors = choose_factors(n) if n > dft.DENSE_MAX else None
    if factors is None:
        return dft.dft_dense_parts(xr, xi, inverse=inverse, mode=mode)
    n1, n2 = factors

    # Step 1+2: column DFTs of the (n1, n2) view, batched as one GEMM.
    br, bi = dft_stacked_parts(xr.reshape(n1, n2 * batch),
                               xi.reshape(n1, n2 * batch),
                               inverse=inverse, mode=mode)
    # Step 3: twiddle scaling (elementwise complex, working precision).
    wr, wi = (w[:, :, None] for w in dft.twiddle(n, n1, n2, inverse))
    br, bi = br.reshape(n1, n2, batch), bi.reshape(n1, n2, batch)
    br, bi = br * wr - bi * wi, br * wi + bi * wr
    # Step 4: transpose, then row DFTs as the second GEMM pass.
    dr, di = dft_stacked_parts(jnp.moveaxis(br, 1, 0).reshape(n2, n1 * batch),
                               jnp.moveaxis(bi, 1, 0).reshape(n2, n1 * batch),
                               inverse=inverse, mode=mode)
    # Step 5: the output is read transposed: X[k2·n1 + k1] = D[k2, k1].
    return dr.reshape(n, batch), di.reshape(n, batch)
