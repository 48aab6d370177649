"""Dense DFT-as-GEMM on the emulation dispatch seam (companion paper, Part 2).

The spectral subsystem's ground rule: the *only* multiplications are matrix
products routed through ``repro.core.dispatch``, so every transform inherits the
Ozaki-II accuracy contract (and the XLA/Pallas routing, plan cache, and TPU
story) of the dispatch layer for free.

A length-n complex DFT is one real GEMM here.  With F = Fr + i·Fi the complex
product F·X splits into the "realified" block form

    [Cr]   [Fr  -Fi] [Xr]
    [Ci] = [Fi   Fr]·[Xi]

so the (2n, 2n) block operator is built once per (n, direction, dtype), cached
on device, and applied to the stacked real/imag operand with a single
``dispatch.matmul`` call — four real matmuls' worth of MACs in one fused kernel
launch, with one plan resolution for the 2n-length contraction.

Twiddle/DFT entries are generated in float64 with exact argument reduction
(j·k mod n in int64) so the operator itself contributes O(u) per entry; the
emulated GEMM then reproduces the correctly-rounded FP64 contraction.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backend, dispatch

# Transforms at or below this length run as a single dense DFT GEMM; longer
# lengths go through the Bailey four-step factorisation (repro.spectral.bailey).
DENSE_MAX = 64

# Hard cap on the dense fallback (taken only when n has no usable factorisation,
# i.e. prime n): an (2n, 2n) operator above this is a memory bug, not a path.
DENSE_HARD_MAX = 4096


def working_complex():
    return jnp.complex128 if jax.config.jax_enable_x64 else jnp.complex64


def _roots_of_unity(row: np.ndarray, col: np.ndarray, n: int,
                    inverse: bool) -> np.ndarray:
    """omega_n^(±row·col) with exact int64 argument reduction mod n."""
    jk = np.mod(np.outer(row.astype(np.int64), col.astype(np.int64)), n)
    sign = 2.0 if inverse else -2.0
    ang = sign * np.pi * jk.astype(np.float64) / float(n)
    return np.cos(ang) + 1j * np.sin(ang)


def dft_matrix(n: int, inverse: bool = False) -> np.ndarray:
    """Unnormalised complex DFT matrix F[j, k] = omega_n^(±jk), float64."""
    idx = np.arange(n)
    return _roots_of_unity(idx, idx, n, inverse)


# Realified operators above this length are built on demand instead of cached:
# the composite path only ever needs factor-sized operators (<= DENSE_MAX), but
# the prime fallback could otherwise pin an unbounded set of (2n, 2n) f64
# arrays (n = 4093 alone is ~536 MB) on device for the process lifetime.
CACHE_MAX = 4 * DENSE_MAX


def _build_realified(n: int, inverse: bool, dtype_name: str) -> jax.Array:
    f = dft_matrix(n, inverse)
    blk = np.block([[f.real, -f.imag], [f.imag, f.real]])
    # A concrete array even when first built inside a jit trace: the cache
    # outlives the trace.
    with jax.ensure_compile_time_eval():
        return jnp.asarray(blk, dtype=jnp.dtype(dtype_name))


@functools.lru_cache(maxsize=None)
def _realified_dft(n: int, inverse: bool, dtype_name: str) -> jax.Array:
    """(2n, 2n) realified block operator [[Fr, -Fi], [Fi, Fr]], device-cached."""
    return _build_realified(n, inverse, dtype_name)


def realified_dft(n: int, inverse: bool = False) -> jax.Array:
    if n > DENSE_HARD_MAX:
        raise ValueError(
            f"dense DFT fallback refused for n={n} > {DENSE_HARD_MAX} "
            "(prime length with no four-step factorisation; pad to a "
            "composite length instead)")
    dtype_name = jnp.dtype(backend.working_float()).name
    if n > CACHE_MAX:
        return _build_realified(int(n), bool(inverse), dtype_name)
    return _realified_dft(int(n), bool(inverse), dtype_name)


# Twiddle tables above this n (16n bytes each) are built on demand instead of
# cached — the same unbounded-device-pinning guard as CACHE_MAX below.
TWIDDLE_CACHE_MAX = 1 << 16


def _build_twiddle(n: int, n1: int, n2: int, inverse: bool,
                   dtype_name: str) -> Tuple[jax.Array, jax.Array]:
    w = _roots_of_unity(np.arange(n1), np.arange(n2), n, inverse)
    dt = jnp.dtype(dtype_name)
    with jax.ensure_compile_time_eval():   # as in _build_realified
        return jnp.asarray(w.real, dtype=dt), jnp.asarray(w.imag, dtype=dt)


@functools.lru_cache(maxsize=None)
def _twiddle(n: int, n1: int, n2: int, inverse: bool,
             dtype_name: str) -> Tuple[jax.Array, jax.Array]:
    """(n1, n2) four-step twiddle W[k1, j2] = omega_n^(±k1·j2), device-cached."""
    return _build_twiddle(n, n1, n2, inverse, dtype_name)


def twiddle(n: int, n1: int, n2: int,
            inverse: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Real and imaginary parts of the (n1, n2) four-step twiddle table."""
    dtype_name = jnp.dtype(backend.working_float()).name
    if n > TWIDDLE_CACHE_MAX:
        return _build_twiddle(int(n), int(n1), int(n2), bool(inverse),
                              dtype_name)
    return _twiddle(int(n), int(n1), int(n2), bool(inverse), dtype_name)


def cache_clear() -> None:
    """Drop the cached DFT operators and twiddle tables (tests / x64 toggles)."""
    _realified_dft.cache_clear()
    _twiddle.cache_clear()


def dft_dense_parts(xr: jax.Array, xi: jax.Array, inverse: bool = False,
                    mode: Optional[str] = None) -> Tuple[jax.Array, jax.Array]:
    """Unnormalised DFT along axis 0 of a stacked (n, batch) operand given as
    its real and imaginary parts.

    One realified GEMM through the dispatch layer: stack real over imag parts
    into a (2n, batch) real operand, multiply by the cached (2n, 2n) block
    operator, and split the halves into the result's parts.
    """
    n = xr.shape[0]
    op = realified_dft(n, inverse)
    out = dispatch.matmul(op, jnp.concatenate([xr, xi], axis=0), mode=mode)
    return out[:n], out[n:]
