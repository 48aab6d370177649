"""Public spectral-transform API (matches the ``jnp.fft`` conventions).

All transforms compose the stacked axis-0 DFT of ``bailey.dft_stacked``:
  * ``fft`` / ``ifft``    — 1-D complex transforms along any axis,
  * ``fft2`` / ``fftn``   — multi-dimensional transforms by axis composition,
  * ``rfft`` / ``irfft``  — real-input / Hermitian-output transforms.

``fft_parts`` takes and returns the real and imaginary parts instead of
complex arrays, for backends without complex128 (XLA:TPU).

Normalisation follows numpy/jax: ``fft`` is unnormalised, ``ifft`` carries the
1/n factor, ``irfft(rfft(x), n) == x``.  ``mode`` forwards to the dispatch
layer (None inherits ``REPRO_DISPATCH`` / ``dispatch.mode_scope``), so a single
``with dispatch.mode_scope("pallas")`` flips every GEMM in a transform onto the
fused kernel route.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backend
from repro.spectral import bailey, dft


def _apply_parts(xr: jax.Array, xi: jax.Array, axis: int, inverse: bool,
                 mode: Optional[str], norm: int = 1
                 ) -> Tuple[jax.Array, jax.Array]:
    """DFT along ``axis`` (over ``norm``) of the real and imaginary parts:
    move the axis to the front, flatten the rest as batch."""
    wf = backend.working_float()
    parts = []
    for part in (xr, xi):
        part = jnp.moveaxis(jnp.asarray(part).astype(wf), axis, 0)
        parts.append(part.reshape(part.shape[0], -1))
    yr, yi = bailey.dft_stacked_parts(*parts, inverse=inverse, mode=mode)
    shp = parts[0].shape[:1] + tuple(np.delete(np.shape(xr), axis))
    yr, yi = (jnp.moveaxis(y.reshape(shp), 0, axis) for y in (yr, yi))
    if norm != 1:
        yr, yi = yr / norm, yi / norm
    return yr, yi


def _apply_along_axis(x: jax.Array, axis: int, inverse: bool,
                      mode: Optional[str], norm: int = 1) -> jax.Array:
    x = jnp.asarray(x)
    return jax.lax.complex(*_apply_parts(jnp.real(x), jnp.imag(x), axis,
                                         inverse, mode, norm))


def fft(x: jax.Array, axis: int = -1, mode: Optional[str] = None) -> jax.Array:
    """Unnormalised complex DFT along ``axis`` (the ``jnp.fft.fft`` contract)."""
    return _apply_along_axis(x, axis, inverse=False, mode=mode)


def ifft(x: jax.Array, axis: int = -1, mode: Optional[str] = None) -> jax.Array:
    """Inverse DFT along ``axis`` with the 1/n normalisation."""
    x = jnp.asarray(x)
    n = x.shape[axis]
    return _apply_along_axis(x, axis, inverse=True, mode=mode, norm=n)


def fft_parts(xr: jax.Array, xi: jax.Array, axis: int = -1,
              mode: Optional[str] = None) -> Tuple[jax.Array, jax.Array]:
    """``fft`` on a complex operand given as its real and imaginary parts,
    returning the parts of the transform.

    For backends without complex128: XLA:TPU compiles no complex128 op at
    all (even taking the real part aborts the compiler), so on a TPU the
    transforms are called this way.  ``fft`` wraps this same computation.
    """
    return _apply_parts(xr, xi, axis, inverse=False, mode=mode)


def _resolve_axes(ndim: int, axes: Optional[Sequence[int]]) -> Tuple[int, ...]:
    if axes is None:
        return tuple(range(ndim))
    return tuple(int(a) for a in axes)


def fftn(x: jax.Array, axes: Optional[Sequence[int]] = None,
         mode: Optional[str] = None) -> jax.Array:
    """N-dimensional DFT by axis composition (default: all axes)."""
    x = jnp.asarray(x)
    for a in _resolve_axes(x.ndim, axes):
        x = fft(x, axis=a, mode=mode)
    return x


def ifftn(x: jax.Array, axes: Optional[Sequence[int]] = None,
          mode: Optional[str] = None) -> jax.Array:
    x = jnp.asarray(x)
    for a in _resolve_axes(x.ndim, axes):
        x = ifft(x, axis=a, mode=mode)
    return x


def fft2(x: jax.Array, axes: Tuple[int, int] = (-2, -1),
         mode: Optional[str] = None) -> jax.Array:
    return fftn(x, axes=axes, mode=mode)


def ifft2(x: jax.Array, axes: Tuple[int, int] = (-2, -1),
          mode: Optional[str] = None) -> jax.Array:
    return ifftn(x, axes=axes, mode=mode)


def rfft(x: jax.Array, axis: int = -1, mode: Optional[str] = None) -> jax.Array:
    """Real-input DFT: the n//2 + 1 non-redundant coefficients along ``axis``.

    Computed as the full complex transform sliced to the Hermitian half — the
    realified GEMM already carries the zero imaginary block exactly, so the
    sliced result matches ``jnp.fft.rfft`` at the same accuracy as ``fft``.
    """
    x = jnp.asarray(x)
    if jnp.iscomplexobj(x):
        raise ValueError("rfft requires real input (matching jnp.fft.rfft); "
                         "use fft for complex operands")
    n = x.shape[axis]
    full = fft(x, axis=axis, mode=mode)
    idx = [slice(None)] * full.ndim
    idx[axis if axis >= 0 else full.ndim + axis] = slice(0, n // 2 + 1)
    return full[tuple(idx)]


def irfft(x: jax.Array, n: Optional[int] = None, axis: int = -1,
          mode: Optional[str] = None) -> jax.Array:
    """Inverse of ``rfft``: Hermitian-extend the half spectrum, inverse-DFT,
    return the real part (length ``n``, default 2·(m − 1) for m coefficients)."""
    x = jnp.asarray(x).astype(dft.working_complex())
    ax = axis if axis >= 0 else x.ndim + axis
    m = x.shape[ax]
    if n is None:
        n = 2 * (m - 1)
    # numpy semantics: the half spectrum is truncated or zero-padded to the
    # n//2 + 1 coefficients the length-n transform actually uses.
    need = n // 2 + 1
    if m > need:
        head = [slice(None)] * x.ndim
        head[ax] = slice(0, need)
        x = x[tuple(head)]
    elif m < need:
        widths = [(0, 0)] * x.ndim
        widths[ax] = (0, need - m)
        x = jnp.pad(x, widths)
    m = need
    k_mirror = n - jnp.arange(m, n)          # n-k in [1, m-1]: always in range
    head = [slice(None)] * x.ndim
    head[ax] = slice(0, m)
    tail = jnp.conj(jnp.take(x, k_mirror, axis=ax))
    full = jnp.concatenate([x[tuple(head)], tail], axis=ax)
    return jnp.real(ifft(full, axis=ax, mode=mode))


def dft_error_bound(n: int) -> float:
    """Crude forward relative-error model for the emulated transform: the
    dispatch GEMM is correctly rounded, so the bound is the twiddle/stage term
    ~ u·(number of four-step levels + 1)·sqrt(n)."""
    u = 2.0 ** -53 if jax.config.jax_enable_x64 else 2.0 ** -24
    levels = 1
    nn = n
    while nn > dft.DENSE_MAX and bailey.choose_factors(nn) is not None:
        nn = bailey.choose_factors(nn)[1]
        levels += 1
    return u * levels * (float(n) ** 0.5)
