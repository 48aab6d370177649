"""Spectral-transform subsystem: Ozaki-Bailey FFT on the FP8 dispatch seam.

Every multiplication in this package is a matrix product routed through
``repro.core.dispatch`` (dense DFT GEMMs below ``dft.DENSE_MAX``, Bailey
four-step factorisation above it), so the transforms inherit the emulated-FP64
accuracy contract and the XLA/Pallas routing of the dispatch layer.
"""

from repro.spectral.bailey import choose_factors, dft_stacked
from repro.spectral.dft import DENSE_MAX, dft_matrix, realified_dft, twiddle
from repro.spectral.fft import (dft_error_bound, fft, fft2, fft_parts, fftn,
                                ifft, ifft2, ifftn, irfft, rfft)

__all__ = [
    "DENSE_MAX", "choose_factors", "dft_error_bound", "dft_matrix",
    "dft_stacked", "fft", "fft2", "fft_parts", "fftn", "ifft", "ifft2",
    "ifftn", "irfft",
    "realified_dft", "rfft", "twiddle",
]
