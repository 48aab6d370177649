"""Phase-1 integer scaling and the TPU-native (hi, lo) int32 operand representation.

Paper mapping (Matsuoka 2026 §2.3 Phase 1, Appendix C):
  * ``scale_to_int`` implements Ã = ⌊D A⌉ with power-of-two diagonal D chosen per row
    (or per column for the right operand) so the largest entry uses the full payload
    width p.  Power-of-two scaling is exact in FP64, so D^{-1} Ĉ E^{-1} is error-free.
    ``global_scale_to_int`` is the same with one shift for the whole array.
  * ``split_hi_lo`` is the hardware adaptation: TPUs have no
    FP64 VMEM type and no fast int64, so the 53-bit scaled integer is carried as an
    exact pair of int32 halves, x = hi * 2^26 + lo.  8 bytes/element — identical HBM
    traffic to native FP64, which is what keeps the TME bandwidth multiplier β = 1.
  * ``residues_from_hilo`` computes balanced residues mod m using int32 arithmetic only
    ((hi mod m) * (2^26 mod m) + lo) mod m — bit-exact vs the int64 oracle (tested).
  * ``ldexp`` / ``pow2`` are the exact power-of-two scalings of Phase 1 and of the
    unscale, in ops XLA:TPU lowers (it has no 64-bit bitcast, which ``jnp.ldexp`` needs).
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.moduli import SPLIT_BITS, SPLIT_RADIX


def _trunc_div(e: jax.Array, d: int) -> jax.Array:
    """Integer e / d rounded toward zero (so e and e - d*q share a sign)."""
    return jnp.where(e < 0, -((-e) // d), e // d)


def pow2(e: jax.Array, dtype=jnp.float64) -> jax.Array:
    """Exact ``2**e`` for an int32 array e with ``2**e`` normal in ``dtype``.

    Built from float32 bit fields (a 32-bit bitcast, which every backend
    lowers) and exact power-of-two products: for float64,
    ``2**e = (2**a)**16 * 2**b`` with ``a = trunc(e/16)``, so every factor
    and partial product is a power of two between 1 and ``2**e``.  XLA:TPU
    lowers none of the 64-bit bitcasts that ``jnp.ldexp``/``jnp.frexp`` use.
    """
    e = e.astype(jnp.int32)

    def f32_pow2(k):          # k in [-126, 127]
        return jax.lax.bitcast_convert_type((k + 127) << 23, jnp.float32)

    if jnp.dtype(dtype).itemsize <= 4:
        return f32_pow2(jnp.clip(e, -126, 127)).astype(dtype)
    a = _trunc_div(e, 16)
    p = f32_pow2(a).astype(dtype)
    for _ in range(4):
        p = p * p
    return p * f32_pow2(e - 16 * a).astype(dtype)


def ldexp(x: jax.Array, e: jax.Array) -> jax.Array:
    """``x * 2**e`` (int e, broadcast against x) as three exact power-of-two
    multiplies — the ``jnp.ldexp`` contract on the values this repo scales,
    in ops that XLA:TPU lowers.

    The exponent splits into three same-signed thirds, so every partial
    product lies between x and the result: no multiply rounds unless the
    result itself leaves the normal range (where CPU arithmetic flushes to
    zero or overflows exactly as ``jnp.ldexp`` does).  A third of the widest
    shift a float64 product needs (|e| <= 2252) stays a normal float64, and
    a third of the widest a float32-range float64 needs stays a normal
    float32, which is what a TPU's float64 holds.
    """
    e = jnp.asarray(e, jnp.int32)
    e1 = _trunc_div(e, 3)
    e2 = _trunc_div(e - e1, 2)
    for part in (e1, e2, e - e1 - e2):
        x = x * pow2(part, x.dtype)
    return x


def scale_to_int(x: jax.Array, payload_bits: int, axis: int) -> Tuple[jax.Array, jax.Array]:
    """Round x (float) to integers after exact power-of-two scaling along ``axis``.

    Returns (xi, shift):
      xi    : float64 array holding exact integers with |xi| < 2**payload_bits
      shift : int32 per-row/col exponents with  xi ≈ x * 2**shift  (exact pow2 scale)

    Rows (slices along ``axis``) that are entirely zero get shift 0.
    """
    ax = axis % x.ndim
    absmax = jnp.max(jnp.abs(x), axis=ax, keepdims=True)
    # exponent e with 2**e <= absmax < 2**(e+1); for absmax == 0 use e = 0.
    e = jnp.floor(jnp.log2(jnp.where(absmax > 0, absmax, 1.0)))
    shift = (payload_bits - 1) - e.astype(jnp.int32)
    # Exact pow2 scaling (NOT exp2 — exp2 is inexact on some backends).
    scaled = ldexp(x, shift)
    # Guard against log2 boundary: ensure scaled max strictly < 2**payload_bits.
    too_big = jnp.max(jnp.abs(scaled), axis=ax, keepdims=True) >= 2.0 ** payload_bits
    shift = shift - too_big.astype(jnp.int32)
    scaled = jnp.where(too_big, scaled * 0.5, scaled)
    xi = jnp.round(scaled)
    return xi, jnp.squeeze(shift, axis=ax)


def global_scale_to_int(x: jax.Array, payload_bits: int) -> Tuple[jax.Array, jax.Array]:
    """``scale_to_int`` with one power-of-two shift for the whole array (a
    scalar int32), for operands that a kernel reads by more than one row:
    the stencil's grid and coefficients, the SpMV's x."""
    absmax = jnp.max(jnp.abs(x))
    e = jnp.floor(jnp.log2(jnp.where(absmax > 0, absmax, 1.0)))
    shift = (payload_bits - 1) - e.astype(jnp.int32)
    scaled = ldexp(x, shift)
    too_big = jnp.max(jnp.abs(scaled)) >= 2.0 ** payload_bits
    shift = shift - too_big.astype(jnp.int32)
    scaled = jnp.where(too_big, scaled * 0.5, scaled)
    return jnp.round(scaled), shift


def split_hi_lo(xi: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Exact split of an integer-valued float array into int32 (hi, lo).

    xi = hi * 2**SPLIT_BITS + lo, with |lo| <= 2**(SPLIT_BITS-1) (balanced) and
    |hi| < 2**(53-SPLIT_BITS+1).  Both halves fit int32 for |xi| < 2**53.
    """
    hi_f = jnp.round(xi / SPLIT_RADIX)
    lo_f = xi - hi_f * SPLIT_RADIX
    return hi_f.astype(jnp.int32), lo_f.astype(jnp.int32)


def merge_hi_lo(hi: jax.Array, lo: jax.Array, dtype=jnp.float64) -> jax.Array:
    """Inverse of split_hi_lo (float reconstruction of the exact integer)."""
    return hi.astype(dtype) * float(SPLIT_RADIX) + lo.astype(dtype)


def balanced_mod(v: jax.Array, m: int) -> jax.Array:
    """Balanced representative of v mod m: range [-(m//2), (m-1)//2].

    The one implementation of the residue reduction: the XLA reference and
    the fused kernels both call it.  Constants take v's integer dtype, so
    under x64 no Python int becomes an int64 scalar inside a Mosaic kernel.
    """
    m_ = np.asarray(m, v.dtype)
    u = jnp.remainder(v, m_)         # canonical [0, m)
    return jnp.where(u > np.asarray((m - 1) // 2, v.dtype), u - m_, u)


def residues_int32(hi: jax.Array, lo: jax.Array, moduli: Sequence[int]) -> List[jax.Array]:
    """Balanced residues of x = hi*2^26 + lo per modulus; int32-only arithmetic."""
    outs = []
    for m in moduli:
        v = balanced_mod(hi, m) * np.int32(SPLIT_RADIX % m) + balanced_mod(lo, m)
        outs.append(balanced_mod(v, m))
    return outs


def residues_from_hilo(hi: jax.Array, lo: jax.Array, moduli: Sequence[int]) -> jax.Array:
    """Balanced residues (stacked axis 0) of x = hi*2^26 + lo for each modulus.

    Pure int32 arithmetic (TPU-friendly).  Output dtype int8: every balanced residue of
    every modulus <= 256 fits [-128, 127].
    """
    return jnp.stack([r.astype(jnp.int8) for r in residues_int32(hi, lo, moduli)],
                     axis=0)


def residues_direct(xi: jax.Array, moduli: Sequence[int]) -> jax.Array:
    """Oracle path: balanced residues straight from the integer-valued float (via int64).

    Only usable where int64 is available (CPU tests with x64 enabled); the production
    path is residues_from_hilo.
    """
    xl = xi.astype(jnp.int64)
    outs = []
    for m in moduli:
        u = jnp.remainder(xl, m)
        u = jnp.where(u > (m - 1) // 2, u - m, u)
        outs.append(u.astype(jnp.int8))
    return jnp.stack(outs, axis=0)


def apply_unscale(c: jax.Array, shift_rows: jax.Array, shift_cols: jax.Array) -> jax.Array:
    """C = D^{-1} C̃ E^{-1}: undo the exact power-of-two row/col scaling on the output."""
    return ldexp(c, -(shift_rows[:, None] + shift_cols[None, :]))


def np_split_hi_lo(xi: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Numpy mirror of split_hi_lo for host-side test oracles."""
    hi = np.round(xi / SPLIT_RADIX)
    lo = xi - hi * SPLIT_RADIX
    return hi.astype(np.int64), lo.astype(np.int64)
