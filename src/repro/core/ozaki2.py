"""Ozaki Scheme II — CRT/residue FP64 matrix-multiplication emulation (paper §2.3–§2.4).

Pipeline (paper Phases 1–3):
  1. ``scale_to_int``  : Ã = ⌊D A⌉, B̃ = ⌊B E⌉ with exact power-of-two diagonal scaling.
  2. ``modular_matmul``: C⁽ⁱ⁾ = (Ã mod mᵢ)(B̃ mod mᵢ) mod mᵢ for r pairwise-coprime
     moduli.  INT8 substrate: int8 dot with int32 accumulation (the TPU MXU int8 path,
     standing in for the paper's INT8 tensor cores).  FP8 substrate: the Uchino-style
     quantisation trick of §2.4 — each balanced residue is split into two exact 4-bit
     E4M3 halves and multiplied with a Karatsuba 3-MMA schedule, FP32 accumulation;
     exactness is guaranteed by construction (all partial sums are integers < 2²⁴).
  3. ``garner_reconstruct``: balanced-digit Garner mixed-radix reconstruction (paper
     eq. (7), Appendix A), followed by the exact power-of-two unscale D^{-1}·E^{-1}.

Everything is pure JAX (jit/vmap/grad-safe, no Python-level data dependence), with the
moduli plan as a static argument.  The Pallas kernels in ``repro.kernels`` implement the
*fused* version of the same arithmetic (β = 1 discipline); this module is the
mathematical reference and the XLA fallback path used by the precision policy.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backend, fp8_quant
from repro.core import moduli as moduli_lib
from repro.core import splitting

Substrate = str  # "int8" | "fp8"

# int32 accumulation of balanced int8 residue products (|v| <= 128) is exact for
# k <= 2**31 / 128**2; chunk the contraction above this.
_INT8_K_CHUNK = 1 << 17
# fp8 path: per-plane integer products <= 16**2; fp32 accumulation exact below 2**24.
_FP8_K_CHUNK = 1 << 16


@dataclasses.dataclass(frozen=True)
class Plan:
    """Static Ozaki-II configuration (hashable; used as a jit static argument)."""

    moduli: Tuple[int, ...]
    payload_bits: int            # p: |Ã| < 2**p
    substrate: Substrate = "int8"

    @property
    def r(self) -> int:
        return len(self.moduli)

    @functools.cached_property
    def garner(self) -> moduli_lib.GarnerConstants:
        # cached_property writes through the instance __dict__, which frozen
        # dataclasses permit; hash/eq still come from the declared fields.
        return moduli_lib.garner_constants(self.moduli)

    @property
    def alpha(self) -> int:
        """TME compute multiplier α: low-precision MMAs per FP64 op (paper Def. 1).

        INT8: r modular GEMMs.  FP8: 3r (Karatsuba hi/lo planes, §2.4's (3r+1) without
        the +1 correction GEMM, which our exact-by-construction split does not need).
        """
        return self.r if self.substrate == "int8" else 3 * self.r


def make_plan(k: int, payload_bits: int = 53, r: Optional[int] = None,
              substrate: Substrate = "int8", margin_bits: int = 2) -> Plan:
    """Build a Plan for contractions of length k.

    If ``r`` is given, the payload is clipped to what those r moduli support at this k
    (paper §2.4 sensitivity analysis); otherwise r is the minimum for ``payload_bits``.
    """
    if r is None:
        r = moduli_lib.required_r(k, payload_bits, margin_bits)
    else:
        payload_bits = min(payload_bits,
                           moduli_lib.max_payload_bits(r, k, margin_bits))
    return Plan(moduli=moduli_lib.DEFAULT_MODULI[:r], payload_bits=payload_bits,
                substrate=substrate)


# ---------------------------------------------------------------------------
# Phase 1+: decomposition to residues
# ---------------------------------------------------------------------------

def decompose(x: jax.Array, plan: Plan, scale_axis: int,
              via_hilo: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Residue decomposition: returns (residues int8 (r, *x.shape), shift int32).

    ``scale_axis`` is the contraction axis (the axis along which the max-magnitude
    scaling of Appendix C is taken): rows of A scale over axis=-1, columns of B over
    axis=0.  ``via_hilo`` selects the TPU-native int32 (hi,lo) residue path (default)
    versus the int64 oracle (CPU tests only).
    """
    xi, shift = splitting.scale_to_int(x, plan.payload_bits, axis=scale_axis)
    if via_hilo:
        hi, lo = splitting.split_hi_lo(xi)
        res = splitting.residues_from_hilo(hi, lo, plan.moduli)
    else:
        res = splitting.residues_direct(xi, plan.moduli)
    return res, shift


# ---------------------------------------------------------------------------
# Phase 2: modular matmuls
# ---------------------------------------------------------------------------

def _dot_int8(a8: jax.Array, b8: jax.Array) -> jax.Array:
    """int8 x int8 -> int32 contraction over the last/first axes (MXU int8 path)."""
    return jax.lax.dot_general(
        a8, b8, (((a8.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


def _chunked_modular_dot_int8(ares: jax.Array, bres: jax.Array, m: int) -> jax.Array:
    """(Ã mod m)(B̃ mod m) mod m with int32-safe chunking over the contraction."""
    k = ares.shape[-1]
    if k <= _INT8_K_CHUNK:
        return splitting.balanced_mod(_dot_int8(ares, bres), m)
    acc = None
    for s in range(0, k, _INT8_K_CHUNK):
        e = min(s + _INT8_K_CHUNK, k)
        part = splitting.balanced_mod(_dot_int8(ares[..., s:e], bres[s:e]), m)
        acc = part if acc is None else splitting.balanced_mod(acc + part, m)
    return acc


def _dot_fp8(a: jax.Array, b: jax.Array) -> jax.Array:
    """float8_e4m3fn x float8_e4m3fn -> float32 contraction (FP8 tensor-core path)."""
    a8 = a.astype(jnp.float8_e4m3fn)
    b8 = b.astype(jnp.float8_e4m3fn)
    return jax.lax.dot_general(
        a8, b8, (((a8.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _chunked_modular_dot_fp8(ares: jax.Array, bres: jax.Array, m: int) -> jax.Array:
    """FP8-substrate modular product (paper §2.4): Karatsuba over 4-bit halves.

    x·y = 256·H + 16·(Mid − H − L) + L with H = x_h·y_h, L = x_l·y_l,
    Mid = (x_h+x_l)·(y_h+y_l).  Each plane accumulates exactly in FP32 (integer sums
    < 2²⁴ for k <= 2¹⁶); planes are reduced mod m *before* recombination so all int32
    arithmetic stays tiny.
    """
    k = ares.shape[-1]
    a_hi, a_lo = fp8_quant.fp8_split(ares)
    b_hi, b_lo = fp8_quant.fp8_split(bres)

    def plane(asrc, bsrc, s, e):
        return _dot_fp8(asrc[..., s:e].astype(jnp.float32),
                        bsrc[s:e].astype(jnp.float32))

    acc = None
    for s in range(0, k, _FP8_K_CHUNK):
        e = min(s + _FP8_K_CHUNK, k)
        H = plane(a_hi, b_hi, s, e).astype(jnp.int32)
        L = plane(a_lo, b_lo, s, e).astype(jnp.int32)
        Mid = plane(a_hi + a_lo, b_hi + b_lo, s, e).astype(jnp.int32)
        part = fp8_quant.fp8_karatsuba_combine(H, Mid, L, m)
        acc = part if acc is None else splitting.balanced_mod(acc + part, m)
    return acc


def modular_matmul(ares: jax.Array, bres: jax.Array, plan: Plan) -> jax.Array:
    """Stacked modular products C⁽ⁱ⁾, int32 (r, m, n), balanced representatives."""
    fn = (_chunked_modular_dot_int8 if plan.substrate == "int8"
          else _chunked_modular_dot_fp8)
    outs = [fn(ares[i], bres[i], m) for i, m in enumerate(plan.moduli)]
    return jnp.stack(outs, axis=0)


# ---------------------------------------------------------------------------
# Phase 3: Garner reconstruction
# ---------------------------------------------------------------------------

def garner_digits(accs: Sequence[jax.Array], plan: Plan) -> List[jax.Array]:
    """Balanced mixed-radix digits v_j (int32) from per-modulus residues.

    The integer half of the reconstruction, shared by the XLA reference and
    the fused kernels (which run it on their accumulators before the store).
    """
    gc = plan.garner
    ms = plan.moduli
    r = plan.r
    carry = [jnp.zeros_like(accs[0]) for _ in range(r)]
    digits: List[jax.Array] = []
    for j in range(r):
        t = splitting.balanced_mod(
            (splitting.balanced_mod(accs[j], ms[j]) - carry[j])
            * np.int32(gc.inv_pref[j]), ms[j])
        digits.append(t)
        for l in range(j + 1, r):
            carry[l] = splitting.balanced_mod(
                carry[l] + t * np.int32(gc.pref_mod[j, l]), ms[l])
    return digits


def _split_prefix(ph: np.ndarray, bits: int):
    """Host-side split of a prefix-product constant into (hi, lo) in ph's
    dtype: hi keeps all but the low ``bits`` significand bits, lo = ph - hi
    exactly.  Bit masking, not Veltkamp's (2**bits + 1) * ph, which overflows
    float32 for the largest prefixes of a 16-modulus plan (~2**116)."""
    it = np.dtype(f"uint{8 * ph.dtype.itemsize}")
    mask = np.asarray(~((1 << bits) - 1) & ((1 << (8 * ph.dtype.itemsize)) - 1), it)
    hi = (np.asarray(ph).view(it) & mask).view(ph.dtype)
    return hi, (ph - hi).astype(ph.dtype)


def digits_to_f64(digits: Sequence[jax.Array], plan: Plan,
                  out_dtype=jnp.float64) -> jax.Array:
    """Compensated double-double Horner over the digits (the float half).

    term_j = t_j * P_j with P_j = pref_f64 + pref_f64_lo exact, accumulated
    with two_sum and a compensation stream, so the result is the correctly
    rounded float of the exact integer.  The two_prod of each term needs no
    split of t_j (|t_j| <= 128 fits any half-mantissa exactly), and the split
    of the constant P_j is done once on the host.

    Where float64 is a float32 pair (XLA:TPU), the double-double algebra does
    not hold and its emulation costs tens of seconds of compile per program:
    there the digits run the double-single Horner, whose float32 EFTs are
    exact, and the pair it returns is the backend's float64.
    """
    if jnp.dtype(out_dtype) == jnp.float64 and backend.float64_is_f32_pair():
        hi, lo = digits_to_ds(digits, plan)
        return hi.astype(out_dtype) + lo.astype(out_dtype)
    gc = plan.garner
    np_dtype = np.dtype(jnp.dtype(out_dtype).name)
    bits = 27 if np_dtype == np.float64 else 12
    out = jnp.zeros(digits[0].shape, out_dtype)
    comp = jnp.zeros(digits[0].shape, out_dtype)
    for j, t in enumerate(digits):
        tf = t.astype(out_dtype)
        ph = np.asarray(gc.pref_f64[j], np_dtype)
        ph_h, ph_l = _split_prefix(ph, bits)
        p = tf * jnp.asarray(ph, out_dtype)
        e = (tf * jnp.asarray(ph_h, out_dtype) - p) + tf * jnp.asarray(ph_l, out_dtype)
        e = e + tf * jnp.asarray(gc.pref_f64_lo[j], out_dtype)
        # two_sum(out, p)
        s = out + p
        v = s - out
        comp = comp + ((out - (s - v)) + (p - v)) + e
        out = s
    return out + comp


def digits_to_ds(digits: Sequence[jax.Array], plan: Plan
                 ) -> Tuple[jax.Array, jax.Array]:
    """Double-single (f32, f32) reconstruction of the digits.

    P_j (to 48 bits) is held as four float32 constants, split on the host, of
    at most 16, 8, 16 and 8 significant bits, so each product with a digit
    (|t_j| <= 128) is exact: the two larger ones enter the (hi, lo) pair
    through two_sum, the two smaller ones are added to lo.  No product
    rounds, so a fused multiply-add (which XLA:CPU forms where it likes)
    cannot change a bit.  The result holds ~45-48 significant bits (vs 24 for
    a naive f32 Horner).
    """
    gc = plan.garner
    hi = jnp.zeros(digits[0].shape, jnp.float32)
    lo = jnp.zeros(digits[0].shape, jnp.float32)
    for j, t in enumerate(digits):
        tf = t.astype(jnp.float32)
        ph = np.float32(gc.pref_f64[j])
        c0, c1 = _split_prefix(ph, 8)
        c2, c3 = _split_prefix(np.float32(gc.pref_f64[j] - np.float64(ph)), 8)
        for c in (c0, c1):
            # two_sum(hi, t_j * c)
            p = tf * c
            s = hi + p
            v = s - hi
            lo = lo + ((hi - (s - v)) + (p - v))
            hi = s
        lo = lo + (tf * c2 + tf * c3)
    s = hi + lo
    lo = lo - (s - hi)
    return s, lo


def garner_reconstruct(cres: jax.Array, plan: Plan,
                       out_dtype=jnp.float64) -> jax.Array:
    """Balanced-digit Garner: recover the (signed) integer value as a float.

    cres: int32 (r, ...) balanced residues of the exact integer product.
    Cost O(r²) elementwise ops — the TME γ term; amortised O(r²/k) per FMA.

    Balanced digits make the representation of |C| << M terminate: digits beyond
    ~log2(2|C|) bits are exactly zero, so only prefix products comparable to |C|
    enter the float sum.  The accumulation runs in compensated double-double
    arithmetic with exact double-double prefix-product constants, so the returned
    value is the *correctly rounded* float of the exact integer: products whose
    unscaled value is representable in the output mantissa are recovered EXACTLY.
    """
    # Optimization barriers (identities on values) around the Garner step keep
    # XLA from fusing it with its producers and with the float epilogue: on
    # XLA:TPU's float64 emulation that fusion costs minutes of compile, and
    # on a (r, M, N) stack gigabytes of temporaries.
    accs = jax.lax.optimization_barrier(
        [cres[j].astype(jnp.int32) for j in range(plan.r)])
    digits = jax.lax.optimization_barrier(garner_digits(accs, plan))
    return digits_to_f64(digits, plan, out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# End-to-end emulated matmul
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("plan", "via_hilo", "out_dtype"))
def emulated_matmul(a: jax.Array, b: jax.Array, plan: Plan,
                    via_hilo: bool = True, out_dtype=jnp.float64) -> jax.Array:
    """FP64-accurate C = A @ B via Ozaki Scheme II on a low-precision substrate.

    a: (m, k), b: (k, n); float inputs (float64 for full FP64 emulation; float32
    inputs also work with payload clipped to 24 bits).
    """
    a = a.astype(out_dtype)
    b = b.astype(out_dtype)
    ares, ashift = decompose(a, plan, scale_axis=-1, via_hilo=via_hilo)
    bres, bshift = decompose(b, plan, scale_axis=0, via_hilo=via_hilo)
    cres = modular_matmul(ares, bres, plan)
    c_int = garner_reconstruct(cres, plan, out_dtype=out_dtype)
    return splitting.apply_unscale(c_int, ashift, bshift)


def emulated_matmul_batched(a: jax.Array, b: jax.Array, plan: Plan,
                            **kw) -> jax.Array:
    """vmap wrapper for (..., m, k) x (..., k, n) batched emulated matmuls."""
    if a.ndim == 2 and b.ndim == 2:
        return emulated_matmul(a, b, plan, **kw)
    fn = functools.partial(emulated_matmul, plan=plan, **kw)
    for _ in range(max(a.ndim, b.ndim) - 2):
        fn = jax.vmap(fn)
    return fn(a, b)
