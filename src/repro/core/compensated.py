"""Compensated reductions — the paper's BLAS-1 closure (§7.1(a) + companion FFT).

The dwarf audit routes BLAS-1 (ddot, dnrm2, CG residuals, FFT scalings) onto
the healthy low-precision vector pipe with error-free-transformation
compensation instead of Ozaki emulation.  This module is the canonical home of
those reductions; the error-free transformations themselves (``two_sum``,
``two_prod``, ``fast_two_sum``) live in ``repro.core.numerics`` and are
re-exported here.

Blocked EFT execution
---------------------
Error-free transformations are blockwise-associative: applying ``two_sum`` in
*any* order and accumulating every discarded rounding term in a plain
compensation stream yields the same Sum2/Dot2 error bound, because each
``two_sum``/``two_prod`` is exact and only the compensation stream (already
O(u)·magnitude) is summed in working precision.  The fast path exploits this:

  1. the operand is zero-padded (exact: ``two_sum(s, 0) = (s, 0)``) and
     reshaped to ``(nblocks, block)`` with ``block`` ~256–1024 lanes from the
     dispatch autotuning table (``repro.core.dispatch.reduce_block``);
  2. within each block, a pairwise ``two_sum`` tree (``log2(block)`` lane-wise
     vector steps, vmapped over all blocks at once) produces per-block partials
     ``(s_b, c_b)``;
  3. a short carry-propagating ``lax.scan`` over the ``nblocks`` partials
     (n/block steps, e.g. 8 for n=4096) folds them with ``two_sum``, feeding
     the carries into the compensation stream;
  4. the result is ``s + c`` — identical math to the element-wise scan, at
     vector-pipe cost, and the whole pipeline is jitted per (shape, block).

Error bound: every product error (``two_prod``) and every summation rounding
(``two_sum``) is captured exactly; only their *sum* rounds.  For ``n`` terms in
precision ``u`` this gives the Ogita-Rump Dot2/Sum2 bound

    |result − exact| ≤ u·|exact| + O(u²)·cond,

where cond = Σ|x_i·y_i| / |Σ x_i·y_i| — twice-working-precision for any
blocking, which is what licenses the blocked evaluation order.  The element
-wise ``lax.scan`` forms are retained as ``*_scan`` references (the parity
oracle in tests/test_compensated.py asserts ≤ 1 ulp agreement).

Provided reductions (working-dtype in/out, ``axis``-aware/batched):
  * ``neumaier_sum``     — compensated summation.  The blocked form uses the
    full Knuth ``two_sum`` EFT, which captures the rounding error exactly for
    *either* magnitude ordering — at least as accurate as the Kahan-Babuska-
    Neumaier case split it replaces (|error| <= 2u·Σ|x| + O(u²));
  * ``compensated_dot``  — Ogita-Rump Dot2: ``two_prod`` each term, ``two_sum``
    the accumulation, carry both error streams — ~twice-working-precision;
  * ``compensated_norm`` — overflow/underflow-safe 2-norm: exact power-of-two
    pre-scaling derived from IEEE bit fields (never the roundable
    ``2.0 ** floor(log2 absmax)``), then a compensated sum of exact
    squared-term pairs.  XLA CPU arithmetic runs flush-to-zero/
    denormals-are-zero — ``jnp.frexp`` misdecodes denormals and any
    mul/div with a denormal operand yields 0 — so the scaling decomposes
    ``|x| = m * 2**e`` via ``lax.bitcast_convert_type`` (bit ops are immune
    to FTZ/DAZ) and denormal *results* are stored by integer-rounding the
    significand and bitcasting it back.  Non-finite semantics are explicit
    and match ``np.linalg.norm``: any NaN → NaN, else any ±inf → +inf.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import backend, splitting
from repro.core.numerics import fast_two_sum, two_prod, two_sum  # noqa: F401
from repro.obs import spans, telemetry as obs

__all__ = ["two_sum", "two_prod", "fast_two_sum", "neumaier_sum",
           "compensated_dot", "compensated_norm", "neumaier_sum_scan",
           "compensated_dot_scan"]


# ---------------------------------------------------------------------------
# Blocked fast path
# ---------------------------------------------------------------------------

def _resolve_block(n: int, block: Optional[int]) -> int:
    if block is None:
        from repro.core import dispatch  # deferred: dispatch does not import us
        block = dispatch.reduce_block(n)
    return max(1, min(int(block), n))


def _pad_to_blocks(x: jax.Array, block: int) -> jax.Array:
    """Zero-pad the last axis to a block multiple (exact for sum and dot)."""
    pad = (-x.shape[-1]) % block
    if pad:
        widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
        x = jnp.pad(x, widths)
    return x


def _block_tree(p: jax.Array, c: jax.Array):
    """Pairwise two_sum tree over the last axis (lane-wise, all blocks at
    once).  Returns per-block partials (s_b, c_b); every discarded rounding
    term lands in the compensation stream c_b."""
    while p.shape[-1] > 1:
        if p.shape[-1] % 2:                  # odd width: add a zero lane (exact)
            zero = jnp.zeros(p.shape[:-1] + (1,), p.dtype)
            p = jnp.concatenate([p, zero], axis=-1)
            c = jnp.concatenate([c, zero], axis=-1)
        s, e = two_sum(p[..., 0::2], p[..., 1::2])
        c = c[..., 0::2] + c[..., 1::2] + e
        p = s
    return p[..., 0], c[..., 0]


def _carry_scan(s_b: jax.Array, c_b: jax.Array) -> jax.Array:
    """Short carry-propagating scan over per-block partials (leading axis)."""
    def step(carry, inp):
        s, c = carry
        sb, cb = inp
        s, e = two_sum(s, sb)
        return (s, c + (e + cb)), None

    zero = jnp.zeros_like(s_b[0])
    (s, c), _ = jax.lax.scan(step, (zero, zero), (s_b, c_b))
    return s + c


@partial(jax.jit, static_argnames=("block",))
def _blocked_sum2(p: jax.Array, e: jax.Array, block: int) -> jax.Array:
    """Compensated sum of p (+ pre-existing error stream e) along the last
    axis: block tree → per-block partials → carry scan."""
    with spans.scope("reduce.dot2"):
        p = _pad_to_blocks(p, block)
        e = _pad_to_blocks(e, block)
        nb = p.shape[-1] // block
        shape = p.shape[:-1] + (nb, block)
        s_b, c_b = _block_tree(p.reshape(shape), e.reshape(shape))
        # scan wants the block axis leading; batch dims ride along.
        return _carry_scan(jnp.moveaxis(s_b, -1, 0), jnp.moveaxis(c_b, -1, 0))


def _normalize_axis(axis: int, ndim: int) -> int:
    if not -ndim <= axis < ndim:
        raise ValueError(f"axis {axis} out of range for ndim {ndim}")
    return axis % ndim


# ---------------------------------------------------------------------------
# Public reductions — blocked fast path
# ---------------------------------------------------------------------------

def neumaier_sum(x: jax.Array, axis: int = -1,
                 block: Optional[int] = None) -> jax.Array:
    """Compensated (twice-working-precision) sum along ``axis``.

    Jitted blocked EFT (see module docstring); ``block`` defaults to the
    dispatch autotuning table's choice for this length.  Batched: all other
    axes are preserved.
    """
    x = jnp.asarray(x)
    x = jnp.moveaxis(x, _normalize_axis(axis, x.ndim), -1)
    rec = obs.op_start("reduce", (x.shape[-1],), "xla", None, x, label="sum2")
    out = _blocked_sum2(x, jnp.zeros_like(x), _resolve_block(x.shape[-1], block))
    return obs.op_end(rec, out)


def _dot_impl(x: jax.Array, y: jax.Array, axis: int,
              block: Optional[int]) -> jax.Array:
    """Blocked Dot2 body, shared by ``compensated_dot`` (which records a
    telemetry event) and ``compensated_norm`` (which records its own — one
    event per public call, not one per internal reduction)."""
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    if x.shape != y.shape:
        raise ValueError(f"operand shapes differ: {x.shape} vs {y.shape}")
    ax = _normalize_axis(axis, x.ndim)
    x = jnp.moveaxis(x, ax, -1)
    y = jnp.moveaxis(y, ax, -1)
    p, e = two_prod(x, y)
    return _blocked_sum2(p, e, _resolve_block(x.shape[-1], block))


def compensated_dot(x: jax.Array, y: jax.Array, axis: int = -1,
                    block: Optional[int] = None) -> jax.Array:
    """Ogita-Rump Dot2 inner product: ~twice-working-precision accuracy.

    Every elementwise product is split exactly with ``two_prod`` and the
    accumulation carries the ``two_sum`` rounding errors, so the result error
    is O(u²·cond) — in FP32 this is the §7.1(a) "FP32 pipe + compensation"
    BLAS-1 path at ~2^-48 effective accuracy.  ``axis`` selects the reduction
    axis (batched over the rest); operands must have matching shapes.
    """
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    rec = obs.op_start("reduce", (x.shape[_normalize_axis(axis, x.ndim)],),
                       "xla", None, x, y, label="dot2")
    return obs.op_end(rec, _dot_impl(x, y, axis, block))


# IEEE-754 layouts: dtype -> (bit-int dtype, mantissa bits, exponent bias,
# exponent width).  Used for FTZ/DAZ-immune exact scaling in compensated_norm.
_IEEE = {
    jnp.dtype(jnp.float32): (jnp.int32, 23, 127, 8),
    jnp.dtype(jnp.float64): (jnp.int64, 52, 1023, 11),
}


def _ieee_layout(dtype):
    try:
        return _IEEE[jnp.dtype(dtype)]
    except KeyError:
        raise TypeError(
            f"compensated_norm: unsupported dtype {jnp.dtype(dtype)}"
        ) from None


def _pow2(p: jax.Array, dtype) -> jax.Array:
    """Exact power of two ``2**p`` built from bit fields (clamped to the
    normal range, so multiplying by it never hands DAZ a denormal operand)."""
    it, mb, eb, _ = _ieee_layout(dtype)
    p = jnp.clip(p, 1 - eb, eb)
    return jax.lax.bitcast_convert_type((p + eb).astype(it) << mb, dtype)


def _decompose(x: jax.Array):
    """Exact ``|x| = m * 2**e`` from IEEE bit fields: ``m`` an integer-valued
    float in ``[0, 2**(mb+1))``, ``e`` an int32 exponent.

    Bit operations are immune to flush-to-zero/denormals-are-zero, so this is
    exact for denormal inputs — which XLA CPU arithmetic (``jnp.frexp``,
    mul/div) otherwise treats as zero.
    """
    it, mb, eb, ew = _ieee_layout(x.dtype)
    bits = jax.lax.bitcast_convert_type(x, it)
    bits = bits & ((1 << (mb + ew)) - 1)          # clear the sign bit
    expf = (bits >> mb).astype(jnp.int32)
    mant = bits & ((1 << mb) - 1)
    denorm = expf == 0
    m = jnp.where(denorm, mant, mant | (1 << mb)).astype(x.dtype)
    e = jnp.where(denorm, 1, expf) - (eb + mb)
    return m, e


def compensated_norm(x: jax.Array, axis: Optional[int] = None) -> jax.Array:
    """Overflow/underflow-safe compensated 2-norm ||x||_2.

    ``axis=None`` (default) reduces over all elements; an integer ``axis``
    reduces that axis only (batched).  The operand is pre-scaled by an exact
    power of two at its magnitude ceiling so squared terms neither overflow
    for ~1e200 inputs nor vanish for denormal-only inputs, and the
    compensated accumulation preserves ~2x-working-precision in the sum.

    XLA CPU arithmetic is flush-to-zero/denormals-are-zero, so the scaling
    never touches a denormal with arithmetic: inputs are decomposed into
    ``m * 2**e`` via bit fields (exact, FTZ-immune), scaled by bit-built
    powers of two, and a result that lands in the denormal range is stored
    by integer-rounding its significand and bitcasting — correctly rounded
    where plain arithmetic would flush it to 0.

    Edge cases (explicit, matching ``np.linalg.norm``):
      * all-zero input → 0.0;
      * any NaN → NaN;
      * otherwise any ±inf → +inf.
    """
    x = jnp.asarray(x)
    if axis is None:
        x = x.reshape(-1)
        ax = 0
    else:
        ax = _normalize_axis(axis, x.ndim)
    rec = obs.op_start("reduce", (x.shape[ax],), "xla", None, x, label="nrm2")
    finite = jnp.isfinite(x)
    has_nan = jnp.any(jnp.isnan(x), axis=ax)
    has_inf = jnp.any(jnp.isinf(x), axis=ax)
    # Non-finite entries are masked out of the scaled accumulation so the
    # normal path never produces inf - inf = NaN; the flags override below.
    xf = jnp.where(finite, x, 0.0)
    if jnp.dtype(x.dtype).itemsize == 8 and backend.float64_is_f32_pair():
        nrm = _norm_f32_pair(xf, ax)
    else:
        nrm = _norm_bits(xf, ax)
    nrm = jnp.where(has_inf, jnp.asarray(jnp.inf, nrm.dtype), nrm)
    return obs.op_end(rec, jnp.where(has_nan, jnp.asarray(jnp.nan, nrm.dtype),
                                     nrm))


def _norm_bits(xf: jax.Array, ax: int) -> jax.Array:
    """IEEE path of ``compensated_norm``: scaling from bit fields."""
    it, mb, eb, _ = _ieee_layout(xf.dtype)
    m, e = _decompose(xf)
    # floor(log2 |x_i|) = e + (exponent of m's leading bit); m is normal or
    # zero here, where frexp is reliable.
    _, mex = jnp.frexp(m)
    sentinel = jnp.int32(-(1 << 30))
    elog = jnp.where(m > 0, e + mex - 1, sentinel)
    es = jnp.max(elog, axis=ax, keepdims=True)
    es = jnp.where(es == sentinel, 0, es)         # all-zero slice: scale 1
    # xs = |x_i| / 2**es, exact: the largest element lands in [1, 2), so
    # squares can neither overflow nor flush.  (Elements so far below absmax
    # that the clip in _pow2 engages contribute < u**4 relatively — below
    # even the compensated bound.)
    xs = m * _pow2(e - es, xf.dtype)
    r = jnp.sqrt(_dot_impl(xs, xs, ax, None))          # in [1, ~2*sqrt(n)]
    es = jnp.squeeze(es, ax)
    # Reconstruct r * 2**es.  Two exact power-of-two multiplies cover the
    # normal range (split so neither factor over/underflows); ...
    half = es // 2
    big = (r * _pow2(half, xf.dtype)) * _pow2(es - half, xf.dtype)
    # ... and a result in the denormal range (or the first normal binade) is
    # t = value * 2**(eb+mb-1) < 2**(mb+1), whose integer rounding IS the
    # result's bit pattern — FTZ'd arithmetic cannot produce these values.
    t = r * _pow2(es + (eb + mb - 1), xf.dtype)
    tiny = t < 2.0 ** (mb + 1)
    k = jnp.round(jnp.where(tiny, t, 0.0)).astype(it)
    return jnp.where(tiny, jax.lax.bitcast_convert_type(k, xf.dtype), big)


def _norm_f32_pair(xf: jax.Array, ax: int) -> jax.Array:
    """``compensated_norm`` where float64 is a float32 pair (XLA:TPU): no
    64-bit bit fields to read, and no denormals to keep.  The scale comes
    from log2 of the largest magnitude; ``splitting.ldexp`` applies it
    exactly, so squares neither overflow nor flush."""
    a = jnp.abs(xf)
    amax = jnp.max(a, axis=ax, keepdims=True)
    es = jnp.floor(jnp.log2(jnp.where(amax > 0, amax, 1.0))).astype(jnp.int32)
    xs = splitting.ldexp(a, -es)
    r = jnp.sqrt(_dot_impl(xs, xs, ax, None))
    return splitting.ldexp(r, jnp.squeeze(es, ax))


# ---------------------------------------------------------------------------
# Element-wise scan references (the parity oracle for the blocked fast path)
# ---------------------------------------------------------------------------

def neumaier_sum_scan(x: jax.Array, axis: int = -1) -> jax.Array:
    """Kahan-Babuska-Neumaier compensated reduction along ``axis``.

    Element-wise ``lax.scan`` reference (O(n) sequential steps, ~50 ms per
    4096-element call on CPU): retained as the parity/accuracy oracle for the
    blocked fast path, not a production code path.
    """
    xm = jnp.moveaxis(x, axis, 0)

    def step(carry, xi):
        s, c = carry
        t = s + xi
        # Feed the two_sum error of (s + xi) into the compensation stream;
        # branchless form of Neumaier's |s| >= |xi| case split.
        c = c + jnp.where(jnp.abs(s) >= jnp.abs(xi),
                          (s - t) + xi, (xi - t) + s)
        return (t, c), None

    zero = jnp.zeros_like(xm[0])
    (s, c), _ = jax.lax.scan(step, (zero, zero), xm)
    return s + c


def compensated_dot_scan(x: jax.Array, y: jax.Array) -> jax.Array:
    """Element-wise Dot2 scan over 1-D operands — the retained reference
    implementation the blocked ``compensated_dot`` is parity-tested against."""
    p, e = two_prod(x, y)

    def step(carry, inp):
        s, c = carry
        pi, ei = inp
        s, e2 = two_sum(s, pi)
        return (s, c + (e2 + ei)), None

    zero = jnp.zeros((), x.dtype)
    (s, c), _ = jax.lax.scan(step, (zero, zero), (p, e))
    return s + c
