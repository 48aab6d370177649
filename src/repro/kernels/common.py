"""What the fused Ozaki-II Pallas kernels share: in-kernel primitives, the
XLA epilogue ``finish``, and the GEMM/GEMV prologue and epilogue.

The β→1 discipline (paper §5.1) in TPU terms: operands enter the kernel as (hi, lo)
int32 pairs (8 B/elem — the same HBM traffic as native FP64); residue planes are
computed *inside* the kernel in VMEM/VREGs and never round-trip to HBM; the Garner
reconstruction runs on the int32 accumulators before the store.

Output representations (the one place the TPU adaptation pays a real cost, since
Mosaic has no float64 type):
  f64    — the working-float result, bit-equivalent to the XLA reference: the
           kernel writes ``digits`` (``kernel_rep``) and XLA finishes (``finish``).
  digits — the kernel stores the r balanced mixed-radix digits as int8 (r
           bytes/output vs 8 for f64) and a cheap bandwidth-bound XLA epilogue
           finishes the double-double Horner.  β_out = r/8.
  ds     — two-float32 double-single output (8 B/output, β_out = 1) with ~49-bit
           accuracy: full-bandwidth mode for consumers that tolerate 2^-45 error.

The in-kernel helpers are shape-polymorphic jnp code so they trace identically
inside pl.pallas_call (interpret or Mosaic) and in the XLA reference path.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backend, ozaki2, splitting
from repro.obs import spans

OUT_REPS = ("f64", "digits", "ds")

# Literal block index 0 for index maps.  Under x64 a Python int traces as an
# int64 scalar, and Mosaic lowers no int64: every integer constant that enters
# a kernel or an index map is an explicit int32.
ZERO = np.int32(0)


def kernel_rep(out_rep: str) -> str:
    """What a kernel writes for a requested ``out_rep``, "digits" or "ds":
    Mosaic has no float64 type, so kernels write the digits of an "f64"
    request and ``finish`` reconstructs them in XLA."""
    if out_rep not in OUT_REPS:
        raise ValueError(f"out_rep must be one of {OUT_REPS}, got {out_rep!r}")
    return "ds" if out_rep == "ds" else "digits"


def finish(raw: jax.Array, plan: ozaki2.Plan, rep: str, dtype) -> jax.Array:
    """Epilogue in XLA: a kernel's raw ``rep`` output -> working float."""
    if rep == "ds":
        return raw[0].astype(dtype) + raw[1].astype(dtype)
    return ozaki2.digits_to_f64(unstack_digits(raw), plan, out_dtype=dtype)


def _pad2(x: jax.Array, bm: int, bn: int) -> jax.Array:
    M, N = x.shape
    pm, pn = (-M) % bm, (-N) % bn
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x


def matmul_prologue(a: jax.Array, b: jax.Array, plan: ozaki2.Plan,
                    a_block: Tuple[int, int], b_block: Tuple[int, int]):
    """The fused GEMM's and GEMV's prologue: A scaled by rows and B by
    columns (Phase 1), each split into (hi, lo) int32 halves and zero-padded
    to whole ``a_block`` and ``b_block`` tiles.  Returns the four halves and
    the two shifts."""
    f64 = backend.working_float()
    # Scopes around the statements in their trace order (``repro.obs.spans``).
    with spans.scope("ozaki.split_a"):
        ai, sa = splitting.scale_to_int(a.astype(f64), plan.payload_bits, axis=-1)
    with spans.scope("ozaki.split_b"):
        bi, sb = splitting.scale_to_int(b.astype(f64), plan.payload_bits, axis=0)
    with spans.scope("ozaki.split_a"):
        a_hi, a_lo = splitting.split_hi_lo(ai)
    with spans.scope("ozaki.split_b"):
        b_hi, b_lo = splitting.split_hi_lo(bi)
    with spans.scope("ozaki.split_a"):
        a_hi, a_lo = _pad2(a_hi, *a_block), _pad2(a_lo, *a_block)
    with spans.scope("ozaki.split_b"):
        b_hi, b_lo = _pad2(b_hi, *b_block), _pad2(b_lo, *b_block)
    return (a_hi, a_lo, b_hi, b_lo), (sa, sb)


def matmul_epilogue(raw: jax.Array, plan: ozaki2.Plan, rep: str, shifts,
                    shape: Tuple[int, int]) -> jax.Array:
    """The fused GEMM's and GEMV's epilogue: ``finish`` to the working float,
    the padding sliced off, and the exact unscale by ``matmul_prologue``'s
    shifts."""
    m, n = shape
    with spans.scope("ozaki.finish"):
        c = finish(raw, plan, rep, backend.working_float())[..., :m, :n]
        return splitting.apply_unscale(c, *shifts)


def stack_digits_int8(digits: Sequence[jax.Array]) -> jax.Array:
    return jnp.stack([d.astype(jnp.int8) for d in digits], axis=0)


def unstack_digits(d8: jax.Array) -> List[jax.Array]:
    return [d8[j].astype(jnp.int32) for j in range(d8.shape[0])]
