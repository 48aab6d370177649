"""Shared in-kernel primitives for the fused Ozaki-II Pallas kernels.

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

All helpers are shape-polymorphic jnp code so they trace identically inside
pl.pallas_call (interpret or Mosaic) and in the XLA reference path.
"""

from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ozaki2
from repro.core.ozaki2 import digits_to_ds, digits_to_f64, garner_digits  # noqa: F401
from repro.core.splitting import balanced_mod, residues_int32  # noqa: F401

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
    return digits_to_f64(unstack_digits(raw), plan, out_dtype=dtype)


def stack_digits_int8(digits: Sequence[jax.Array]) -> jax.Array:
    return jnp.stack([d.astype(jnp.int8) for d in digits], axis=0)


def unstack_digits(d8: jax.Array) -> List[jax.Array]:
    return [d8[j].astype(jnp.int32) for j in range(d8.shape[0])]
