"""Fused Ozaki-II GEMM Pallas kernel (paper §5.1 discipline, dense-GEMM workload).

TPU mapping of the paper's register-fusion pattern:
  * operands arrive as exact (hi, lo) int32 pairs of the Phase-1 scaled integers —
    8 B/element, identical to native-FP64 HBM traffic (β = 1 for the inputs);
  * per-modulus residue planes are computed in VMEM immediately after the tile load
    (the paper's "in registers" — VREGs after Mosaic vectorisation);
  * one int8 × int8 → int32 MXU contraction per modulus per K-step, accumulated in a
    VMEM scratch (the paper's r accumulator fragments);
  * balanced-digit Garner runs on the accumulators before the single store.

Block shapes default to MXU-friendly multiples (second-minor 8/32, minor 128 lanes);
the VMEM working set is r·bm·bn·4 B of accumulator + (bm+bn)·bk·8 B of tiles —
r=16, bm=bn=128, bk=256: ~1.0 MiB + 0.5 MiB, comfortably inside a v5e core's VMEM.

``gemm`` is the whole pallas route of ``repro.core.dispatch.matmul``: the
shared prologue (``common.matmul_prologue``), ``gemm_hilo`` and the shared
epilogue (``common.matmul_epilogue``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import ozaki2, splitting
from repro.kernels import common


def _gemm_kernel(a_hi_ref, a_lo_ref, b_hi_ref, b_lo_ref, out_ref, acc_ref, *,
                 plan: ozaki2.Plan, out_rep: str, k_steps: int):
    kidx = pl.program_id(2)

    @pl.when(kidx == np.int32(0))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Residue decomposition of the freshly-loaded tiles — stays in VMEM/VREGs.
    a_res = splitting.residues_int32(a_hi_ref[...], a_lo_ref[...], plan.moduli)
    b_res = splitting.residues_int32(b_hi_ref[...], b_lo_ref[...], plan.moduli)

    for i, m in enumerate(plan.moduli):
        part = jax.lax.dot_general(
            a_res[i].astype(jnp.int8), b_res[i].astype(jnp.int8),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
        acc_ref[i] = splitting.balanced_mod(acc_ref[i] + part, m)

    @pl.when(kidx == np.int32(k_steps - 1))
    def _epilogue():
        digits = ozaki2.garner_digits([acc_ref[i] for i in range(plan.r)], plan)
        if out_rep == "ds":
            hi, lo = ozaki2.digits_to_ds(digits, plan)
            out_ref[0] = hi
            out_ref[1] = lo
        else:  # digits
            out_ref[...] = common.stack_digits_int8(digits)


@functools.partial(jax.jit, static_argnames=("plan", "out_rep", "bm", "bn", "bk",
                                             "interpret"))
def gemm_hilo(a_hi: jax.Array, a_lo: jax.Array, b_hi: jax.Array, b_lo: jax.Array,
              plan: ozaki2.Plan, out_rep: str = "digits",
              bm: int = 128, bn: int = 128, bk: int = 256,
              interpret: bool = True) -> jax.Array:
    """Raw kernel entry on pre-scaled (hi, lo) operands.  Shapes must tile evenly.

    Returns: ds f32 (2,M,N) | digits int8 (r,M,N) — the *integer-scaled*
    product; callers finish it (``common.finish``) and apply the exact
    power-of-two unscale.
    """
    M, K = a_hi.shape
    K2, N = b_hi.shape
    assert K == K2 and M % bm == 0 and N % bn == 0 and K % bk == 0, \
        (a_hi.shape, b_hi.shape, bm, bn, bk)
    k_steps = K // bk
    grid = (M // bm, N // bn, k_steps)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
    ]
    if out_rep == "ds":
        out_shape = jax.ShapeDtypeStruct((2, M, N), jnp.float32)
        out_spec = pl.BlockSpec((2, bm, bn), lambda i, j, k: (common.ZERO, i, j))
    elif out_rep == "digits":
        out_shape = jax.ShapeDtypeStruct((plan.r, M, N), jnp.int8)
        out_spec = pl.BlockSpec((plan.r, bm, bn), lambda i, j, k: (common.ZERO, i, j))
    else:
        raise ValueError(f"out_rep must be 'digits' or 'ds', got {out_rep!r}")

    kernel = functools.partial(_gemm_kernel, plan=plan, out_rep=out_rep,
                               k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((plan.r, bm, bn), jnp.int32)],
        interpret=interpret,
    )(a_hi, a_lo, b_hi, b_lo)


def gemm(a: jax.Array, b: jax.Array, plan: ozaki2.Plan, out_rep: str = "f64",
         bm: int = 128, bn: int = 128, bk: int = 256,
         interpret: bool = True) -> jax.Array:
    """FP64-accurate C = A @ B through the fused Pallas kernel."""
    M, K = a.shape
    _, N = b.shape
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    operands, shifts = common.matmul_prologue(a, b, plan, (bm, bk), (bk, bn))
    rep = common.kernel_rep(out_rep)
    raw = gemm_hilo(*operands, plan, out_rep=rep, bm=bm, bn=bn, bk=bk,
                    interpret=interpret)
    return common.matmul_epilogue(raw, plan, rep, shifts, (M, N))
