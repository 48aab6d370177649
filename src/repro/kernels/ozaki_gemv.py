"""Fused Ozaki-II batched GEMV Pallas kernel (paper §5.2, Algorithm 1).

Y = A·X with A (M, N) and a small batch X (N, B).  B maps onto the MXU minor
dimension (the paper's 16/32-wide tensor-core n-dim); the M and N axes tile.
Operational intensity ≈ B/2 FLOPs/B, the regime where the TME model predicts the
largest memory-bound win on FP64-starved parts (~24x on B300 at B=8).

The fusion discipline: (hi, lo) int32 operands in, residues built per tile and
accumulators VMEM-resident, Garner before store.  Register-pressure
note from §5.2: r accumulator planes of (bm, B) int32 — at r=16, bm=128, B=8 that
is 64 KiB of VMEM scratch, far below the spill threshold; the paper's caveat that
B ≳ 8 forces spilling applies to CUDA register files, not to VMEM-scale scratch
(a real difference between the TPU and a GPU).

``gemv`` is the narrow-RHS pallas route of ``repro.core.dispatch.matmul``:
the GEMM's prologue and epilogue (``common``) around ``gemv_hilo``.
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


def _gemv_kernel(a_hi_ref, a_lo_ref, x_hi_ref, x_lo_ref, out_ref, acc_ref, *,
                 plan: ozaki2.Plan, out_rep: str, k_steps: int):
    kidx = pl.program_id(1)

    @pl.when(kidx == np.int32(0))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a_res = splitting.residues_int32(a_hi_ref[...], a_lo_ref[...], plan.moduli)
    x_res = splitting.residues_int32(x_hi_ref[...], x_lo_ref[...], plan.moduli)

    for i, m in enumerate(plan.moduli):
        part = jax.lax.dot_general(
            a_res[i].astype(jnp.int8), x_res[i].astype(jnp.int8),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
        acc_ref[i] = splitting.balanced_mod(acc_ref[i] + part, m)

    @pl.when(kidx == np.int32(k_steps - 1))
    def _epilogue():
        digits = ozaki2.garner_digits([acc_ref[i] for i in range(plan.r)], plan)
        if out_rep == "ds":
            hi, lo = ozaki2.digits_to_ds(digits, plan)
            out_ref[0] = hi
            out_ref[1] = lo
        else:
            out_ref[...] = common.stack_digits_int8(digits)


@functools.partial(jax.jit, static_argnames=("plan", "out_rep", "bm", "bk",
                                             "interpret"))
def gemv_hilo(a_hi: jax.Array, a_lo: jax.Array, x_hi: jax.Array, x_lo: jax.Array,
              plan: ozaki2.Plan, out_rep: str = "digits", bm: int = 128,
              bk: int = 256, interpret: bool = True) -> jax.Array:
    M, N = a_hi.shape
    _, B = x_hi.shape
    assert M % bm == 0 and N % bk == 0
    k_steps = N // bk
    grid = (M // bm, k_steps)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, k: (i, k)),
        pl.BlockSpec((bm, bk), lambda i, k: (i, k)),
        pl.BlockSpec((bk, B), lambda i, k: (k, common.ZERO)),
        pl.BlockSpec((bk, B), lambda i, k: (k, common.ZERO)),
    ]
    if out_rep == "ds":
        out_shape = jax.ShapeDtypeStruct((2, M, B), jnp.float32)
        out_spec = pl.BlockSpec((2, bm, B), lambda i, k: (common.ZERO, i, common.ZERO))
    elif out_rep == "digits":
        out_shape = jax.ShapeDtypeStruct((plan.r, M, B), jnp.int8)
        out_spec = pl.BlockSpec((plan.r, bm, B), lambda i, k: (common.ZERO, i, common.ZERO))
    else:
        raise ValueError(f"out_rep must be 'digits' or 'ds', got {out_rep!r}")

    kernel = functools.partial(_gemv_kernel, plan=plan, out_rep=out_rep,
                               k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((plan.r, bm, B), jnp.int32)],
        interpret=interpret,
    )(a_hi, a_lo, x_hi, x_lo)


def gemv(a: jax.Array, x: jax.Array, plan: ozaki2.Plan, out_rep: str = "f64",
         bm: int = 128, bk: int = 256, interpret: bool = True) -> jax.Array:
    """Batched GEMV Y = A @ X (paper Alg. 1): A (M,N) fp64, X (N,B) with small B."""
    M, N = a.shape
    _, B = x.shape
    bm, bk = min(bm, M), min(bk, N)
    operands, shifts = common.matmul_prologue(a, x, plan, (bm, bk), (bk, B))
    rep = common.kernel_rep(out_rep)
    raw = gemv_hilo(*operands, plan, out_rep=rep, bm=bm, bk=bk,
                    interpret=interpret)
    return common.matmul_epilogue(raw, plan, rep, shifts, (M, B))
