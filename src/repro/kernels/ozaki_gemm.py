"""Fused Ozaki-II GEMM Pallas kernel (paper §5.1 discipline, dense-GEMM workload).

TPU mapping of the paper's register-fusion pattern:
  * the prologue (XLA) scales and splits each operand into exact (hi, lo)
    int32 halves (``common.matmul_prologue``) and builds its balanced int8
    residue planes once per element, in every call, under the ``ozaki.residues``
    scope: A as ``s8[r, M, K]``, B as ``s8[r, K, N]``.  The kernel's k-loop
    does no remainder arithmetic on its operands;
  * one int8 × int8 → int32 MXU contraction per modulus per K-step, added into
    a VMEM scratch (the paper's r accumulator fragments) with no remainder:
    ``reduce_interval`` K-steps of products cannot overflow int32, so the
    accumulator is reduced only every that many steps (at K = 8192, never
    before the last);
  * balanced-digit Garner runs on the accumulators, ``EPILOGUE_ROWS`` rows of
    the tile at a time, before the single store.

HBM bytes: the prologue reads 8 B/element of (hi, lo) and writes r B/element
of planes; the kernel reads each A plane element N/bn times and each B plane
element M/bm times, r B a visit.  With the tuned 512/512/512 tiles
(``dispatch.TUNE_TABLE``) at 8192^3 and r = 16 that is ~34 GB a call, ~42 ms
at 819 GB/s (137 GB at 128 x 128).  VMEM working set of a grid step: the
double-buffered plane tiles, 2·r·(bm+bn)·bk B = 16 MiB, the int32
accumulator, r·bm·bn·4 B = 16 MiB, and the output block, 2·r·bm·bn B = 8 MiB
double-buffered; ``_vmem_limit_bytes`` grants 96 MiB of a v5e core's 128 MiB.

``gemm`` is the whole pallas route of ``repro.core.dispatch.matmul``: the
shared prologue (``common.matmul_prologue``), the residue planes,
``gemm_hilo`` and the shared epilogue (``common.matmul_epilogue``).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import ozaki2, splitting
from repro.kernels import common
from repro.obs import spans

_INT32_MAX = (1 << 31) - 1

# Rows of an output tile that Garner reconstructs at a time: one int8 sublane
# tile.  Mosaic unrolls the epilogue over every vreg it touches: at 8192^3 with
# 512 x 512 tiles on one TPU v5e the kernel took 137.6 ms with the whole tile
# at once (110 s to compile), 122.7 ms with 64 rows and 111.8 ms with 32 (9 s).
EPILOGUE_ROWS = 32


def reduce_interval(bk: int, moduli: Sequence[int]) -> int:
    """K-steps of ``bk`` products that an int32 accumulator holding one
    balanced residue can take without overflow.

    A balanced residue of m is at most h = ⌈max m / 2⌉ in magnitude, so s
    steps add at most s·bk·h² to it: s is the largest with
    s·bk·h² + h < 2³¹.
    """
    h = (max(moduli) + 1) // 2
    s = (_INT32_MAX - h) // (bk * h * h)
    if s < 1:
        raise ValueError(f"bk={bk} products of residues mod {max(moduli)} "
                         f"overflow an int32 accumulator")
    return s


def _vmem_limit_bytes(r: int, bm: int, bn: int, bk: int) -> int:
    """Scoped VMEM for one grid step: the double-buffered int8 plane tiles,
    the int32 accumulator and three times its size again (a bound on the
    double-buffered output block and Garner's temporaries), and Mosaic's
    default 16 MiB for its own use."""
    return 2 * r * (bm + bn) * bk + 4 * r * bm * bn * 4 + (16 << 20)


def _gemm_kernel(a_ref, b_ref, out_ref, acc_ref, *, plan: ozaki2.Plan,
                 out_rep: str, k_steps: int, interval: int,
                 strip_rows: int):
    kidx = pl.program_id(2)

    @pl.when(kidx == np.int32(0))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for i in range(plan.r):
        acc_ref[i] = acc_ref[i] + jax.lax.dot_general(
            a_ref[i], b_ref[i], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)

    if k_steps > interval:
        @pl.when((kidx + np.int32(1)) % np.int32(interval) == np.int32(0))
        def _reduce():
            for i, m in enumerate(plan.moduli):
                acc_ref[i] = splitting.balanced_mod(acc_ref[i], m)

    @pl.when(kidx == np.int32(k_steps - 1))
    def _epilogue():
        def strip(s, carry):
            rows = pl.ds(pl.multiple_of(s * np.int32(strip_rows), strip_rows),
                         strip_rows)
            # garner_digits reduces each accumulator mod its modulus first.
            digits = ozaki2.garner_digits(
                [acc_ref[i, rows, :] for i in range(plan.r)], plan)
            if out_rep == "ds":
                hi, lo = ozaki2.digits_to_ds(digits, plan)
                out_ref[0, rows, :] = hi
                out_ref[1, rows, :] = lo
            else:  # digits
                out_ref[:, rows, :] = common.stack_digits_int8(digits)
            return carry

        common.loop(acc_ref.shape[1] // strip_rows, strip, np.int32(0))


@functools.partial(jax.jit, static_argnames=("plan", "out_rep", "bm", "bn", "bk",
                                             "interpret"))
def gemm_hilo(a_res: jax.Array, b_res: jax.Array, plan: ozaki2.Plan,
              out_rep: str = "digits", bm: int = 128, bn: int = 128,
              bk: int = 256, interpret: bool = True) -> jax.Array:
    """Raw kernel entry on the int8 residue planes a_res (r, M, K) and
    b_res (r, K, N) of pre-scaled operands.  Shapes must tile evenly.

    Returns: ds f32 (2,M,N) | digits int8 (r,M,N) — the *integer-scaled*
    product; callers finish it (``common.finish``) and apply the exact
    power-of-two unscale.

    The name is the Mosaic custom call's in the compiled program
    (``gemm_hilo.<n>``), which the benchmark's trace readers match: it stays
    although the kernel now reads residue planes, not (hi, lo) halves.
    """
    r, M, K = a_res.shape
    _, K2, N = b_res.shape
    assert r == plan.r and K == K2 and M % bm == 0 and N % bn == 0 \
        and K % bk == 0, (a_res.shape, b_res.shape, bm, bn, bk)
    k_steps = K // bk
    grid = (M // bm, N // bn, k_steps)

    in_specs = [
        pl.BlockSpec((r, bm, bk), lambda i, j, k: (common.ZERO, i, k)),
        pl.BlockSpec((r, bk, bn), lambda i, j, k: (common.ZERO, k, j)),
    ]
    if out_rep == "ds":
        out_shape = jax.ShapeDtypeStruct((2, M, N), jnp.float32)
        out_spec = pl.BlockSpec((2, bm, bn), lambda i, j, k: (common.ZERO, i, j))
    elif out_rep == "digits":
        out_shape = jax.ShapeDtypeStruct((r, M, N), jnp.int8)
        out_spec = pl.BlockSpec((r, bm, bn), lambda i, j, k: (common.ZERO, i, j))
    else:
        raise ValueError(f"out_rep must be 'digits' or 'ds', got {out_rep!r}")

    kernel = functools.partial(_gemm_kernel, plan=plan, out_rep=out_rep,
                               k_steps=k_steps,
                               interval=reduce_interval(bk, plan.moduli),
                               strip_rows=(EPILOGUE_ROWS if bm % EPILOGUE_ROWS == 0
                                           else bm))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((r, bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit_bytes(r, bm, bn, bk)),
        interpret=interpret,
    )(a_res, b_res)


def gemm(a: jax.Array, b: jax.Array, plan: ozaki2.Plan, out_rep: str = "f64",
         bm: int = 128, bn: int = 128, bk: int = 256,
         interpret: bool = True) -> jax.Array:
    """FP64-accurate C = A @ B through the fused Pallas kernel."""
    M, K = a.shape
    _, N = b.shape
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    (a_hi, a_lo, b_hi, b_lo), shifts = common.matmul_prologue(
        a, b, plan, (bm, bk), (bk, bn))
    with spans.scope("ozaki.residues"):
        a_res = splitting.residues_from_hilo(a_hi, a_lo, plan.moduli)
        b_res = splitting.residues_from_hilo(b_hi, b_lo, plan.moduli)
    rep = common.kernel_rep(out_rep)
    raw = gemm_hilo(a_res, b_res, plan, out_rep=rep, bm=bm, bn=bn, bk=bk,
                    interpret=interpret)
    return common.matmul_epilogue(raw, plan, rep, shifts, (M, N))
