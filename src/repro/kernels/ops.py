"""Jit'd public wrappers for the fused Ozaki-II Pallas kernels.

Two tiers of wrapper live here:

  * ``ozaki_gemm`` / ``ozaki_gemv`` — kernel-level wrappers: the cheap
    streaming pre/post work (Phase-1 scaling, hi/lo split, padding to block
    multiples, digit epilogue, exact unscale) around one ``pallas_call``.
    These ARE the pallas route; ``repro.core.dispatch.matmul`` calls them and
    decides ``interpret`` (Mosaic on TPU, interpreter elsewhere).
  * ``ozaki_spmv_bell`` / ``ozaki_stencil7`` / ``ozaki_attention`` — routed
    entry points: thin delegates to ``dispatch.spmv`` / ``dispatch.stencil7``
    / ``dispatch.attention``, so ``mode_scope`` / ``REPRO_DISPATCH`` flips
    them between the fused kernel and the bit-identical reference like every
    other multiplication in the repo.  Route selection (and the interpret
    flavour of the pallas route) lives in the dispatch layer only.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import dispatch, ozaki2, splitting
from repro.kernels import common
from repro.kernels import ozaki_gemm as _gemm
from repro.kernels import ozaki_gemv as _gemv
from repro.obs import spans


def _pad2(x: jax.Array, bm: int, bn: int) -> jax.Array:
    M, N = x.shape
    pm, pn = (-M) % bm, (-N) % bn
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x


def _working_f64():
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


def ozaki_gemm(a: jax.Array, b: jax.Array, plan: Optional[ozaki2.Plan] = None,
               out_rep: str = "f64", bm: int = 128, bn: int = 128, bk: int = 256,
               interpret: Optional[bool] = None) -> jax.Array:
    """FP64-accurate C = A @ B through the fused Pallas kernel."""
    M, K = a.shape
    _, N = b.shape
    if plan is None:
        plan = dispatch.get_plan(K)
    if interpret is None:
        interpret = dispatch.pallas_interpret("gemm")
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    f64 = _working_f64()

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
        a_hi, a_lo = _pad2(a_hi, bm, bk), _pad2(a_lo, bm, bk)
    with spans.scope("ozaki.split_b"):
        b_hi, b_lo = _pad2(b_hi, bk, bn), _pad2(b_lo, bk, bn)

    rep = common.kernel_rep(out_rep)
    raw = _gemm.gemm_hilo(a_hi, a_lo, b_hi, b_lo, plan, out_rep=rep,
                          bm=bm, bn=bn, bk=bk, interpret=interpret)
    with spans.scope("ozaki.finish"):
        c = common.finish(raw, plan, rep, f64)[..., :M, :N]
        return splitting.apply_unscale(c, sa, sb)


def ozaki_gemv(a: jax.Array, x: jax.Array, plan: Optional[ozaki2.Plan] = None,
               out_rep: str = "f64", bm: int = 128, bk: int = 256,
               interpret: Optional[bool] = None) -> jax.Array:
    """Batched GEMV Y = A @ X (paper Alg. 1): A (M,N) fp64, X (N,B) with small B."""
    M, N = a.shape
    _, B = x.shape
    if plan is None:
        plan = dispatch.get_plan(N)
    if interpret is None:
        interpret = dispatch.pallas_interpret("gemv")
    bm, bk = min(bm, M), min(bk, N)
    f64 = _working_f64()

    # Scopes around the statements in their trace order (``repro.obs.spans``).
    with spans.scope("ozaki.split_a"):
        ai, sa = splitting.scale_to_int(a.astype(f64), plan.payload_bits, axis=-1)
    with spans.scope("ozaki.split_b"):
        xi, sx = splitting.scale_to_int(x.astype(f64), plan.payload_bits, axis=0)
    with spans.scope("ozaki.split_a"):
        a_hi, a_lo = splitting.split_hi_lo(ai)
    with spans.scope("ozaki.split_b"):
        x_hi, x_lo = splitting.split_hi_lo(xi)
    with spans.scope("ozaki.split_a"):
        a_hi, a_lo = _pad2(a_hi, bm, bk), _pad2(a_lo, bm, bk)
    with spans.scope("ozaki.split_b"):
        x_hi, x_lo = _pad2(x_hi, bk, B), _pad2(x_lo, bk, B)

    rep = common.kernel_rep(out_rep)
    raw = _gemv.gemv_hilo(a_hi, a_lo, x_hi, x_lo, plan, out_rep=rep,
                          bm=bm, bk=bk, interpret=interpret)
    with spans.scope("ozaki.finish"):
        y = common.finish(raw, plan, rep, f64)[..., :M, :B]
        return splitting.apply_unscale(y, sa, sx)


def ozaki_stencil7(u: jax.Array, c: jax.Array,
                   plan: Optional[ozaki2.Plan] = None, out_rep: str = "f64",
                   bx: int = 1, mode: Optional[str] = None) -> jax.Array:
    """7-point 3-D stencil (paper Alg. 2) at FP64 accuracy, dispatch-routed.

    u: (X, Y, Z) grid, c: (7,) coefficients ordered
    [centre, -x, +x, -y, +y, -z, +z].  Boundary points use zero halo.
    ``mode`` (or the ambient ``mode_scope`` / ``REPRO_DISPATCH``) selects the
    fused Pallas kernel or the bit-identical jnp reference.
    """
    return dispatch.stencil7(u, c, plan=plan, out_rep=out_rep, bx=bx, mode=mode)


def ozaki_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    mask: Optional[jax.Array] = None, softcap: float = 0.0,
                    plan_qk: Optional[ozaki2.Plan] = None,
                    plan_pv: Optional[ozaki2.Plan] = None,
                    mode: Optional[str] = None) -> jax.Array:
    """Fused emulated attention softmax(mask(QKᵀ/√D)) V, dispatch-routed.

    q: (..., S, D), k/v: (..., T, D), mask: None | (S, T) | (..., S, T)
    (nonzero = attend).  ``mode`` selects the FlashAttention-style fused
    Pallas kernel (QKᵀ and PV ride the Ozaki-II residue pipeline inside one
    online-softmax scan) or the bit-identical reference composed from the
    seam GEMMs, like every dispatch-seam multiplication.
    """
    return dispatch.attention(q, k, v, mask=mask, softcap=softcap,
                              plan_qk=plan_qk, plan_pv=plan_pv, mode=mode)


def ozaki_spmv_bell(a_val: jax.Array, a_col: jax.Array, x: jax.Array,
                    plan: Optional[ozaki2.Plan] = None, out_rep: str = "f64",
                    br: int = 128, mode: Optional[str] = None) -> jax.Array:
    """Blocked-ELL SpMV y = A x (paper Alg. 3), dispatch-routed.

    a_val: (M, bw) padded per-row nonzero values; a_col: (M, bw) int32 column
    indices (structural-zero slots must point at a valid column, value 0.0).

    ``mode`` selects the route like every dispatch-seam multiplication.  On
    CPU backends ``auto`` takes the bit-identical jnp reference: the
    interpreted ``pallas_call`` hands XLA a gather-heavy graph with a
    multi-minute compile — a correctness oracle (``mode="pallas"``, used by
    the slow-lane parity test), not a path anyone should pay by default.  On
    TPU ``auto`` is the fused Mosaic kernel.
    """
    return dispatch.spmv(a_val, a_col, x, plan=plan, out_rep=out_rep, br=br,
                         mode=mode)
