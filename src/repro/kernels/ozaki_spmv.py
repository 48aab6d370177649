"""Fused Ozaki-II Blocked-ELL SpMV Pallas kernel (paper §5.4, Algorithm 3).

y = A·x with A in Blocked-ELL layout: ``a_val (M, bw)`` padded nonzero values and
``a_col (M, bw)`` gather indices.  Each program handles a block of ``br`` rows:
stream the value block and the gathered x block, residue-decompose both in VMEM,
contract the bw-length products per modulus, Garner, store.

TPU adaptation notes:
  * the gather x[a_col] runs in XLA ahead of the kernel, as (hi, lo) int32 pairs:
    Mosaic lowers no general vector gather.  That costs 8 B per stored slot
    on top of Algorithm 3's VMEM-resident x.  A banded operator (each slot one
    diagonal, ``spmv_formats.band_offsets``) takes static shifted slices of x
    instead, with no gather;
  * blocks are laid out (bw, br), rows on the 128 lanes, so a block is
    lane-dense for any band width;
  * β otherwise inherits the ELL padding ratio ρ_pad exactly as Appendix D
    derives (residues never touch HBM).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import ozaki2, splitting
from repro.kernels import common
from repro.kernels.ozaki_stencil import _global_scale_to_int
from repro.obs import spans


def _spmv_kernel(av_hi_ref, av_lo_ref, xg_hi_ref, xg_lo_ref, out_ref, *,
                 plan: ozaki2.Plan, out_rep: str):
    # (bw, br) blocks: the row's slots on sublanes, rows on the lanes.
    a_res = common.residues_int32(av_hi_ref[...], av_lo_ref[...], plan.moduli)
    x_res = common.residues_int32(xg_hi_ref[...], xg_lo_ref[...], plan.moduli)

    accs = []
    for i, m in enumerate(plan.moduli):
        prod = a_res[i] * x_res[i]           # (bw, br) int32, |.| <= 128*128
        accs.append(common.balanced_mod(
            jnp.sum(prod, axis=0, keepdims=True, dtype=jnp.int32), m))

    digits = common.garner_digits(accs, plan)   # (1, br) each
    if out_rep == "ds":
        hi, lo = common.digits_to_ds(digits, plan)
        out_ref[0] = hi
        out_ref[1] = lo
    else:
        out_ref[...] = common.stack_digits_int8(digits)


def _decompose_operands(a_val: jax.Array, a_col: jax.Array, x: jax.Array,
                        plan: ozaki2.Plan):
    """Shared prologue of the fused kernel and the jnp reference: Phase-1
    scaling, hi/lo split, column cast.  One implementation keeps the two
    paths' bit-identity structural rather than a testing promise."""
    f64 = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    # Scopes around the statements in their trace order (``repro.obs.spans``).
    with spans.scope("ozaki.split_a"):
        av, sa = splitting.scale_to_int(a_val.astype(f64), plan.payload_bits,
                                        axis=-1)
    with spans.scope("ozaki.split_b"):
        xi, sx = _global_scale_to_int(x.astype(f64), plan.payload_bits)
    with spans.scope("ozaki.split_a"):
        av_hi, av_lo = splitting.split_hi_lo(av)
    with spans.scope("ozaki.split_b"):
        x_hi, x_lo = splitting.split_hi_lo(xi)
    return av_hi, av_lo, a_col.astype(jnp.int32), x_hi, x_lo, sa, sx


@functools.partial(jax.jit, static_argnames=("plan",))
def _spmv_ref_digits(a_val: jax.Array, a_col: jax.Array, x: jax.Array,
                     plan: ozaki2.Plan):
    """Reference front half: scaling, residues, contraction, Garner digits."""
    av_hi, av_lo, cols, x_hi, x_lo, sa, sx = _decompose_operands(
        a_val, a_col, x, plan)

    a_res = common.residues_int32(av_hi, av_lo, plan.moduli)
    x_res = common.residues_int32(x_hi[cols], x_lo[cols], plan.moduli)
    accs = [common.balanced_mod(
                jnp.sum(a_res[i] * x_res[i], axis=-1, dtype=jnp.int32), m)
            for i, m in enumerate(plan.moduli)]
    return common.garner_digits(accs, plan), sa, sx


@functools.partial(jax.jit, static_argnames=("plan", "out_rep"))
def _spmv_ref_epilogue(digits, sa: jax.Array, sx: jax.Array,
                       plan: ozaki2.Plan, out_rep: str) -> jax.Array:
    """Reference back half: digit reconstruction + exact unscale."""
    f64 = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    if out_rep in ("f64", "digits"):
        y = common.digits_to_f64(digits, plan, out_dtype=f64)
    elif out_rep == "ds":
        hi, lo = common.digits_to_ds(digits, plan)
        y = hi.astype(f64) + lo.astype(f64)
    else:
        raise ValueError(f"out_rep must be one of {common.OUT_REPS}")
    return splitting.ldexp(y, -(sa + sx))


def spmv_bell_ref(a_val: jax.Array, a_col: jax.Array, x: jax.Array,
                  plan: ozaki2.Plan, out_rep: str = "f64") -> jax.Array:
    """Unfused jnp reference of the fused kernel's arithmetic, bit-identical.

    Same scaling, hi/lo split, residues, per-modulus contraction and Garner
    digits as ``_spmv_kernel`` — every integer step is exact and row-local, so
    the result matches the Pallas path bit-for-bit regardless of row blocking.
    This is the CPU fast path for tests and solvers: interpret-mode
    ``pl.pallas_call`` hands XLA a gather-heavy graph that costs minutes to
    compile (ROADMAP open item).

    Deliberately jitted as two stages split at the integer digit boundary: the
    combined residue graph + double-double reconstruction triggers a
    pathological XLA-CPU optimisation pass (minutes for r = 15), while the
    halves each compile in ~1 s.  The digits crossing the boundary are exact
    int32, so the split cannot change a single bit of the result.
    """
    digits, sa, sx = _spmv_ref_digits(a_val, a_col, x, plan)
    return _spmv_ref_epilogue(tuple(digits), sa, sx, plan, out_rep)


def _band_slices(x: jax.Array, offsets: Tuple[int, ...], rows: int) -> jax.Array:
    """(bw, rows): row k is x[r + offsets[k]] for r < rows, 0 outside x —
    the gather of a banded operator's x, as static slices of a padded x."""
    lo = max(0, -min(offsets))
    xp = jnp.pad(x, (lo, max(0, rows + max(offsets) - x.shape[0])))
    return jnp.stack([xp[lo + o:lo + o + rows] for o in offsets])


@functools.partial(jax.jit, static_argnames=("plan", "out_rep", "br", "interpret",
                                             "offsets"))
def spmv_bell(a_val: jax.Array, a_col: jax.Array, x: jax.Array,
              plan: ozaki2.Plan, out_rep: str = "f64", br: int = 128,
              interpret: bool = True,
              offsets: Optional[Tuple[int, ...]] = None) -> jax.Array:
    """``offsets`` (``spmv_formats.band_offsets``) marks a banded operator:
    slot k reads x at row + offsets[k] wherever its value is nonzero.  x is
    then laid out by static shifts and ``a_col`` is not read; where a value
    is 0 its A residues are 0, so the result is bit-identical to the gather."""
    M, bw = a_val.shape
    f64 = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    br = min(br, M)
    pm = (-M) % br
    Mp = M + pm
    if offsets is not None and len(offsets) != bw:
        raise ValueError(f"{len(offsets)} offsets for {bw} slots")

    av_hi, av_lo, col, x_hi, x_lo, sa, sx = _decompose_operands(
        a_val, a_col, x, plan)
    # The gather runs in XLA: Mosaic has no general vector gather.  Rows go
    # to the lanes, so a (bw, br) block is lane-dense for any band width.
    # Scopes around the statements in their trace order (``repro.obs.spans``).
    if offsets is None:
        with spans.scope("spmv.gather"):
            xg_hi, xg_lo = x_hi[col], x_lo[col]
    with spans.scope("ozaki.split_a"):
        av_hi, av_lo = [jnp.pad(o, ((0, pm), (0, 0))).T for o in (av_hi, av_lo)]
    with spans.scope("spmv.gather"):
        if offsets is None:
            xg_hi, xg_lo = [jnp.pad(o, ((0, pm), (0, 0))).T for o in (xg_hi, xg_lo)]
        else:
            xg_hi, xg_lo = [_band_slices(o, offsets, Mp) for o in (x_hi, x_lo)]
    zero = common.ZERO
    blk = pl.BlockSpec((bw, br), lambda i: (zero, i))

    rep = common.kernel_rep(out_rep)
    if rep == "ds":
        out_shape = jax.ShapeDtypeStruct((2, 1, Mp), jnp.float32)
        out_spec = pl.BlockSpec((2, 1, br), lambda i: (zero, zero, i))
    else:
        out_shape = jax.ShapeDtypeStruct((plan.r, 1, Mp), jnp.int8)
        out_spec = pl.BlockSpec((plan.r, 1, br), lambda i: (zero, zero, i))

    kernel = functools.partial(_spmv_kernel, plan=plan, out_rep=rep)
    raw = pl.pallas_call(
        kernel,
        grid=(Mp // br,),
        in_specs=[blk] * 4,
        out_specs=out_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(av_hi, av_lo, xg_hi, xg_lo)

    with spans.scope("ozaki.finish"):
        y = common.finish(raw, plan, rep, f64)[0, :M]
        return splitting.ldexp(y, -(sa + sx))
