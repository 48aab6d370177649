"""Fused Ozaki-II Blocked-ELL SpMV Pallas kernel (paper §5.4, Algorithm 3).

y = A·x with A in Blocked-ELL layout: ``a_val (M, bw)`` padded nonzero values and
``a_col (M, bw)`` gather indices.  Each program handles a block of ``br`` rows:
stream the value block and the gathered x block (or gather it in VMEM),
residue-decompose both in VMEM, contract the bw-length products per modulus,
Garner, store.

TPU adaptation notes:
  * x reaches the kernel as (hi, lo) int32 halves in one of three ways.  A
    banded operator (each slot one diagonal, ``spmv_formats.band_offsets``)
    takes static shifted slices of x, with no gather.  A general operator
    whose x is short enough (``x_in_vmem``) keeps x's halves resident in VMEM,
    Algorithm 3's layout, and the kernel gathers them to its block with
    Mosaic's lane gather, chunk by chunk of ``br`` entries; XLA then only
    transposes ``a_col`` and reshapes x.  Longer x is gathered in XLA ahead
    of the kernel, 8 B per stored slot, since the in-kernel cost per slot
    grows with x's length;
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
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import backend, ozaki2, splitting
from repro.kernels import common
from repro.obs import spans

_SUBLANES = 8
# Most chunks of x a gathering tile takes per trip of its loop.  A trip costs
# about 80 ns besides its chunks (TPU v5e, NPB class B's (75000, 404) shape:
# 1580, 271, 119 ms an SpMV at 1, 8, 64 chunks a trip; 97, 95, 92 ms at
# 196, 293, 586).  Each process traces and lowers the trip with every chunk
# written out, compile cache or not (at 586, class B's set-up took 10 s
# more), so the last 5% is left for a short set-up.
_GATHER_UNROLL = 196
# Longest x (entries) that a general operator's kernel holds in VMEM and
# gathers from; longer ones are gathered in XLA.  TPU v5e, a (75000, 404)
# operator, 586 chunks a trip: the SpMV takes 92, 290 and 553 ms at
# n = 75,000, 300,000 and 600,000 with the kernel's gather, 483 ms at each
# with XLA's, so they cross near n = 520,000; 196 chunks a trip cost about
# 7% more a chunk, which moves that to about 485,000.
X_VMEM_MAX = 450_000


def x_in_vmem(n: int, bw: int, br: int) -> bool:
    """Whether the fused kernel gathers a general operator's x (length n)
    itself, from halves resident in VMEM, for bw slots a row in blocks of br
    rows.  Its cost per stored slot grows with x's chunks of br entries and
    with bw's padding to whole 8-slot tiles; XLA's gather's does not."""
    padded = -(-bw // _SUBLANES) * _SUBLANES * (-(-n // br) * br)
    return padded <= bw * X_VMEM_MAX


def _lane_gather(src: jax.Array, idx: jax.Array) -> jax.Array:
    """src[i, idx[i, j, 0]] for 2-D src and (rows, lanes, 1) int32 idx, which
    Mosaic lowers to its lane gather (``jnp.take_along_axis`` would widen idx
    to int64 under x64)."""
    dims = lax.GatherDimensionNumbers(
        offset_dims=(), collapsed_slice_dims=(1,), start_index_map=(1,),
        operand_batching_dims=(0,), start_indices_batching_dims=(0,))
    return lax.gather(src, idx, dims, slice_sizes=(1, 1),
                      mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def _loop(n: int, body, init):
    """``lax.fori_loop(0, n, body, init)`` with an int32 index: under x64
    fori_loop's index is int64, which Mosaic does not lower."""
    def step(carry, _):
        i, x = carry
        return (i + np.int32(1), body(i, x)), None
    return lax.scan(step, (np.int32(0), init), None, length=n)[0][1]


def _gather_x(col_ref, x_hi_ref, x_lo_ref, xg_hi_ref, xg_lo_ref, *,
              unroll: int):
    """xg_hi/xg_lo[k, j] = x_hi/x_lo[col[k, j]] for a (bw, br) block of
    columns, from x's halves resident in VMEM as (chunks, br) rows.

    Each (8, br) tile of the block keeps its two carries in registers while
    every chunk c is broadcast to the tile, lane-gathered at col % br and
    kept where col // br == c.  A trip of the loop takes ``unroll`` chunks
    (x is padded to a whole number of trips).  The unrolled body is traced
    and lowered in every process, cache hit or not, so it is kept to plain
    lax primitives."""
    bw, br = col_ref.shape
    tile_shape = (_SUBLANES, br)

    def tile(t, carry):
        rows = pl.ds(pl.multiple_of(t * _SUBLANES, _SUBLANES), _SUBLANES)
        col = col_ref[rows, :]
        chunk = lax.div(col, np.int32(br))
        lane = lax.broadcast_in_dim(lax.rem(col, np.int32(br)),
                                    tile_shape + (1,), (0, 1))

        def trip(u, acc):
            first = lax.mul(u, np.int32(unroll))
            for k in range(unroll):
                c = lax.add(first, np.int32(k))
                hit = lax.eq(chunk, lax.broadcast_in_dim(c, tile_shape, ()))
                acc = tuple(
                    lax.select(hit, _lane_gather(lax.broadcast_in_dim(
                        ref[pl.ds(c, 1), :], tile_shape, (0, 1)), lane), a)
                    for ref, a in zip((x_hi_ref, x_lo_ref), acc))
            return acc

        zero = jnp.zeros(tile_shape, jnp.int32)
        hi, lo = _loop(x_hi_ref.shape[0] // unroll, trip, (zero, zero))
        xg_hi_ref[rows, :] = hi
        xg_lo_ref[rows, :] = lo
        return carry

    _loop(bw // _SUBLANES, tile, np.int32(0))


def _spmv_kernel(*refs, plan: ozaki2.Plan, out_rep: str, unroll: int):
    # (bw, br) blocks: the row's slots on sublanes, rows on the lanes.
    if unroll:
        av_hi_ref, av_lo_ref, col_ref, x_hi_ref, x_lo_ref, out_ref, \
            xg_hi_ref, xg_lo_ref = refs
        _gather_x(col_ref, x_hi_ref, x_lo_ref, xg_hi_ref, xg_lo_ref,
                  unroll=unroll)
    else:
        av_hi_ref, av_lo_ref, xg_hi_ref, xg_lo_ref, out_ref = refs
    a_res = splitting.residues_int32(av_hi_ref[...], av_lo_ref[...], plan.moduli)
    x_res = splitting.residues_int32(xg_hi_ref[...], xg_lo_ref[...], plan.moduli)

    accs = []
    for i, m in enumerate(plan.moduli):
        prod = a_res[i] * x_res[i]           # (bw, br) int32, |.| <= 128*128
        accs.append(splitting.balanced_mod(
            jnp.sum(prod, axis=0, keepdims=True, dtype=jnp.int32), m))

    digits = ozaki2.garner_digits(accs, plan)   # (1, br) each
    if out_rep == "ds":
        hi, lo = ozaki2.digits_to_ds(digits, plan)
        out_ref[0] = hi
        out_ref[1] = lo
    else:
        out_ref[...] = common.stack_digits_int8(digits)


def _decompose_operands(a_val: jax.Array, a_col: jax.Array, x: jax.Array,
                        plan: ozaki2.Plan):
    """Shared prologue of the fused kernel and the jnp reference: Phase-1
    scaling, hi/lo split, column cast.  One implementation keeps the two
    paths' bit-identity structural rather than a testing promise."""
    f64 = backend.working_float()
    # Scopes around the statements in their trace order (``repro.obs.spans``).
    with spans.scope("ozaki.split_a"):
        av, sa = splitting.scale_to_int(a_val.astype(f64), plan.payload_bits,
                                        axis=-1)
    with spans.scope("ozaki.split_b"):
        xi, sx = splitting.global_scale_to_int(x.astype(f64), plan.payload_bits)
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

    a_res = splitting.residues_int32(av_hi, av_lo, plan.moduli)
    x_res = splitting.residues_int32(x_hi[cols], x_lo[cols], plan.moduli)
    accs = [splitting.balanced_mod(
                jnp.sum(a_res[i] * x_res[i], axis=-1, dtype=jnp.int32), m)
            for i, m in enumerate(plan.moduli)]
    return ozaki2.garner_digits(accs, plan), sa, sx


@functools.partial(jax.jit, static_argnames=("plan", "out_rep"))
def _spmv_ref_epilogue(digits, sa: jax.Array, sx: jax.Array,
                       plan: ozaki2.Plan, out_rep: str) -> jax.Array:
    """Reference back half: digit reconstruction + exact unscale."""
    f64 = backend.working_float()
    if out_rep in ("f64", "digits"):
        y = ozaki2.digits_to_f64(digits, plan, out_dtype=f64)
    elif out_rep == "ds":
        hi, lo = ozaki2.digits_to_ds(digits, plan)
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
    is 0 its A residues are 0, so the result is bit-identical to the gather.
    Without ``offsets`` the kernel gathers x itself where ``x_in_vmem``
    holds, and XLA gathers it otherwise; both read the same halves."""
    M, bw = a_val.shape
    f64 = backend.working_float()
    in_vmem = offsets is None and x_in_vmem(x.shape[0], bw, br)
    br = min(br, M)
    pm = (-M) % br
    Mp = M + pm
    if offsets is not None and len(offsets) != bw:
        raise ValueError(f"{len(offsets)} offsets for {bw} slots")

    av_hi, av_lo, col, x_hi, x_lo, sa, sx = _decompose_operands(
        a_val, a_col, x, plan)
    # Rows go to the lanes, so a (bw, br) block is lane-dense for any band
    # width.  x reaches the kernel by static shifts (banded), as halves that
    # stay in VMEM for the kernel to gather from, or gathered in XLA.
    # Scopes around the statements in their trace order (``repro.obs.spans``).
    if offsets is None and not in_vmem:
        with spans.scope("spmv.gather"):
            xg_hi, xg_lo = x_hi[col], x_lo[col]
    bwp = -(-bw // _SUBLANES) * _SUBLANES if in_vmem else bw
    with spans.scope("ozaki.split_a"):
        av_hi, av_lo = [jnp.pad(o, ((0, pm), (0, bwp - bw))).T
                        for o in (av_hi, av_lo)]
    unroll = 0                  # chunks of x a trip of the kernel's gather
    with spans.scope("spmv.gather"):
        if in_vmem:
            col = jnp.pad(col, ((0, pm), (0, bwp - bw))).T
            chunks = -(-x.shape[0] // br)
            trips = -(-chunks // _GATHER_UNROLL)
            unroll = -(-chunks // trips)
            xv_hi, xv_lo = [
                jnp.pad(o, (0, trips * unroll * br - o.shape[0])).reshape(-1, br)
                for o in (x_hi, x_lo)]
        elif offsets is None:
            xg_hi, xg_lo = [jnp.pad(o, ((0, pm), (0, 0))).T for o in (xg_hi, xg_lo)]
        else:
            xg_hi, xg_lo = [_band_slices(o, offsets, Mp) for o in (x_hi, x_lo)]
    zero = common.ZERO
    blk = pl.BlockSpec((bwp, br), lambda i: (zero, i))
    if in_vmem:
        # One block for the whole grid, fetched once and never rotated.
        resident = pl.BlockSpec(xv_hi.shape, lambda i: (zero, zero),
                                pipeline_mode=pl.Buffered(1))
        operands = (av_hi, av_lo, col, xv_hi, xv_lo)
        in_specs = [blk] * 3 + [resident] * 2
        scratch = [pltpu.VMEM((bwp, br), jnp.int32)] * 2
    else:
        operands = (av_hi, av_lo, xg_hi, xg_lo)
        in_specs = [blk] * 4
        scratch = []

    rep = common.kernel_rep(out_rep)
    if rep == "ds":
        out_shape = jax.ShapeDtypeStruct((2, 1, Mp), jnp.float32)
        out_spec = pl.BlockSpec((2, 1, br), lambda i: (zero, zero, i))
    else:
        out_shape = jax.ShapeDtypeStruct((plan.r, 1, Mp), jnp.int8)
        out_spec = pl.BlockSpec((plan.r, 1, br), lambda i: (zero, zero, i))

    kernel = functools.partial(_spmv_kernel, plan=plan, out_rep=rep,
                               unroll=unroll)
    raw = pl.pallas_call(
        kernel,
        grid=(Mp // br,),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*operands)

    with spans.scope("ozaki.finish"):
        y = common.finish(raw, plan, rep, f64)[0, :M]
        return splitting.ldexp(y, -(sa + sx))
