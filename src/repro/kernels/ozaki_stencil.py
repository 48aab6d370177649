"""Fused Ozaki-II 7-point stencil Pallas kernel (paper §5.3, Algorithm 2).

im2col-in-registers mapping: per z-slab, the 7-point neighbourhood of every output
is assembled in VMEM, residue-decomposed, and contracted against the pre-decomposed
coefficient residues (the paper's constant-memory table — here a tiny (r, 7) int8
operand) with a 1×7×N_tile int8 MXU contraction per modulus.

Halo handling without β inflation: the z-axis is blocked and each program receives
the *previous*, *current* and *next* slabs of the same array through three
BlockSpecs with clamped index maps — the TPU equivalent of a halo'd shared-memory
tile (re-reads hit the same HBM pages the neighbouring programs stream anyway; the
paper's §5.3 traffic model already counts them as cached).  Global-boundary planes
are masked to the zero halo inside the kernel.

HBM traffic per output: 8 B in (hi+lo int32) + r B of int8 digits out (8 B for
out_rep="ds"), see common.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import backend, ozaki2, splitting
from repro.kernels import common
from repro.obs import spans

# Scoped VMEM for one x-slab: the r residue accumulators and Garner carries
# of a 256 x 256 plane take ~20 MiB, past Mosaic's 16 MiB default (a v5e core
# has 128 MiB of VMEM).
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _roll_mask(arr: jax.Array, ax: int, d: int) -> jax.Array:
    """Shift by one along ``ax`` with a zero fill at the exposed boundary."""
    rolled = jnp.roll(arr, d, axis=ax)
    idx = [slice(None)] * 3
    idx[ax] = 0 if d == 1 else -1
    return rolled.at[tuple(idx)].set(0)


def _shift_in_tile(arr: jax.Array, ax: int, d: int) -> jax.Array:
    """``_roll_mask`` inside a kernel: a lane/sublane rotate (``pltpu.roll``,
    whose shift must be non-negative) with the wrapped row masked to zero."""
    n = arr.shape[ax]
    rolled = pltpu.roll(arr, np.int32(d % n), ax)
    edge = np.int32(0 if d == 1 else n - 1)
    pos = jax.lax.broadcasted_iota(jnp.int32, arr.shape, ax)
    return jnp.where(pos == edge, jnp.zeros_like(arr), rolled)


def _stencil_kernel(c_res_ref, u_hi_p, u_lo_p, u_hi_c, u_lo_c, u_hi_n, u_lo_n,
                    out_ref, *, plan: ozaki2.Plan, out_rep: str, x_steps: int):
    xidx = pl.program_id(0)
    bx = u_hi_c.shape[0]
    # Halo planes beyond the global x boundary are the zero halo.
    first = xidx == np.int32(0)
    last = xidx == np.int32(x_steps - 1)

    # The residue of a neighbour is the neighbour of the residue: decompose
    # each point once per modulus, then shift residue planes.
    accs = []
    for i, m in enumerate(plan.moduli):
        (cur,) = splitting.residues_int32(u_hi_c[...], u_lo_c[...], (m,))
        (prev,) = splitting.residues_int32(u_hi_p[...], u_lo_p[...], (m,))
        (nxt,) = splitting.residues_int32(u_hi_n[...], u_lo_n[...], (m,))
        prev = jnp.where(first, jnp.zeros_like(prev), prev)
        nxt = jnp.where(last, jnp.zeros_like(nxt), nxt)
        if bx == 1:
            xm, xp = prev, nxt
        else:
            xm = jnp.concatenate([prev, cur[:-1]], axis=0)
            xp = jnp.concatenate([cur[1:], nxt], axis=0)
        # [centre, -x, +x, -y, +y, -z, +z]; |sum| <= 7 * 128 * 128, exact.
        nbs = (cur, xm, xp,
               _shift_in_tile(cur, 1, 1), _shift_in_tile(cur, 1, -1),
               _shift_in_tile(cur, 2, 1), _shift_in_tile(cur, 2, -1))
        acc = nbs[0] * c_res_ref[i, 0]
        for t in range(1, 7):
            acc = acc + nbs[t] * c_res_ref[i, t]
        accs.append(splitting.balanced_mod(acc, m))

    digits = ozaki2.garner_digits(accs, plan)
    if out_rep == "ds":
        hi, lo = ozaki2.digits_to_ds(digits, plan)
        out_ref[0] = hi
        out_ref[1] = lo
    else:
        out_ref[...] = common.stack_digits_int8(digits)


@functools.partial(jax.jit, static_argnames=("plan", "out_rep", "bx", "interpret"))
def stencil7(u: jax.Array, c: jax.Array, plan: ozaki2.Plan,
             out_rep: str = "f64", bx: int = 1, interpret: bool = True) -> jax.Array:
    X, Y, Z = u.shape
    f64 = backend.working_float()
    bx = min(bx, X)
    px = (-X) % bx
    # Scopes around the statements in their trace order (``repro.obs.spans``).
    with spans.scope("ozaki.split_b"):
        ui, su = splitting.global_scale_to_int(u.astype(f64), plan.payload_bits)
    with spans.scope("ozaki.split_a"):
        ci, sc = splitting.global_scale_to_int(c.astype(f64), plan.payload_bits)
    with spans.scope("ozaki.split_b"):
        u_hi, u_lo = splitting.split_hi_lo(ui)
        if px:
            # Zero planes past X are the zero halo the last real plane sees.
            u_hi = jnp.pad(u_hi, ((0, px), (0, 0), (0, 0)))
            u_lo = jnp.pad(u_lo, ((0, px), (0, 0), (0, 0)))
    with spans.scope("ozaki.split_a"):
        c_hi, c_lo = splitting.split_hi_lo(ci)
        c_res = jnp.stack(splitting.residues_int32(c_hi, c_lo, plan.moduli))

    Xp = X + px
    x_steps = Xp // bx
    zero = common.ZERO
    # x-blocked slabs of whole (Y, Z) planes (z on the lanes); the x halo is
    # one plane on each side, clamped at the ends and masked in the kernel.
    cur = pl.BlockSpec((bx, Y, Z), lambda k: (k, zero, zero))
    prev = pl.BlockSpec((1, Y, Z),
                        lambda k: (jnp.maximum(k * bx - 1, 0), zero, zero))
    nxt = pl.BlockSpec((1, Y, Z),
                       lambda k: (jnp.minimum((k + 1) * bx, Xp - 1), zero, zero))
    in_specs = [pl.BlockSpec((plan.r, 7), lambda k: (zero, zero),
                             memory_space=pltpu.SMEM),
                prev, prev, cur, cur, nxt, nxt]

    rep = common.kernel_rep(out_rep)
    if rep == "ds":
        out_shape = jax.ShapeDtypeStruct((2, Xp, Y, Z), jnp.float32)
        out_spec = pl.BlockSpec((2, bx, Y, Z), lambda k: (zero, k, zero, zero))
    else:
        out_shape = jax.ShapeDtypeStruct((plan.r, Xp, Y, Z), jnp.int8)
        out_spec = pl.BlockSpec((plan.r, bx, Y, Z),
                                lambda k: (zero, k, zero, zero))

    kernel = functools.partial(_stencil_kernel, plan=plan, out_rep=rep,
                               x_steps=x_steps)
    raw = pl.pallas_call(
        kernel,
        grid=(x_steps,),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(c_res, u_hi, u_lo, u_hi, u_lo, u_hi, u_lo)

    with spans.scope("ozaki.finish"):
        v = common.finish(raw, plan, rep, f64)[..., :X, :, :]
        return splitting.ldexp(v, -(su + sc))


@functools.partial(jax.jit, static_argnames=("plan", "out_rep"))
def stencil7_ref(u: jax.Array, c: jax.Array, plan: ozaki2.Plan,
                 out_rep: str = "f64") -> jax.Array:
    """Unfused jnp reference of the fused stencil kernel, bit-identical.

    Same Phase-1 global scaling, hi/lo split, residues, zero-halo
    neighbourhood, per-modulus 7-term contraction, Garner digits, and
    reconstruction epilogue as ``stencil7`` — every integer step is exact
    and point-local, so the result matches the Pallas path bit-for-bit
    regardless of x-blocking.  This is the ``xla``
    route of ``repro.core.dispatch.stencil7``.
    """
    f64 = backend.working_float()
    ui, su = splitting.global_scale_to_int(u.astype(f64), plan.payload_bits)
    ci, sc = splitting.global_scale_to_int(c.astype(f64), plan.payload_bits)
    u_hi, u_lo = splitting.split_hi_lo(ui)
    c_hi, c_lo = splitting.split_hi_lo(ci)
    c_res = splitting.residues_int32(c_hi, c_lo, plan.moduli)
    u_res = splitting.residues_int32(u_hi, u_lo, plan.moduli)

    accs = []
    for i, m in enumerate(plan.moduli):
        # The kernel's order: residues once per point, then the zero-halo
        # neighbours of the residue grid.  |sum| <= 7 * 128 * 128, exact.
        res = u_res[i]
        nbs = (res, _roll_mask(res, 0, 1), _roll_mask(res, 0, -1),
               _roll_mask(res, 1, 1), _roll_mask(res, 1, -1),
               _roll_mask(res, 2, 1), _roll_mask(res, 2, -1))
        acc = nbs[0] * c_res[i][0]
        for t in range(1, 7):
            acc = acc + nbs[t] * c_res[i][t]
        accs.append(splitting.balanced_mod(acc, m))

    # Barriers around the Garner step, as in ``ozaki2.garner_reconstruct``.
    accs = jax.lax.optimization_barrier(accs)
    digits = jax.lax.optimization_barrier(ozaki2.garner_digits(accs, plan))
    if out_rep in ("f64", "digits"):
        v = ozaki2.digits_to_f64(digits, plan, out_dtype=f64)
    elif out_rep == "ds":
        hi, lo = ozaki2.digits_to_ds(digits, plan)
        v = hi.astype(f64) + lo.astype(f64)
    else:
        raise ValueError(f"out_rep must be one of {common.OUT_REPS}")
    return splitting.ldexp(v, -(su + sc))
