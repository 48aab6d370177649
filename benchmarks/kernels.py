"""Kernel benchmarks: wall-clock on CPU-interpret (machinery check) plus the
*structural* β accounting that the paper's §5/Appendix D analysis is about.

derived column:
  wallclock rows — CPU interpret μs (not TPU perf; the TPU roofline comes from
                   the compiled dry-run, ``repro.launch.roofline``);
  beta rows      — HBM bytes of the emulated kernel / bytes of the native-FP64
                   kernel, computed from the actual operand/result shapes.  The
                   paper's claim is β = 1 for f64/ds output and (8+r)/16-ish for
                   digits mode; this prints the exact numbers.
  route rows     — xla vs pallas through the dispatch entry points
                   (``dispatch.spmv`` / ``dispatch.stencil7`` with
                   ``mode=``); derived = max |pallas - xla|, expected exactly 0
                   (the routes are bit-identical).

Every row pins its dispatch mode so the perf trajectory measures the same code
path in both legs of the CI ``REPRO_DISPATCH`` matrix.  The SpMV pallas-route
row uses a 24-bit-payload plan (r = 7): the interpreted gather graph with the
default r = 15 plan costs *minutes* of XLA-CPU compile (ROADMAP), which is a
parity-oracle price the benchmark lane must not pay.
"""

from __future__ import annotations

import time
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dispatch, ozaki2
from repro.kernels import ozaki_gemm, ozaki_gemv
from repro.obs import telemetry as obs

Row = Tuple[str, float, float]


def _provenance(fn) -> Tuple[str, str]:
    """(route, shape_class) of fn's dispatch call, via a telemetry probe —
    one extra untimed call so the BENCH route rows are self-describing."""
    _, ev = obs.probe(fn)
    return (ev.route, ev.shape_class) if ev is not None else ("", "")


def _timed(fn, *args, reps=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def _beta(in_native: int, out_native: int, in_emu: int, out_emu: int) -> float:
    return (in_emu + out_emu) / (in_native + out_native)


def all_kernels() -> List[Row]:
    rows: List[Row] = []
    rng = np.random.default_rng(0)

    # --- GEMM ---------------------------------------------------------------
    m = k = n = 128
    a = jnp.asarray(rng.standard_normal((m, k)))
    b = jnp.asarray(rng.standard_normal((k, n)))
    plan = ozaki2.make_plan(k)
    for rep in ("f64", "digits", "ds"):
        us = _timed(lambda rep=rep: ozaki_gemm.gemm(
            a, b, plan, out_rep=rep, bm=64, bn=64, bk=64,
            interpret=dispatch.pallas_interpret("gemm")))
        out_bytes = {"f64": 8, "ds": 8, "digits": plan.r}[rep] * m * n
        beta = _beta((m * k + k * n) * 8, m * n * 8,
                     (m * k + k * n) * 8, out_bytes)
        rows.append((f"kernel_gemm/{rep}/beta", us, beta))

    # --- batched GEMV (B = 8 and 2: the Table 3/4 rows) ----------------------
    M, N = 512, 256
    A = jnp.asarray(rng.standard_normal((M, N)))
    for B in (8, 2):
        X = jnp.asarray(rng.standard_normal((N, B)))
        planv = ozaki2.make_plan(N)
        for rep in ("f64", "digits"):
            us = _timed(lambda rep=rep, X=X: ozaki_gemv.gemv(
                A, X, planv, out_rep=rep, bm=128, bk=128,
                interpret=dispatch.pallas_interpret("gemv")))
            out_bytes = {"f64": 8, "digits": planv.r}[rep] * M * B
            beta = _beta((M * N + N * B) * 8, M * B * 8,
                         (M * N + N * B) * 8, out_bytes)
            rows.append((f"kernel_gemv_b{B}/{rep}/beta", us, beta))

    # --- 7-point stencil ------------------------------------------------------
    # mode="pallas" pins the wallclock rows to the fused kernel (the CPU auto
    # route is now the jnp reference via the dispatch seam).
    u = jnp.asarray(rng.standard_normal((32, 32, 32)))
    c = jnp.asarray(np.array([6.0, -1, -1, -1, -1, -1, -1]))
    for rep in ("f64", "digits", "ds"):
        usx = _timed(lambda rep=rep: dispatch.stencil7(u, c, out_rep=rep,
                                                       bx=4, mode="pallas"))
        plan_s = ozaki2.make_plan(8, margin_bits=4)
        npts = 32 ** 3
        out_bytes = {"f64": 8, "ds": 8, "digits": plan_s.r}[rep] * npts
        beta = _beta(npts * 8, npts * 8, npts * 8, out_bytes)
        rows.append((f"kernel_stencil/{rep}/beta", usx, beta))

    # --- Blocked-ELL SpMV ------------------------------------------------------
    Ms, Ns, bw = 1024, 1024, 16
    col = jnp.asarray(rng.integers(0, Ns, (Ms, bw)).astype(np.int32))
    val_np = rng.standard_normal((Ms, bw))
    val_np[rng.random((Ms, bw)) < 0.3] = 0.0
    val = jnp.asarray(val_np)
    x = jnp.asarray(rng.standard_normal(Ns))
    for rep in ("f64", "digits"):
        # mode="xla" pins these rows to the bit-identical jnp reference: the
        # interpreted Pallas SpMV pays a multi-minute XLA-CPU compile at the
        # default plan, which would hang the smoke lane.  The fused-kernel
        # machinery is covered by the bounded-plan route rows below (and on
        # TPU these same entry points measure the Mosaic kernel via auto).
        us = _timed(lambda rep=rep: dispatch.spmv(val, col, x, out_rep=rep,
                                                  br=256, mode="xla"))
        plan_v = ozaki2.make_plan(bw, margin_bits=4)
        out_bytes = {"f64": 8, "digits": plan_v.r}[rep] * Ms
        # native bytes: values + colidx + x-gather (cached ~1x) + y
        native = Ms * bw * 8 + Ms * bw * 4 + Ns * 8 + Ms * 8
        emu = Ms * bw * 8 + Ms * bw * 4 + Ns * 8 + out_bytes
        rows.append((f"kernel_spmv/{rep}/beta", us, emu / native))

    # --- dispatch-route comparison (the seam, both sides) ---------------------
    # derived on both rows of a pair = max |pallas - xla| (expected exactly 0:
    # the routes are bit-identical); outputs are computed once per route.
    # stencil: default plan, both routes are cheap on CPU.
    stencil_out = {}
    for mode in ("xla", "pallas"):
        us = _timed(lambda mode=mode: dispatch.stencil7(u, c, bx=4, mode=mode))
        route, cls = _provenance(
            lambda mode=mode: dispatch.stencil7(u, c, bx=4, mode=mode))
        stencil_out[mode] = (f"kernel_stencil/route_{mode}/us", us,
                             dispatch.stencil7(u, c, bx=4, mode=mode),
                             route, cls)
    diff = float(jnp.max(jnp.abs(stencil_out["pallas"][2]
                                 - stencil_out["xla"][2])))
    rows.extend((name, us, diff, route, cls)
                for name, us, _, route, cls in stencil_out.values())

    # spmv: 24-bit payload (r = 7) bounds the interpreter compile to seconds.
    plan_r7 = ozaki2.make_plan(8, payload_bits=24, margin_bits=4)
    Mr, Nr, bwr = 256, 256, 8
    col_r = jnp.asarray(rng.integers(0, Nr, (Mr, bwr)).astype(np.int32))
    val_r = jnp.asarray(rng.standard_normal((Mr, bwr)))
    x_r = jnp.asarray(rng.standard_normal(Nr))
    spmv_out = {}
    for mode in ("xla", "pallas"):
        us = _timed(lambda mode=mode: dispatch.spmv(
            val_r, col_r, x_r, plan=plan_r7, br=128, mode=mode))
        route, cls = _provenance(lambda mode=mode: dispatch.spmv(
            val_r, col_r, x_r, plan=plan_r7, br=128, mode=mode))
        spmv_out[mode] = (f"kernel_spmv/route_{mode}/us", us,
                          dispatch.spmv(val_r, col_r, x_r, plan=plan_r7,
                                        br=128, mode=mode),
                          route, cls)
    diff = float(jnp.max(jnp.abs(spmv_out["pallas"][2] - spmv_out["xla"][2])))
    rows.extend((name, us, diff, route, cls)
                for name, us, _, route, cls in spmv_out.values())

    # --- padding-ratio -> beta (Appendix D) -----------------------------------
    for rho in (1.0, 2.0, 4.0):
        rows.append((f"kernel_spmv/padding_rho{rho}/beta_bound", 0.0, rho))
    return rows
