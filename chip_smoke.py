#!/usr/bin/env python3
"""On-chip smoke test of the emulated-FP64 dispatch path (one TPU, one process).

Runs each dispatch kind once at a size an HPC user would call real, through
the public entry points on the ``auto`` route, and checks every result
against a host numpy oracle computed from the same host float64 arrays:

  phase 0   what "float64" is on the device (round trips and arithmetic);
  gemm      ``dispatch.matmul`` 4096^3, default 53-bit plan;
  gemv      ``dispatch.matmul`` 8192 x 8192 with 8 right-hand sides;
  spmv/cg   ``dispatch.spmv`` and ``cg.cg_solve_bell`` on the 7-point 3-D
            Poisson operator, 64^3 unknowns in Blocked-ELL, to 1e-10;
  stencil   ``dispatch.stencil7`` and ``jacobi.jacobi_solve`` sweeps, 256^3;
  fft       ``spectral.fft_parts`` (``spectral.fft`` on real and imaginary
            parts), 8 batched complex transforms of n = 65536;
  attention ``dispatch.attention`` prefill (B*H = 8, S = T = 2048, D = 128,
            causal) and one decode step at T = 2048.

Each phase checks that ``auto`` resolved to the compiled Mosaic kernels (the
``pallas`` route, not interpreted: ``tpu_custom_call`` in the compiled
program, or the route in the telemetry counters for the solver loops), runs
the ``xla`` route on the chip too, and checks that the two agree.  Every
phase prints its error beside its bound and its wall time; all phases run,
and any failure makes the exit code non-zero.  The last line of standard
output is one JSON object naming the device.  With no TPU the script exits
non-zero before any phase runs.

The bounds are the CPU tests' tolerances with the float64 unit roundoff
2^-53 replaced by the one phase 0 measures on the device (``u_dev``).

Run from the repository root: ``python chip_smoke.py``.  The compile cache
lives where ``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache/``.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
SEED = 20260613
U64 = 2.0 ** -53

# Problem sizes: the real ones, which the checks and bounds do not assume.
SIZES = {
    "gemm": (4096, 4096, 4096),     # m, k, n
    "gemv": (8192, 8192, 8),        # m, k, right-hand sides
    "poisson": 64,                  # grid points per axis (CG, SpMV)
    "grid": (256, 256, 256),        # stencil and Jacobi
    "fft": (8, 65536),              # batch, length
    "attention": (8, 2048, 128),    # B*H, T (= prefill S), D
}


def log(*args) -> None:
    print(*args, flush=True)


class Phase:
    """One phase's checks.  A failed check is reported and appended to
    ``failures``, not raised, so every phase runs and the exit code still
    says that one failed."""

    def __init__(self, name: str, failures: list):
        self.name = name
        self.failures = failures

    def check(self, what: str, ok: bool, detail: str = "") -> None:
        log(f"{self.name} {what}: {'ok' if ok else 'FAILED'} {detail}".rstrip())
        if not ok:
            self.failures.append(f"{self.name} {what} {detail}")

    def bound(self, what: str, err: float, bound: float) -> None:
        self.check(what, bool(err <= bound), f"err={err!r} bound={bound!r}")


def _run(ph: Phase, route: str, fn, *args):
    """Jit ``fn`` and run it twice: the first call compiles, the second is
    the wall time.  On the pallas route the lowered program must hold a
    Mosaic kernel (``tpu_custom_call``; interpreted kernels leave none)."""
    import jax
    jitted = jax.jit(fn)
    if route == "pallas":
        ph.check("pallas route lowers to a Mosaic kernel",
                 "tpu_custom_call" in jitted.lower(*args).as_text())
    times = []
    for _ in range(2):
        t0 = time.perf_counter()
        out = jax.block_until_ready(jitted(*args))
        times.append(time.perf_counter() - t0)
    log(f"{ph.name} {route} first_call_s={times[0]:.6f} wall_s={times[1]:.6f}")
    return out


def _auto_is_pallas(ph: Phase, kind: str, plan, shape) -> None:
    from repro.core import dispatch
    route = dispatch.choose_route(plan, kind, None, shape=shape)
    interp = dispatch.pallas_interpret(kind)
    ph.check("auto route", route == "pallas" and not interp,
             f"route={route} interpret={interp}")


def _same(ph: Phase, a, b) -> None:
    """pallas vs xla on the chip: the CPU tests hold them bit-identical."""
    a, b = np.asarray(a), np.asarray(b)
    diff = float(np.max(np.abs(a - b))) if a.size else 0.0
    ph.check("pallas == xla (bit-identical)", bool(np.array_equal(a, b)),
             f"max_abs_diff={diff!r}")


# ---------------------------------------------------------------------------
# Phase 0: what float64 is on the device
# ---------------------------------------------------------------------------

def _contiguous_max(ok) -> int:
    """Largest k such that ok[0..k-1] all hold (0 if ok[0] fails)."""
    k = 0
    while k < len(ok) and ok[k]:
        k += 1
    return k


def probe_f64() -> float:
    """Round-trip host float64 values through the device and run a few
    arithmetic ops on it.  Prints what survives: the significand width of
    storage and of arithmetic, and the exponent range.  Returns ``u_dev``,
    the worst relative error of one device float64 operation (at least
    2^-53): every later bound uses it in place of the unit roundoff."""
    import jax
    import jax.numpy as jnp

    def rt(x):
        return np.asarray(jax.device_put(np.asarray(x, np.float64)))

    named = {"2^53-1": 2.0 ** 53 - 1, "1+2^-52": 1.0 + 2.0 ** -52,
             "2^-1022": 2.0 ** -1022, "2^1000": 2.0 ** 1000}
    got = rt(list(named.values()))
    for (name, want), g in zip(named.items(), got):
        log(f"phase0 roundtrip {name}: sent {want!r} got {float(g)!r} "
            f"exact={bool(g == want)}")

    # Storage: 1 + 2^-k survives a round trip for k < bits.
    tiny = 2.0 ** -np.arange(1, 64).astype(np.float64)
    rt_bits = 1 + _contiguous_max(rt(1.0 + tiny) - 1.0 == tiny)
    big = 2.0 ** np.arange(1, 64)
    int_bits = 1 + _contiguous_max(rt(big + 1.0) - big == 1.0)
    # Exponent range: the powers of two that round-trip exactly.
    es = np.arange(-1074, 1024)
    pw = np.ldexp(np.ones(es.shape), es)
    ok = rt(pw) == pw
    lo = hi = int(np.where(es == 0)[0][0])
    while lo > 0 and ok[lo - 1]:
        lo -= 1
    while hi + 1 < len(es) and ok[hi + 1]:
        hi += 1

    # Arithmetic on values the device holds exactly, against host float64 on
    # the same values.  Errors are relative to the operand scale (|a| + |b|
    # for add and sub), so cancellation does not inflate them; the host's own
    # rounding (<= 2^-53) is included, which only makes u_dev larger.
    rng = np.random.default_rng(SEED)
    a = rt(rng.standard_normal(1 << 16))
    b = rt(rng.standard_normal(1 << 16))
    da, db = jax.device_put(a), jax.device_put(b)
    scale_add = np.abs(a) + np.abs(b)
    ops = {
        "add": (jnp.add(da, db), a + b, scale_add),
        "sub": (jnp.subtract(da, db), a - b, scale_add),
        "mul": (jnp.multiply(da, db), a * b, np.abs(a * b)),
        "div": (jnp.divide(da, db), a / b, np.abs(a / b)),
        "sqrt": (jnp.sqrt(jnp.abs(da)), np.sqrt(np.abs(a)), np.sqrt(np.abs(a))),
    }
    errs = {name: float(np.max(np.abs(np.asarray(dev) - host) / s))
            for name, (dev, host, s) in ops.items()}
    u_dev = max([U64] + list(errs.values()))
    out = {"significand_bits_storage": rt_bits,
           "integer_bits_storage": int_bits,
           "exponent_range_storage": [int(es[lo]), int(es[hi])],
           "rel_err": errs,
           "u_dev": u_dev,
           "significand_bits_arith": -math.log2(u_dev)}
    log("phase0 f64 on device: " + json.dumps(out))
    return u_dev


# ---------------------------------------------------------------------------
# Dense: GEMM and GEMV
# ---------------------------------------------------------------------------

def _ld_rows(a: np.ndarray, b: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """Rows of a @ b accumulated in long double (the oracle's own error is
    k * eps_ld relative to |a||b|, added to the bound)."""
    return (a[rows].astype(np.longdouble) @ b.astype(np.longdouble))


def phase_matmul(name: str, m: int, k: int, n: int, u_dev: float, rng,
                 fails: list) -> None:
    import jax
    from repro.core import dispatch

    ph = Phase(name, fails)
    a = rng.standard_normal((m, k))
    b = rng.standard_normal((k, n))
    da, db = jax.device_put(a), jax.device_put(b)
    kind = "gemv" if n <= dispatch.GEMV_MAX_B else "gemm"
    _auto_is_pallas(ph, kind, dispatch.get_plan(k), (m, k, n))
    outs = {}
    for route in ("pallas", "xla"):
        mode = None if route == "pallas" else "xla"
        outs[route] = _run(ph, route, lambda x, y, mode=mode:
                           dispatch.matmul(x, y, mode=mode), da, db)
    _same(ph, outs["pallas"], outs["xla"])
    rows = rng.choice(m, size=8, replace=False)
    c = np.asarray(outs["pallas"])[rows]
    want = _ld_rows(a, b, rows)
    denom = np.abs(a[rows]) @ np.abs(b)
    err = float(np.max(np.abs(c - want) / denom))
    # test_kernels: |C - C_ref| <= 16 u |A||B| componentwise (GEMM and GEMV),
    # at the device's u; plus the long-double oracle's k * eps_ld.
    eps_ld = float(np.finfo(np.longdouble).eps)
    ph.bound("componentwise error / (|A||B|), 8 sampled rows", err,
             16 * u_dev + k * eps_ld)


# ---------------------------------------------------------------------------
# Sparse: SpMV and CG on the 3-D Poisson operator
# ---------------------------------------------------------------------------

def poisson_bell(n: int):
    """7-point -Δ_h on an n^3 grid, Blocked-ELL with bw = 7 (banded)."""
    from repro.hpc import spmv_formats
    return spmv_formats.laplacian_3d_bell(n)


def _bell_matvec(val, col, x):
    return np.sum(val * x[col], axis=-1)


def _np_cg(val, col, b, tol, maxiter):
    """Plain float64 CG on the host (the test_hpc_cg oracle)."""
    x = np.zeros_like(b)
    r = b.copy()
    p = r.copy()
    rs = r @ r
    bn = np.linalg.norm(b)
    for it in range(1, maxiter + 1):
        ap = _bell_matvec(val, col, p)
        alpha = rs / (p @ ap)
        x += alpha * p
        r -= alpha * ap
        rs_new = r @ r
        if math.sqrt(rs_new) / bn < tol:
            return x, it
        p = r + (rs_new / rs) * p
        rs = rs_new
    return x, maxiter


def phase_sparse(u_dev: float, rng, fails: list) -> None:
    import jax
    from repro.core import dispatch
    from repro.hpc import cg
    from repro.obs import telemetry as obs

    ph = Phase("spmv", fails)
    n, tol = SIZES["poisson"], 1e-10
    val, col = poisson_bell(n)
    x = rng.standard_normal(n ** 3)
    dv, dc, dx = (jax.device_put(t) for t in (val, col, x))
    _auto_is_pallas(ph, "spmv_bell", dispatch.get_plan(7, margin_bits=4),
                    val.shape)
    outs = {}
    for route in ("pallas", "xla"):
        mode = None if route == "pallas" else "xla"
        outs[route] = _run(ph, route, lambda v, c, y, mode=mode:
                           dispatch.spmv(v, c, y, mode=mode), dv, dc, dx)
    _same(ph, outs["pallas"], outs["xla"])
    want = _bell_matvec(val, col, x)
    denom = np.abs(val).sum(-1) * np.max(np.abs(x))
    err = float(np.max(np.abs(np.asarray(outs["pallas"]) - want) / denom))
    # test_kernels SpMV sweep: 16 u (sum|a_row|) max|x|, at the device's u.
    ph.bound("error / (sum|a_row| max|x|)", err, 16 * u_dev)

    ph = Phase("cg", fails)
    b = rng.standard_normal(n ** 3)
    db = jax.device_put(b)
    res = {}
    for route in ("pallas", "xla"):
        mode = None if route == "pallas" else "xla"
        obs.reset()
        t0 = time.perf_counter()
        with obs.telemetry_scope("counters"):
            res[route] = cg.cg_solve_bell(dv, dc, db, tol=tol, maxiter=2000,
                                          mode=mode, record_plain=False)
        secs = time.perf_counter() - t0
        routes = sorted({k[2] for k in obs.counters_snapshot()
                         if k[0] == "spmv_bell"})
        log(f"cg {route} wall_s={secs:.6f} iters={res[route].iters} "
            f"residual={res[route].residual!r} telemetry_routes={routes}")
        ph.check(f"{route} telemetry route", routes == [route])
        ph.check(f"{route} converged", res[route].converged)
    _same(ph, res["pallas"].x, res["xla"].x)
    xp = np.asarray(res["pallas"].x)
    x_np, it_np = _np_cg(val, col, b, tol, 2000)
    log(f"cg host float64 oracle iters={it_np}")
    r_dev = np.linalg.norm(b - _bell_matvec(val, col, xp))
    r_np = np.linalg.norm(b - _bell_matvec(val, col, x_np))
    bn = np.linalg.norm(b)
    # The recurrence residual stopped below tol; the true residual drifts from
    # it by the rounding of the updates, first order iters * u * ||A|| ||x||
    # (||A||_2 <= 12 for this operator).
    ph.bound("true relative residual", float(r_dev / bn),
             tol + res["pallas"].iters * u_dev * 12 * np.linalg.norm(xp) / bn)
    # Both solutions solve A x = b to their residuals, so they differ by at
    # most ||A^-1|| (||r_dev|| + ||r_np||), lambda_min = 3 * 4 sin^2(pi/2(n+1)).
    lam_min = 12 * math.sin(math.pi / (2 * (n + 1))) ** 2
    ph.bound("||x - x_host_cg||_2", float(np.linalg.norm(xp - x_np)),
             float((r_dev + r_np) / lam_min))


# ---------------------------------------------------------------------------
# Structured grid: stencil and Jacobi sweeps
# ---------------------------------------------------------------------------

def _np_stencil(u, c):
    v = c[0] * u
    for t, (ax, d) in enumerate(((0, 1), (0, -1), (1, 1), (1, -1),
                                 (2, 1), (2, -1)), start=1):
        s = np.zeros_like(u)
        src = [slice(None)] * 3
        dst = [slice(None)] * 3
        if d == 1:
            dst[ax], src[ax] = slice(1, None), slice(None, -1)
        else:
            dst[ax], src[ax] = slice(None, -1), slice(1, None)
        s[tuple(dst)] = u[tuple(src)]
        v = v + c[t] * s
    return v


def phase_grid(u_dev: float, rng, fails: list) -> None:
    import jax
    from repro.core import dispatch
    from repro.hpc import jacobi
    from repro.obs import telemetry as obs

    ph = Phase("stencil", fails)
    shape = SIZES["grid"]
    c = np.asarray(jacobi.laplacian_coeffs())
    u = rng.standard_normal(shape)
    du, dc = jax.device_put(u), jax.device_put(c)
    _auto_is_pallas(ph, "stencil7", dispatch.get_plan(8, margin_bits=4), shape)
    outs = {}
    for route in ("pallas", "xla"):
        mode = None if route == "pallas" else "xla"
        outs[route] = _run(ph, route, lambda x, k, mode=mode:
                           dispatch.stencil7(x, k, mode=mode), du, dc)
    _same(ph, outs["pallas"], outs["xla"])
    del outs["xla"]
    scale = 7 * np.max(np.abs(u)) * np.max(np.abs(c))
    err = float(np.max(np.abs(np.asarray(outs.pop("pallas")) - _np_stencil(u, c))))
    # test_kernels / test_jacobi: 8 u (7 max|u| max|c|), at the device's u.
    ph.bound("max error", err, 8 * u_dev * scale)

    ph = Phase("jacobi", fails)
    sweeps = 4
    f = rng.standard_normal(shape)
    dfj = jax.device_put(f)
    res = {}
    for route in ("pallas", "xla"):
        mode = None if route == "pallas" else "xla"
        obs.reset()
        t0 = time.perf_counter()
        with obs.telemetry_scope("counters"):
            res[route] = jacobi.jacobi_solve(dfj, tol=0.0, maxiter=sweeps,
                                             mode=mode)
        jax.block_until_ready(res[route].u)
        secs = time.perf_counter() - t0
        routes = sorted({k[2] for k in obs.counters_snapshot()
                         if k[0] == "stencil7"})
        log(f"jacobi {route} wall_s={secs:.6f} sweeps={res[route].iters} "
            f"residual={res[route].residual!r} telemetry_routes={routes}")
        ph.check(f"{route} telemetry route", routes == [route])
    _same(ph, res["pallas"].u, res["xla"].u)
    # Host sweeps u <- u + (f - L u) / c0.  The sweep matrix has inf-norm 1,
    # so local errors add: per sweep the stencil's 8 u (7 max|u| max|c|) and
    # the residual's u (max|f| + max|Lu|), both over |c0| = 6, and the
    # update's 2 u max|u_next|.
    uh = np.zeros(shape)
    bound = 0.0
    for _ in range(sweeps):
        lu = _np_stencil(uh, c)
        un = uh + (1.0 / c[0]) * (f - lu)
        bound += (8 * u_dev * 7 * np.max(np.abs(uh)) * np.max(np.abs(c))
                  + u_dev * (np.max(np.abs(f)) + np.max(np.abs(lu)))) / abs(c[0])
        bound += 2 * u_dev * np.max(np.abs(un))
        uh = un
    err = float(np.max(np.abs(np.asarray(res["pallas"].u) - uh)))
    ph.bound(f"max error after {sweeps} sweeps", err, float(bound))


# ---------------------------------------------------------------------------
# Spectral: batched FFT
# ---------------------------------------------------------------------------

def phase_fft(u_dev: float, rng, fails: list) -> None:
    import jax
    from repro import spectral
    from repro.core import dispatch

    ph = Phase("fft", fails)
    batch, n = SIZES["fft"]
    xr, xi = rng.standard_normal((batch, n)), rng.standard_normal((batch, n))
    dxr, dxi = jax.device_put(xr), jax.device_put(xi)
    # For n = 65536 every GEMM of the four-step transform is a 16-point dense
    # DFT (a 32 x 32 realified operator) applied to a wide batch.
    _auto_is_pallas(ph, "gemm", dispatch.get_plan(32), (32, 32, batch * n // 16))
    outs = {}
    for route in ("pallas", "xla"):
        mode = None if route == "pallas" else "xla"
        # XLA:TPU compiles no complex128 op, so the transform takes and
        # returns real and imaginary parts (``spectral.fft`` wraps this).
        outs[route] = _run(ph, route, lambda a, b, mode=mode:
                           spectral.fft_parts(a, b, mode=mode), dxr, dxi)
    _same(ph, outs["pallas"], outs["xla"])
    want = np.fft.fft(xr + 1j * xi, axis=-1)
    got = np.asarray(outs["pallas"][0]) + 1j * np.asarray(outs["pallas"][1])
    err = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    # test_spectral: relative 2-norm error <= 1e-12 = (1e-12 / 2^-53) u.
    ph.bound("relative 2-norm error", err, 1e-12 / U64 * u_dev)


# ---------------------------------------------------------------------------
# Attention: prefill and decode
# ---------------------------------------------------------------------------

def _np_attention(q, k, v, mask):
    s = q @ np.swapaxes(k, -1, -2) / math.sqrt(q.shape[-1])
    s = np.where(mask, s, -np.inf)
    s = s - s.max(-1, keepdims=True)
    p = np.exp(s)
    return (p / p.sum(-1, keepdims=True)) @ v


def phase_attention(u_dev: float, rng, fails: list) -> None:
    import jax
    from repro.core import dispatch

    BH, T, D = SIZES["attention"]
    k = rng.standard_normal((BH, T, D))
    v = rng.standard_normal((BH, T, D))
    dk, dv = jax.device_put(k), jax.device_put(v)
    for name, S in (("attention_prefill", T), ("attention_decode", 1)):
        ph = Phase(name, fails)
        q = rng.standard_normal((BH, S, D))
        mask = np.tril(np.ones((T, T), np.int8))[T - S:]   # causal
        dq, dm = jax.device_put(q), jax.device_put(mask)
        _auto_is_pallas(ph, "attention", dispatch.get_plan(D), (BH, S, D, T))
        outs = {}
        for route in ("pallas", "xla"):
            mode = None if route == "pallas" else "xla"
            outs[route] = _run(ph, route, lambda a, b, c, m, mode=mode:
                               dispatch.attention(a, b, c, mask=m, mode=mode),
                               dq, dk, dv, dm)
        _same(ph, outs["pallas"], outs["xla"])
        want = _np_attention(q, k, v, mask.astype(bool))
        got = np.asarray(outs["pallas"])
        # test_attention: |out - oracle| <= 1e-12 + 1e-12 |oracle|, i.e.
        # (1e-12 / 2^-53) u on each term, at the device's u.
        tol = 1e-12 / U64 * u_dev
        err = float(np.max(np.abs(got - want) / (tol + tol * np.abs(want))))
        ph.bound("max |out - oracle| / (atol + rtol |oracle|)", err, 1.0)


# ---------------------------------------------------------------------------

def main() -> int:
    import jax
    jax.config.update("jax_enable_x64", True)
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(REPO, ".jax_cache"))
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); "
              "this script runs only on the chip", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(REPO, "src"))
    import repro  # noqa: F401 — fails here when run outside the checkout

    log(f"device_kind={dev.device_kind} platform={dev.platform} "
        f"count={len(jax.devices())}")
    rng = np.random.default_rng(SEED)
    fails: list = []
    t0 = time.perf_counter()
    u_dev = probe_f64()
    phase_matmul("gemm", *SIZES["gemm"], u_dev, rng, fails)
    phase_matmul("gemv", *SIZES["gemv"], u_dev, rng, fails)
    phase_sparse(u_dev, rng, fails)
    phase_grid(u_dev, rng, fails)
    phase_fft(u_dev, rng, fails)
    phase_attention(u_dev, rng, fails)
    log(f"total_s={time.perf_counter() - t0:.3f} device_kind={dev.device_kind}")
    if fails:
        print("chip_smoke: FAILED:\n  " + "\n  ".join(fails), file=sys.stderr)
        return 1
    log(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                           "kind": dev.device_kind,
                                           "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
