"""Unified emulation dispatch layer — plan cache + XLA/Pallas routing.

The paper's §8 recommendation is that Ozaki-style emulation live *behind* the
precision-policy interface of the standard libraries, with the register-fused
kernels as the default execution path.  This module is that seam: every
emulated multiplication in the repo (``Policy.dot``, the HPC solvers, the
serving engine, the spectral transforms) resolves its configuration and its
execution path here instead of hand-rolling both at each call-site.  The
kernel modules under ``repro.kernels`` each own their whole pallas route and
import nothing from this layer.

Three concerns, one layer:

  1. **Plan cache** — ``get_plan`` memoises ``ozaki2.make_plan`` on
     ``(k, payload_bits, substrate, r, margin_bits)`` and primes the Garner
     constants at cache-fill time, so the per-call ``make_plan`` +
     ``required_r`` + Garner recomputation disappears from the hot path
     (previously paid on *every* ``Policy.dot`` trace and every VJP re-plan).

  2. **Shape-normalising router** — one entry point per fused-kernel *kind*
     (``matmul`` covering gemm/gemv, ``spmv`` for Blocked-ELL, ``stencil7``
     for the 7-point stencil) normalises operands (MXU padding for GEMM:
     sublane 8, lane 128), routes, and unpads.  The ``pallas`` route is the
     fused kernel (interpret-mode on CPU, compiled Mosaic on TPU); the
     ``xla`` route is the unfused bit-identical reference
     (``ozaki2.emulated_matmul``, ``ozaki_spmv.spmv_bell_ref``,
     ``ozaki_stencil.stencil7_ref``).  Zero-padding is exact: padded
     rows/columns scale with shift 0 and contribute zero residues, so the two
     routes are *bit-identical* on the unpadded region for every kind.

  3. **Mode override** — the route is selected by, in priority order: an
     explicit ``mode=`` argument, the ``mode_scope``/``set_mode``
     programmatic override, and the ``REPRO_DISPATCH`` environment variable
     (``auto | xla | pallas``, default ``auto``).  ``auto`` resolves through
     the per-kind backend table ``AUTO_ROUTE``: every kind prefers the fused
     kernel on TPU backends and the reference path on CPU (where
     interpret-mode Pallas is a correctness tool, not a fast path — for
     ``spmv_bell`` the interpreted gather graph even costs minutes of XLA
     compile).  Whether the pallas route runs interpreted is *not* routing:
     ``pallas_interpret`` decides it here, per backend, and no caller outside
     this module passes ``interpret=`` for route selection.

  4. **Autotuning table** — ``get_tuning(kind, shape)`` resolves block/tile
     parameters per (kind, shape-class), keyed like the plan cache.  The
     shape-class buckets each dimension to the next power of two, so one
     measured entry covers a band of problem sizes.  Kinds are the fused
     kernel kinds plus ``reduce`` (the blocked-EFT compensated reductions in
     ``repro.core.compensated``, which take their block size from here).  The
     committed ``TUNE_TABLE`` seeds measured defaults; the ``REPRO_TUNE``
     environment variable (inline JSON or a path to a JSON file, shaped
     ``{kind: {shape-class-or-*: {param: value, ...}}}``) overrides entries
     without code changes.  ``choose_route`` consults the table too: an entry
     may pin ``"route": "xla" | "pallas"`` for its shape class, which wins
     over the backend default in ``auto`` mode (explicit modes still win).

  5. **Telemetry** — with ``REPRO_TELEMETRY=counters|trace``
     (``repro.obs.telemetry``), every entry point records its kind,
     shape-class, chosen route, plan r/payload_bits, fenced wall time, and
     the TME-predicted time for the same op; ``get_plan``/``get_tuning``
     count their cache hits and misses.  Recording is tracer-safe (a jitted
     caller records nothing) and free when off.
"""

from __future__ import annotations

import contextlib
import functools
import json
import os
import threading
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import backend, ozaki2
from repro.kernels import (ozaki_attention, ozaki_gemm, ozaki_gemv, ozaki_spmv,
                           ozaki_stencil)
from repro.obs import telemetry as obs

MODES = ("auto", "xla", "pallas")
ENV_VAR = "REPRO_DISPATCH"
TUNE_VAR = "REPRO_TUNE"

# Fused-kernel kinds the router understands.  "gemm"/"gemv" share the matmul
# entry point (split on RHS width); "spmv_bell", "stencil7", and "attention"
# (the fused online-softmax scan) have their own.
KINDS = ("gemm", "gemv", "spmv_bell", "stencil7", "attention")

# Kinds the autotuning table covers: the fused-kernel kinds plus the
# blocked-EFT compensated reductions (no fused Pallas kernel yet — the blocked
# jnp pipeline *is* the vector-pipe fast path, so its route is always "xla").
TUNE_KINDS = KINDS + ("reduce",)

# Per-kind auto-route defaults by backend family.  One table instead of the
# old per-wrapper ``_default_interpret()`` logic: the fused kernels are the
# production route on TPU for every kind; on CPU/GPU the bit-identical
# reference is the fast path (the Pallas interpreter is a parity oracle —
# and for spmv_bell its gather graph pays a multi-minute XLA-CPU compile).
AUTO_ROUTE = {
    "gemm": {"tpu": "pallas", "default": "xla"},
    "gemv": {"tpu": "pallas", "default": "xla"},
    "spmv_bell": {"tpu": "pallas", "default": "xla"},
    "stencil7": {"tpu": "pallas", "default": "xla"},
    "attention": {"tpu": "pallas", "default": "xla"},
    "reduce": {"default": "xla"},
}

# MXU geometry (Pallas TPU tiling constraints): second-minor axis in sublane
# multiples of 8, minor axis in lane multiples of 128.
SUBLANE = 8
LANE = 128
# int8 blocks (the GEMM's residue planes, both kernels' digits) tile in
# (32, 128): a tuned bm is a multiple of 32, else the whole padded m-axis.
INT8_SUBLANE = 32
DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 256

# Per-thread override so concurrent engines (e.g. two ServeEngines tracing
# under different modes) cannot interleave each other's route resolution.
_tls = threading.local()


# ---------------------------------------------------------------------------
# Mode resolution
# ---------------------------------------------------------------------------

def _validate_mode(mode: str) -> str:
    if mode not in MODES:
        raise ValueError(f"dispatch mode must be one of {MODES}, got {mode!r}")
    return mode


def get_mode() -> str:
    """Effective dispatch mode: programmatic override, else env, else auto."""
    override = getattr(_tls, "mode", None)
    if override is not None:
        return override
    return _validate_mode(os.environ.get(ENV_VAR, "auto"))


def set_mode(mode: Optional[str]) -> None:
    """Set (or with None, clear) this thread's dispatch-mode override."""
    _tls.mode = None if mode is None else _validate_mode(mode)


@contextlib.contextmanager
def mode_scope(mode: Optional[str]):
    """Temporarily force a dispatch mode (None = inherit the ambient mode)."""
    prev = getattr(_tls, "mode", None)
    set_mode(mode if mode is not None else prev)
    try:
        yield
    finally:
        _tls.mode = prev


# ---------------------------------------------------------------------------
# Plan / Garner-constant cache
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _cached_plan(k: int, payload_bits: int, substrate: str, r: Optional[int],
                 margin_bits: int) -> ozaki2.Plan:
    plan = ozaki2.make_plan(k, payload_bits, r=r, substrate=substrate,
                            margin_bits=margin_bits)
    plan.garner  # noqa: B018 — prime the Garner constants at cache-fill time
    return plan


def get_plan(k: int, payload_bits: int = 53, substrate: str = "int8",
             r: Optional[int] = None, margin_bits: int = 2) -> ozaki2.Plan:
    """Cache-resolved Plan for contractions of length k (Garner pre-primed).

    Semantically identical to ``ozaki2.make_plan`` but amortised: repeated
    lookups (every policy dot, every VJP re-plan, every CG iteration) return
    the same object without re-running moduli selection or Garner setup.
    """
    if obs.enabled():
        before = _cached_plan.cache_info().misses
        plan = _cached_plan(int(k), int(payload_bits), substrate, r, margin_bits)
        obs.record_cache("plan", _cached_plan.cache_info().misses == before)
        return plan
    return _cached_plan(int(k), int(payload_bits), substrate, r, margin_bits)


def plan_cache_info():
    """lru_cache statistics for the plan cache (tests / benchmarks)."""
    return _cached_plan.cache_info()


def clear_plan_cache() -> None:
    """Drop every memoised Plan (tests that vary moduli/payload per case)."""
    _cached_plan.cache_clear()


# ---------------------------------------------------------------------------
# Autotuning table: (kind, shape-class) -> block/tile parameters
# ---------------------------------------------------------------------------

# Seeded (measured) tuning entries.  "*" is the per-kind wildcard; specific
# shape-classes (see ``shape_class``) override it.  The GEMV entry mirrors
# the MXU defaults (DEFAULT_BM/BK); spmv_bell/stencil7 carry the kernel
# defaults so every kind resolves its blocking here rather than in
# per-call-site constants.  GEMM: the fastest of bm, bn in {128, 256, 512}
# and bk in {256, 512} on one TPU v5e, at 8192^3 (the kernel 137.8 ms, 246.4
# at 128/128/256) and at 2048^3 (7.03 ms, 7.68 at 128/128/256), each with
# Garner over the whole tile.
TUNE_TABLE: Dict[Tuple[str, str], Dict[str, Any]] = {
    ("gemm", "*"): {"bm": 512, "bn": 512, "bk": 512},
    ("gemv", "*"): {"bm": 128, "bk": 256},
    ("spmv_bell", "*"): {"br": 128},
    ("stencil7", "*"): {"bx": 1},
    ("attention", "*"): {"bkv": 128},
    ("reduce", "*"): {"block": 512},
    # Measured on CPU (f64 compensated_dot sweep): short vectors are
    # dispatch-bound and flat across blocks; >=64k-element reductions favor
    # the shorter 256-lane block (smaller carry scan wins over tree width).
    ("reduce", "65536"): {"block": 256},
    ("reduce", "131072"): {"block": 256},
}


def _next_pow2(n: int) -> int:
    n = max(1, int(n))
    return 1 << (n - 1).bit_length()


def shape_class(dims: Sequence[int]) -> str:
    """Bucket a shape into its tuning class: each dim rounded up to the next
    power of two, joined with "x" (e.g. (100, 64, 24) -> "128x64x32")."""
    return "x".join(str(_next_pow2(d)) for d in dims)


@functools.lru_cache(maxsize=None)
def _tune_overrides(env: str) -> Dict[Tuple[str, str], Dict[str, Any]]:
    """Parse REPRO_TUNE (inline JSON, or a path to a JSON file) into the same
    (kind, class) -> params mapping as TUNE_TABLE.  Malformed input raises —
    a silently-ignored tuning override is worse than a loud one."""
    if not env:
        return {}
    text = env
    if not env.lstrip().startswith("{"):
        with open(env) as fh:
            text = fh.read()
    raw = json.loads(text)
    table: Dict[Tuple[str, str], Dict[str, Any]] = {}
    for kind, classes in raw.items():
        if kind not in TUNE_KINDS:
            raise ValueError(f"{TUNE_VAR}: unknown kind {kind!r} "
                             f"(expected one of {TUNE_KINDS})")
        for cls, params in classes.items():
            table[(kind, str(cls))] = dict(params)
    return table


@functools.lru_cache(maxsize=None)
def _cached_tuning(kind: str, cls: str, env: str) -> Dict[str, Any]:
    merged: Dict[str, Any] = {}
    overrides = _tune_overrides(env)
    for layer in (TUNE_TABLE.get((kind, "*")), TUNE_TABLE.get((kind, cls)),
                  overrides.get((kind, "*")), overrides.get((kind, cls))):
        if layer:
            merged.update(layer)
    return merged


def get_tuning(kind: str, dims: Sequence[int]) -> Dict[str, Any]:
    """Tuning parameters for ``kind`` at this shape-class (memoised, like the
    plan cache): seeded TUNE_TABLE defaults layered under any REPRO_TUNE
    overrides, most-specific last.  Returns a (shared) dict — treat as
    read-only."""
    if kind not in TUNE_KINDS:
        raise ValueError(f"tuning kind must be one of {TUNE_KINDS}, got {kind!r}")
    args = (kind, shape_class(dims), os.environ.get(TUNE_VAR, ""))
    if obs.enabled():
        before = _cached_tuning.cache_info().misses
        tuning = _cached_tuning(*args)
        obs.record_cache("tune", _cached_tuning.cache_info().misses == before)
        return tuning
    return _cached_tuning(*args)


def clear_tune_cache() -> None:
    """Drop memoised tuning lookups (tests flip REPRO_TUNE between cases)."""
    _cached_tuning.cache_clear()
    _tune_overrides.cache_clear()


def reduce_block(n: int) -> int:
    """Block size for the blocked-EFT reductions over length-n operands —
    the ``repro.core.compensated`` fast path resolves its blocking here."""
    return max(1, int(get_tuning("reduce", (n,)).get("block", 512)))


# ---------------------------------------------------------------------------
# Shape normalisation
# ---------------------------------------------------------------------------

def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def choose_blocks(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """MXU-friendly (bm, bn, bk) for an (m, k) x (k, n) problem.

    The target tiling comes from the autotuning table (kind "gemm"/"gemv" by
    RHS width, default 128/128/256); smaller axes shrink to the dimension
    rounded up to the hardware granule (sublane 8 for the second-minor m-axis,
    lane 128 for the minor n/k axes) so padding stays bounded while tiles keep
    legal Mosaic shapes; a tuned bm is a multiple of the int8 sublane tile
    (32), and a smaller m-axis is one whole block.  Tuned values are clamped
    to the same legality rules, so a bad REPRO_TUNE entry degrades
    performance, never correctness.
    """
    tune = get_tuning(_matmul_kind(n), (m, k, n))
    tbm = _round_up(int(tune.get("bm", DEFAULT_BM)), INT8_SUBLANE)
    tbn = int(tune.get("bn", DEFAULT_BN))
    tbk = int(tune.get("bk", DEFAULT_BK))
    bm = tbm if m >= tbm else _round_up(m, SUBLANE)
    bn = _round_up(tbn, LANE) if n >= tbn else _round_up(n, LANE)
    # bk must divide the lane-padded K; falling back to one lane (128) keeps
    # the K padding at < one lane of zeros (bk=256 on k=257 would pad to 512).
    tbk = max(LANE, _round_up(tbk, LANE))
    kp = _round_up(k, LANE)
    bk = tbk if kp % tbk == 0 else LANE
    return bm, bn, bk


def _pad_axis(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def pad_operands(a: jax.Array, b: jax.Array,
                 blocks: Optional[Tuple[int, int, int]] = None
                 ) -> Tuple[jax.Array, jax.Array, Tuple[int, int, int]]:
    """Zero-pad (m,k)x(k,n) operands to block multiples.  Exactness: padded
    rows/cols are all-zero, scale with shift 0 and contribute zero residues,
    so the product over the real region is unchanged bit-for-bit."""
    m, k = a.shape
    _, n = b.shape
    bm, bn, bk = blocks if blocks is not None else choose_blocks(m, k, n)
    a = _pad_axis(_pad_axis(a, 0, bm), 1, bk)
    b = _pad_axis(_pad_axis(b, 0, bk), 1, bn)
    return a, b, (bm, bn, bk)


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------

def _validate_kind(kind: str) -> str:
    if kind not in TUNE_KINDS:
        raise ValueError(f"dispatch kind must be one of {TUNE_KINDS}, "
                         f"got {kind!r}")
    return kind


def pallas_supported(plan: Optional[ozaki2.Plan], kind: str = "gemm") -> bool:
    """The fused kernels implement the int8 residue substrate only; the FP8
    Karatsuba substrate runs through the XLA reference path (every kind).
    The ``reduce`` kind has no fused kernel at all — its blocked-EFT jnp
    pipeline is the vector-pipe fast path."""
    _validate_kind(kind)
    if kind == "reduce":
        return False
    return plan is not None and plan.substrate == "int8"


def choose_route(plan: Optional[ozaki2.Plan], kind: str = "gemm",
                 mode: Optional[str] = None,
                 shape: Optional[Sequence[int]] = None) -> str:
    """Resolve a concrete route ('xla' | 'pallas') for this plan/kind/mode.

    ``shape`` (the operand dimensions, optional) lets ``auto`` mode consult
    the autotuning table: a tuning entry carrying ``"route"`` pins the route
    for its (kind, shape-class) ahead of the backend default — e.g. forcing
    tiny problems onto the reference path even on TPU.  Explicit modes and
    substrate support still win over the table.
    """
    _validate_kind(kind)
    mode = _validate_mode(mode) if mode is not None else get_mode()
    if mode == "xla" or not pallas_supported(plan, kind):
        return "xla"
    if mode == "pallas":
        return "pallas"
    if shape is not None:
        route = get_tuning(kind, shape).get("route")
        if route is not None:
            if route not in ("xla", "pallas"):
                raise ValueError(f"tuned route must be 'xla' or 'pallas', "
                                 f"got {route!r}")
            return route
    table = AUTO_ROUTE[kind]
    return table.get(backend.name(), table["default"])


def pallas_interpret(kind: str = "gemm") -> bool:
    """Whether the pallas route runs the kernel interpreter on this backend.

    This is the *execution flavour* of the fused route, not route selection:
    on TPU the kernels lower through Mosaic, everywhere else they run under
    the Pallas interpreter.  Callers outside this module never pass
    ``interpret=`` to pick a path — they pass ``mode=`` and land here.
    """
    _validate_kind(kind)
    return backend.name() != "tpu"


# RHS widths at or below this route to the fused batched-GEMV kernel (paper
# Alg. 1's small-B regime) instead of padding the N axis up to a full GEMM lane.
GEMV_MAX_B = 16


def _matmul_kind(n: int) -> str:
    """gemm vs gemv: narrow RHS routes to the fused batched-GEMV kernel."""
    return "gemv" if n <= GEMV_MAX_B else "gemm"


def _pallas_matmul(a: jax.Array, b: jax.Array, plan: ozaki2.Plan) -> jax.Array:
    m, k = a.shape
    n = b.shape[1]
    if _matmul_kind(n) == "gemv":
        # Narrow RHS (matvec / small batch): the GEMV kernel keeps B on the MXU
        # minor dim rather than zero-padding it to a 128-wide GEMM tile.
        bm, _, bk = choose_blocks(m, k, n)
        ap = _pad_axis(_pad_axis(a, 0, bm), 1, bk)
        bp = _pad_axis(b, 0, bk)
        out = ozaki_gemv.gemv(ap, bp, plan, bm=bm, bk=bk,
                              interpret=pallas_interpret("gemv"))
        return out[:m]
    ap, bp, (bm, bn, bk) = pad_operands(a, b)
    out = ozaki_gemm.gemm(ap, bp, plan, bm=bm, bn=bn, bk=bk,
                          interpret=pallas_interpret("gemm"))
    return out[:m, :n]


def matmul(a: jax.Array, b: jax.Array, plan: Optional[ozaki2.Plan] = None,
           payload_bits: int = 53, substrate: str = "int8",
           mode: Optional[str] = None) -> jax.Array:
    """Emulated FP64-accurate C = A @ B through the dispatch layer.

    a: (m, k), b: (k, n); returns working-float (m, n) regardless of route —
    callers needing the kernel-native digits/ds output representations use
    ``ozaki_gemm.gemm`` / ``ozaki_gemv.gemv`` directly.  The plan comes from
    the process cache unless given explicitly; the execution path follows
    ``choose_route``.
    """
    if plan is None:
        plan = get_plan(a.shape[-1], payload_bits, substrate)
    kind = _matmul_kind(b.shape[1])
    shape = (a.shape[0], a.shape[1], b.shape[1])
    route = choose_route(plan, kind, mode, shape=shape)
    rec = obs.op_start(kind, shape, route, plan, a, b)
    if route == "pallas":
        out = _pallas_matmul(a, b, plan)
    else:
        out = ozaki2.emulated_matmul(a, b, plan,
                                     out_dtype=backend.working_float())
    return obs.op_end(rec, out)


def dot(x: jax.Array, w: jax.Array, plan: Optional[ozaki2.Plan] = None,
        payload_bits: int = 53, substrate: str = "int8",
        mode: Optional[str] = None) -> jax.Array:
    """(..., k) x (k, n) emulated dot — the shape contract of ``Policy.dot``."""
    lead = x.shape[:-1]
    out = matmul(x.reshape((-1, x.shape[-1])), w, plan=plan,
                 payload_bits=payload_bits, substrate=substrate, mode=mode)
    return out.reshape(lead + (w.shape[-1],))


def spmv(a_val: jax.Array, a_col: jax.Array, x: jax.Array,
         plan: Optional[ozaki2.Plan] = None, out_rep: str = "f64",
         br: Optional[int] = None, mode: Optional[str] = None,
         offsets: Optional[Tuple[int, ...]] = None) -> jax.Array:
    """Emulated Blocked-ELL SpMV y = A x through the dispatch layer.

    a_val: (M, bw) padded per-row nonzero values, a_col: (M, bw) int32 column
    indices, x: (N,).  Same contract as ``matmul``: the plan resolves from the
    process cache (k = bw, stencil/SpMV margin), the route follows
    ``choose_route(plan, "spmv_bell", mode)``, and the two routes are
    bit-identical — the fused kernel pads M up to the row-block internally and
    unpads before returning, with all-zero padded rows contributing nothing.

    ``offsets`` (``spmv_formats.band_offsets`` of the operator) lets the
    pallas route read x by static shifts instead of a gather; without them
    the pallas kernel gathers x itself from VMEM where
    ``ozaki_spmv.x_in_vmem`` holds, and XLA gathers it otherwise.  The xla
    route keeps its gather as the independent reference.  The ``spmv_band``
    and ``spmv_vmem`` cache tallies count a hit for each recorded call that
    takes the shifts or the in-kernel gather.
    """
    if plan is None:
        plan = get_plan(a_val.shape[1], margin_bits=4)
    route = choose_route(plan, "spmv_bell", mode, shape=a_val.shape)
    rec = obs.op_start("spmv_bell",
                       (a_val.shape[0], a_val.shape[1], x.shape[0]),
                       route, plan, a_val, a_col, x)
    if route == "pallas":
        if br is None:
            br = int(get_tuning("spmv_bell", a_val.shape).get("br", 128))
        out = ozaki_spmv.spmv_bell(
            a_val, a_col, x, plan, out_rep=out_rep, br=br,
            interpret=pallas_interpret("spmv_bell"), offsets=offsets)
    else:
        out = ozaki_spmv.spmv_bell_ref(a_val, a_col, x, plan, out_rep=out_rep)
    if rec is not None:
        obs.record_cache("spmv_band", route == "pallas" and offsets is not None)
        obs.record_cache("spmv_vmem", route == "pallas" and offsets is None
                         and ozaki_spmv.x_in_vmem(x.shape[0], a_val.shape[1], br))
    return obs.op_end(rec, out)


def stencil7(u: jax.Array, c: jax.Array, plan: Optional[ozaki2.Plan] = None,
             out_rep: str = "f64", bx: Optional[int] = None,
             mode: Optional[str] = None) -> jax.Array:
    """Emulated 7-point stencil v = S[c] u through the dispatch layer.

    u: (X, Y, Z) grid, c: (7,) coefficients ordered
    [centre, -x, +x, -y, +y, -z, +z]; boundary points see a zero halo.  The
    route follows ``choose_route(plan, "stencil7", mode)``: the fused Pallas
    kernel (x-axis blocked, padded and unpadded internally) vs the
    bit-identical jnp reference ``ozaki_stencil.stencil7_ref``.
    """
    if plan is None:
        plan = get_plan(8, margin_bits=4)
    route = choose_route(plan, "stencil7", mode, shape=u.shape)
    rec = obs.op_start("stencil7", u.shape, route, plan, u, c)
    if route == "pallas":
        if bx is None:
            bx = int(get_tuning("stencil7", u.shape).get("bx", 1))
        out = ozaki_stencil.stencil7(u, c, plan, out_rep=out_rep, bx=bx,
                                     interpret=pallas_interpret("stencil7"))
    else:
        out = ozaki_stencil.stencil7_ref(u, c, plan, out_rep=out_rep)
    return obs.op_end(rec, out)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              mask: Optional[jax.Array] = None, softcap: float = 0.0,
              plan_qk: Optional[ozaki2.Plan] = None,
              plan_pv: Optional[ozaki2.Plan] = None,
              payload_bits: int = 53, substrate: str = "int8",
              mode: Optional[str] = None) -> jax.Array:
    """Emulated attention out = softmax(mask(QKᵀ/√D + softcap)) V.

    q: (..., S, D) queries, k/v: (..., T, D) keys/values; leading dims (batch,
    heads, ...) are flattened and mapped.  ``mask`` is None (attend to all),
    a shared (S, T) array, or batched (..., S, T); nonzero/True = attend.
    ``softcap`` > 0 applies the tanh logit cap between scaling and masking
    (the models' score order).  Returns working-float (..., S, D).

    Both routes run one FlashAttention-style online-softmax scan over kv
    blocks (``ozaki_attention``).  Routing follows ``choose_route(plan_qk,
    "attention", mode)``: the pallas route computes each block's QKᵀ and PV
    on the Pallas GEMM/GEMV kernels (``_pallas_matmul``); the xla route,
    bit-identical, on ``ozaki2.emulated_matmul``.  Each block's scores and
    probabilities go through HBM on both.  ``plan_qk`` covers the length-D score
    contraction, ``plan_pv`` the length-bkv probability-value contraction;
    both resolve from the plan cache when omitted.  Telemetry records the
    op with a ``prefill`` (S > 1) or ``decode`` (S = 1) label so the two
    serving shape classes stay distinguishable in the measured-vs-TME table.
    """
    lead = q.shape[:-2]
    S, D = q.shape[-2:]
    T = k.shape[-2]
    B = 1
    for d in lead:
        B *= int(d)
    tune = get_tuning("attention", (B, S, D, T))
    bkv = min(_round_up(int(tune.get("bkv", 128)), SUBLANE),
              _round_up(T, SUBLANE))
    if plan_qk is None:
        plan_qk = get_plan(D, payload_bits, substrate)
    if plan_pv is None:
        plan_pv = get_plan(bkv, payload_bits, substrate)
    route = choose_route(plan_qk, "attention", mode, shape=(B, S, D, T))
    rec = obs.op_start("attention", (B, S, D, T), route, plan_qk, q, k, v,
                       label="decode" if S == 1 else "prefill")
    wf = backend.working_float()
    if mask is None:
        mask = jnp.ones((S, T), jnp.int8)
    if mask.ndim == 2:
        mask = jnp.broadcast_to(mask.astype(jnp.int8), (B, S, T))
    else:
        mask = mask.astype(jnp.int8).reshape(B, S, T)
    qf = q.astype(wf).reshape(B, S, D)
    kf = k.astype(wf).reshape(B, T, D)
    vf = v.astype(wf).reshape(B, T, D)
    if route == "pallas":
        def one(args):
            qi, ki, vi, mi = args
            return ozaki_attention.attention_pallas_gemms(
                qi, ki, vi, mi, plan_qk, plan_pv, _pallas_matmul,
                softcap=softcap, bkv=bkv, out_dtype=wf)
    else:
        def one(args):
            qi, ki, vi, mi = args
            return ozaki_attention.attention_ref(
                qi, ki, vi, mi, plan_qk, plan_pv, softcap=softcap, bkv=bkv,
                out_dtype=wf)
    out = jax.lax.map(one, (qf, kf, vf, mask))
    return obs.op_end(rec, out.reshape(lead + (S, D)))
