"""Seam telemetry — live measured-vs-TME tracing for the dispatch layer.

The paper's central claim is falsifiable *by instrument*: the TME model
(``repro.core.tme``, eqs. 8–9) predicts emulated-FP64 time from (α, β, γ),
and every emulated multiplication in this repo already routes through one
seam (``repro.core.dispatch``).  This module records what actually happens at
that seam — so "measured vs TME-predicted" is a continuously collected
quantity, not a hand-run benchmark.

What gets recorded per dispatched op (``op_start``/``op_end`` around the
route execution): kind, shape-class, chosen route, the plan's r and
payload_bits, wall time (``jax.block_until_ready``-fenced), the derived
FLOPs/bytes of the FP64-equivalent op, and the TME-predicted time for the
same op on the reference chip (``tme.default_chip``, $REPRO_TME_CHIP).
Plan/tuning cache hits and misses are counted separately (``record_cache``),
and free-form events (solver residual traces, serving step latencies) ride
the same stream via ``record_event``.

Storage is two-tier, selected by ``REPRO_TELEMETRY=off|counters|trace`` (or
the ``telemetry_scope(...)`` context manager / ``set_mode``, mirroring
``dispatch.mode_scope``):

  * **counters** — per-(kind, shape-class, route) aggregates: call count,
    total/min/max wall μs, total FLOPs/bytes, total TME-predicted μs.  O(1)
    memory regardless of run length.
  * **trace** — counters *plus* a bounded ring buffer (``TRACE_CAP`` most
    recent events) for post-hoc inspection; old events fall off the end.

Two invariants the instrumented call-sites rely on:

  * **Tracer-safe** — ``op_start`` returns ``None`` (and ``record_event``
    no-ops) when any operand is a ``jax.core.Tracer``: instrumented entry
    points still jit, and a traced call records nothing (there is no wall
    time to measure inside a trace anyway).  Recording never adds ops to a
    jaxpr.
  * **Zero-overhead when off** — the off path is one thread-local/env lookup
    per call; no timing fence, no allocation, no lock.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

from repro.core import tme

MODES = ("off", "counters", "trace")
ENV_VAR = "REPRO_TELEMETRY"

# Ring-buffer capacity in trace mode (most recent events win).
TRACE_CAP = 4096

_tls = threading.local()
_lock = threading.Lock()

# (kind, shape_class, route) -> mutable aggregate dict.
_counters: Dict[Tuple[str, str, str], Dict[str, float]] = {}
# cache name ("plan" | "tune") -> [hits, misses]
_caches: Dict[str, List[int]] = {}
_trace: deque = deque(maxlen=TRACE_CAP)


@dataclasses.dataclass(frozen=True)
class OpEvent:
    """One recorded event.  Dispatch ops fill every field; free-form events
    (``record_event``) carry their payload in ``extra`` and may leave the
    plan/cost fields at zero."""
    kind: str
    shape_class: str
    route: str
    r: int
    payload_bits: int
    us: float                  # measured wall time (block_until_ready-fenced)
    flops: float               # W of the FP64-equivalent op
    bytes: float               # Q of the FP64-equivalent op
    tme_us: float              # TME-predicted time for the same op
    label: str = ""
    extra: Tuple[Tuple[str, Any], ...] = ()


# ---------------------------------------------------------------------------
# Mode resolution (mirrors dispatch.mode_scope)
# ---------------------------------------------------------------------------

def _validate_mode(mode: str) -> str:
    if mode not in MODES:
        raise ValueError(f"telemetry mode must be one of {MODES}, got {mode!r}")
    return mode


def get_mode() -> str:
    """Effective telemetry mode: programmatic override, else env, else off."""
    override = getattr(_tls, "mode", None)
    if override is not None:
        return override
    return _validate_mode(os.environ.get(ENV_VAR, "off"))


def set_mode(mode: Optional[str]) -> None:
    """Set (or with None, clear) this thread's telemetry-mode override."""
    _tls.mode = None if mode is None else _validate_mode(mode)


@contextlib.contextmanager
def telemetry_scope(mode: Optional[str]):
    """Temporarily force a telemetry mode (None = inherit the ambient mode)."""
    prev = getattr(_tls, "mode", None)
    set_mode(mode if mode is not None else prev)
    try:
        yield
    finally:
        _tls.mode = prev


def enabled() -> bool:
    """Whether any recording is active.  This is the per-call fast path the
    instrumented seams check first — keep it one lookup, no allocation."""
    mode = getattr(_tls, "mode", None)
    if mode is None:
        mode = os.environ.get(ENV_VAR, "off")
    if mode == "off":
        return False
    _validate_mode(mode)
    return True


# ---------------------------------------------------------------------------
# Recording
# ---------------------------------------------------------------------------

def reset() -> None:
    """Drop all counters, cache tallies, and the trace ring buffer."""
    with _lock:
        _counters.clear()
        _caches.clear()
        _trace.clear()


def _shape_class(dims: Sequence[int]) -> str:
    if not dims:
        return ""
    # Deferred: dispatch imports this module at load time, not vice versa.
    from repro.core.dispatch import shape_class
    return shape_class(dims)


def _record(ev: OpEvent) -> None:
    key = (ev.kind, ev.shape_class, ev.route)
    with _lock:
        agg = _counters.get(key)
        if agg is None:
            agg = _counters[key] = {
                "calls": 0, "us": 0.0, "us_min": float("inf"), "us_max": 0.0,
                "flops": 0.0, "bytes": 0.0, "tme_us": 0.0,
            }
        agg["calls"] += 1
        agg["us"] += ev.us
        agg["us_min"] = min(agg["us_min"], ev.us)
        agg["us_max"] = max(agg["us_max"], ev.us)
        agg["flops"] += ev.flops
        agg["bytes"] += ev.bytes
        agg["tme_us"] += ev.tme_us
        if get_mode() == "trace":
            _trace.append(ev)


def _has_tracer(values) -> bool:
    return any(isinstance(v, jax.core.Tracer) for v in values)


def op_start(kind: str, dims: Sequence[int], route: str, plan=None,
             *operands, label: str = ""):
    """Begin recording one dispatched op; returns an opaque token for
    ``op_end``, or None when recording is off or any operand is a tracer
    (instrumented entry points must stay jit-traceable)."""
    if not enabled():
        return None
    if _has_tracer(operands):
        return None
    return (kind, tuple(int(d) for d in dims), route, plan, label,
            time.perf_counter())


def op_end(token, out):
    """Finish the op begun by ``op_start``: fence with ``block_until_ready``,
    compute derived FLOPs/bytes and the TME prediction, record, and return
    ``out`` (so call-sites can ``return op_end(tok, out)``)."""
    if token is None:
        return out
    if isinstance(out, jax.core.Tracer):  # concrete inputs, traced output
        return out
    kind, dims, route, plan, label, t0 = token
    out = jax.block_until_ready(out)
    us = (time.perf_counter() - t0) * 1e6
    W, Q, n_out = tme.op_costs(kind, dims)
    if plan is not None:
        r, pb = plan.r, plan.payload_bits
        tme_us = tme.predict_op_time(kind, dims, r=r, alpha=float(plan.alpha),
                                     substrate=plan.substrate,
                                     route=route) * 1e6
    else:
        r, pb = 0, 0
        tme_us = tme.predict_op_time(kind, dims, route=route) * 1e6
    _record(OpEvent(kind, _shape_class(dims), route, r, pb, us, W, Q, tme_us,
                    label=label))
    return out


def record_event(kind: str, *, us: float = 0.0, dims: Sequence[int] = (),
                 route: str = "", label: str = "", **extra) -> None:
    """Record a free-form event (solver residuals, serving latencies, queue
    depths).  No TME prediction; tracer-valued payloads are dropped whole."""
    if not enabled():
        return
    if _has_tracer(extra.values()):
        return
    _record(OpEvent(kind, _shape_class(dims), route, 0, 0, float(us),
                    0.0, 0.0, 0.0, label=label,
                    extra=tuple(sorted(extra.items()))))


def record_cache(name: str, hit: bool) -> None:
    """Count a plan/tuning cache lookup (only called when recording is on)."""
    with _lock:
        tally = _caches.setdefault(name, [0, 0])
        tally[0 if hit else 1] += 1


# ---------------------------------------------------------------------------
# Introspection
# ---------------------------------------------------------------------------

def counters_snapshot() -> Dict[Tuple[str, str, str], Dict[str, float]]:
    """Copy of the aggregate counters, keyed (kind, shape_class, route)."""
    with _lock:
        return {k: dict(v) for k, v in _counters.items()}


def cache_snapshot() -> Dict[str, Tuple[int, int]]:
    """Cache tallies: name -> (hits, misses)."""
    with _lock:
        return {k: (v[0], v[1]) for k, v in _caches.items()}


def trace_snapshot() -> List[OpEvent]:
    """Copy of the ring buffer (oldest first; trace mode only fills it)."""
    with _lock:
        return list(_trace)


def snapshot() -> Dict[str, Any]:
    """JSON-serialisable snapshot of everything recorded so far."""
    counters = [
        {"kind": k, "shape_class": cls, "route": route, **agg}
        for (k, cls, route), agg in sorted(counters_snapshot().items())
    ]
    return {
        "mode": get_mode(),
        "chip": tme.default_chip().name,
        "counters": counters,
        "caches": {name: {"hits": h, "misses": m}
                   for name, (h, m) in sorted(cache_snapshot().items())},
        "trace": [dataclasses.asdict(ev) for ev in trace_snapshot()],
    }


def write_json(path: str) -> str:
    """Dump ``snapshot()`` to ``path`` (the CI telemetry artifact)."""
    with open(path, "w") as fh:
        json.dump(snapshot(), fh, indent=2, sort_keys=True)
        fh.write("\n")
    return path


def probe(fn):
    """Run ``fn`` once under trace telemetry and return ``(result, event)``
    where ``event`` is the last dispatched-op event it produced (None if it
    recorded none).  Benchmarks use this to source the route/shape-class CSV
    columns from the telemetry stream rather than re-deriving them."""
    with telemetry_scope("trace"):
        before = len(_trace)
        out = jax.block_until_ready(fn())
        new = list(_trace)[before:]
    for ev in reversed(new):
        if ev.route:
            return out, ev
    return out, None
