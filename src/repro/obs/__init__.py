"""Observability, in two parts.

Telemetry, on the host clock: ``repro.obs.telemetry`` records per-op events
at the dispatch seam, the compensated reductions, the iterative solvers, and
the serving engine; ``repro.obs.report`` turns the counters into the
measured-vs-TME-predicted table (``python -m repro.obs.report``).  Controlled
by ``REPRO_TELEMETRY=off|counters|trace`` or ``telemetry_scope(...)``.

Spans, on the profiler's clock: ``repro.obs.spans`` names the emulation's
phases inside the compiled programs (``scope``) and the solver loop's
iterations and host syncs (``span``), for readers of a profiler trace.
"""

from repro.obs.telemetry import (  # noqa: F401
    ENV_VAR,
    MODES,
    TRACE_CAP,
    OpEvent,
    cache_snapshot,
    counters_snapshot,
    enabled,
    get_mode,
    op_end,
    op_start,
    probe,
    record_cache,
    record_event,
    reset,
    set_mode,
    snapshot,
    telemetry_scope,
    trace_snapshot,
    write_json,
)
from repro.obs.spans import SCOPES, SPANS, scope, span  # noqa: F401
