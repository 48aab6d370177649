"""Reader of the program's own host spans in a reduced trace.

The program names its host work with ``jax.profiler.TraceAnnotation`` spans
``repro.<name>`` (``repro.obs.spans``), on the profiler's clock like the
benchmark's ``bench.*`` spans.  These functions read them from a
``bench.trace.Trace``: the spans on the thread that ran the window, clipped
to the window.  A trace of a program that records no such span reads 0.

Times are in seconds.
"""

from __future__ import annotations

from typing import List, Tuple

CG_ITER = "repro.cg.iter"   # one iteration of the CG host loop (hpc/cg.py)
SYNC = "repro.sync"         # one device-to-host read the loop waits on


def _spans(trace, name: str) -> List[Tuple[float, float]]:
    out = []
    for ev in trace.host.get(trace.thread, ()):
        if ev.name == name:
            s, e = max(ev.start, trace.lo), min(ev.end, trace.hi)
            if e > s:
                out.append((s, e))
    return out


def span_count(trace, name: str) -> int:
    """Number of ``name`` spans in the window."""
    return len(_spans(trace, name))


def span_s(trace, name: str) -> float:
    """Summed duration of the ``name`` spans in the window."""
    return sum(e - s for s, e in _spans(trace, name)) * 1e-9


def span_self_s(trace, name: str, child: str) -> float:
    """Summed duration of the ``name`` spans less the part of each that
    ``child`` spans cover."""
    children = sorted(_spans(trace, child))
    total = 0.0
    for s, e in _spans(trace, name):
        covered, reach = 0.0, s
        for cs, ce in children:
            cs, ce = max(cs, reach), min(ce, e)
            if ce > cs:
                covered += ce - cs
                reach = ce
        total += (e - s) - covered
    return total * 1e-9
