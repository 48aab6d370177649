"""Names on the profiler's clock: device scopes and host spans.

``repro.obs.telemetry`` tallies whole dispatched ops on the host clock and
cannot see inside a compiled program.  This module names the phases of the
emulation where the profiler can see them, on the same clock as the
device's operations:

  * ``scope(name)`` is ``jax.named_scope(name)``: every op traced under it
    carries ``name`` in its HLO ``op_name`` metadata, which a profiler trace
    keeps with the program's HLO.  It costs nothing at run time and leaves the
    compiled program as it was.  Use it inside a jitted function: a scope
    around an eager call into a jitted function does not reach that
    function's program.  Wrap statements where they stand, even if one
    scope then opens twice: reordering the traced ops renumbers the compiled
    program's instructions, and a device trace names its ops by them.
  * ``span(name)`` is ``jax.profiler.TraceAnnotation("repro." + name)``: a
    host span, a few hundred nanoseconds when no profiler is recording.  The
    profiler is the switch; there is no mode.

``SCOPES`` and ``SPANS`` are the one table of names: the program takes its
names from it, and readers of a trace look them up in it.
"""

from __future__ import annotations

import jax

PREFIX = "repro."

# Device scopes (HLO op_name path segments).
SCOPES = (
    "ozaki.split_a",   # Phase-1 scaling, hi/lo split, padding of the matrix operand
    "ozaki.split_b",   # the same for the other operand (B, x, the stencil's u)
    "spmv.gather",     # x's hi/lo gathered (banded: shifted) to the ELL slots, for the kernel;
                       # where the kernel gathers from VMEM, a_col's transpose and x's reshape
    "ozaki.finish",    # digits -> working float, and the exact unscale
    "reduce.dot2",     # the compensated sum's block tree and carry scan
)

# Host spans, recorded as PREFIX + name.
SPANS = (
    "cg.iter",         # one CG iteration of the host loop
    "sync",            # one device-to-host read that the host loop waits on
    "npb.outer",       # one outer step of NPB CG's inverse power iteration
)


def scope(name: str) -> jax.named_scope:
    """Name the ops traced under it ``name`` in the HLO metadata."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; known: {SCOPES}")
    return jax.named_scope(name)


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span ``repro.<name>`` on the profiler's clock."""
    if name not in SPANS:
        raise ValueError(f"unknown span {name!r}; known: {SPANS}")
    return jax.profiler.TraceAnnotation(PREFIX + name)
