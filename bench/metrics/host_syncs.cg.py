"""host_syncs.cg: device-to-host reads the CG host loop waits on, per
iteration: the ``repro.sync`` spans of ``hpc/cg.py`` in the window over the
iterations completed (one before a set's loop and one an iteration read
1.02 at 50 iterations a set).  None where the program records no such
span."""

from bench import spans


def read(ctx):
    n = spans.span_count(ctx.trace, spans.SYNC)
    return n / ctx.units if n and ctx.units else None
