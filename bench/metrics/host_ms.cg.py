"""host_ms.cg: host milliseconds per CG iteration outside the loop's reads:
the ``repro.cg.iter`` spans of ``hpc/cg.py`` less the ``repro.sync`` reads
inside them.  That is the iteration's eager dispatches, and any wait in a
dispatch while the runtime's launch queue is full (the device behind the
host); once the device keeps up, the floor of ``cg_iter_ms``.  None where
the program records no such span."""

from bench import spans


def read(ctx):
    return ctx.per_unit_ms(spans.span_self_s(ctx.trace, spans.CG_ITER, spans.SYNC))
