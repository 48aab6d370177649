"""host_ms.npb_outer: host milliseconds per CG iteration of NPB CG's outer
step outside its CG iterations: the ``repro.npb.outer`` spans of
``hpc/npb_cg.py`` less the ``repro.cg.iter`` spans inside them.  That is the
solve's set-up and first read, the rnorm SpMV, the zeta dot, the norms and
the normalisation, dispatched once every 25 iterations.  None where the
program records no such span."""

from bench import spans

NPB_OUTER = "repro.npb.outer"   # one outer step of NPB CG (hpc/npb_cg.py)


def read(ctx):
    return ctx.per_unit_ms(spans.span_self_s(ctx.trace, NPB_OUTER, spans.CG_ITER))
