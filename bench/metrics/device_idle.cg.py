"""device_idle.cg: share of the window in which the chip ran nothing, in the
CG cells; the host loop of ``hpc/cg.py`` syncs once an iteration."""


def read(ctx):
    return ctx.idle_pct()
