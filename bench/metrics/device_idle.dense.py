"""device_idle.dense: share of the window in which the chip ran nothing, in
the dense cells (one closed-loop caller of ``dispatch.matmul``)."""


def read(ctx):
    return ctx.idle_pct()
