"""xla_ms.cg: device milliseconds per CG iteration outside the fused SpMV
kernel: the SpMV's gather, split and ``finish``, the compensated dots and
the axpys."""

KERNEL = "spmv_bell"  # the fused SpMV kernel's custom call, spmv_bell.<n>


def read(ctx):
    return ctx.xla_ms(KERNEL)
