"""kernel_ms.spmv: device milliseconds per CG iteration of the fused
Blocked-ELL SpMV kernel (``kernels/ozaki_spmv.py``).  The Mosaic custom
call is named after the jitted function around its ``pallas_call``:
``spmv_bell.<n>``."""

KERNEL = "spmv_bell"


def read(ctx):
    return ctx.kernel_ms(KERNEL)
