"""kernel_ms.gemm: device milliseconds per call of the fused GEMM kernel
(``kernels/ozaki_gemm.py``), summed over its operations in the trace.  The
Mosaic custom call is named after the jitted function around its
``pallas_call``: ``gemm_hilo.<n>``."""

KERNEL = "gemm_hilo"


def read(ctx):
    return ctx.kernel_ms(KERNEL)
