"""xla_ms.gemm: device milliseconds per call outside the fused GEMM kernel: the
XLA prologue (Phase-1 scale and split) and epilogue (``common.finish``)."""

KERNEL = "gemm_hilo"  # the fused GEMM kernel's custom call, gemm_hilo.<n>


def read(ctx):
    return ctx.xla_ms(KERNEL)
