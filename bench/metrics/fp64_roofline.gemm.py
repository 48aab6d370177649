"""fp64_roofline.gemm: least time of one call's FP64 work (``dgemm_ref.work``)
on this chip over the device's busy time per call, in percent."""


def read(ctx):
    return ctx.roofline_pct()
