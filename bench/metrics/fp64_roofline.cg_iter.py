"""fp64_roofline.cg_iter: least time of one CG iteration's FP64 work
(``cg_poisson7_ref.work``) on this chip over the device's busy time per
iteration, in percent."""


def read(ctx):
    return ctx.roofline_pct()
