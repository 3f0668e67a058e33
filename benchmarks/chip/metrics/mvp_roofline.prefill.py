"""mvp_roofline.prefill (%): as mvp_roofline.decode, over the
projection launches of the prefill batches in the traced window."""


def read(ctx):
    return ctx.red.roofline("prefill", ctx.peaks)
