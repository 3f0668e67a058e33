"""mvp_roofline.decode (%): over the projection launches of the decode
steps in the traced window, the roofline time of their work (the larger
of int8 operations over the chip's int8 peak and bytes over its memory
bandwidth, per launch) over their device time. Fails loudly when a
decode step ran fewer launches than the configuration has projections."""


def read(ctx):
    return ctx.red.roofline("decode", ctx.peaks)
