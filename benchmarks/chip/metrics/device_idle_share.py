"""device_idle_share (%): 1 - union of the device's busy intervals over
the traced window (profiler trace, first device)."""


def read(ctx):
    if ctx.red.busy_s <= 0:
        return None
    return 100.0 * ctx.red.idle_share
