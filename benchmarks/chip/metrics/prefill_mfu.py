"""prefill_mfu (%): useful model operations of the window's prefill
batches (real prompt tokens only, causal attention, one LM head row per
prompt) over the prefill calls' seconds times the chip's int8 peak."""


def read(ctx):
    calls = ctx.calls("prefill")
    secs = sum(c.t1 - c.t0 for c in calls)
    if not calls or secs <= 0:
        return None
    return 100.0 * sum(c.useful for c in calls) / (
        secs * ctx.peaks["int8_op_s"])
