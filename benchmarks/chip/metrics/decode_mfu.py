"""decode_mfu (%): useful model operations of the window's decode steps
(projections and LM head for occupied rows, attention over each row's
live context) over the decode steps' seconds (the harness's spans around
the executor's decode) times the chip's int8 peak."""


def read(ctx):
    calls = ctx.calls("decode")
    secs = sum(c.t1 - c.t0 for c in calls)
    if not calls or secs <= 0:
        return None
    return 100.0 * sum(c.useful for c in calls) / (
        secs * ctx.peaks["int8_op_s"])
