"""prefill_stall_share (%): share of the window's consecutive-token gaps
during which a prefill batch ran (the server's ``lm_prefill_batches``
counter read by the harness after every tick)."""


def read(ctx):
    return ctx.e2e.get("prefill_stall_share")
