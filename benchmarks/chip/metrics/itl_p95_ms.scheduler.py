"""itl_p95_ms.scheduler (ms): the 95th percentile of the window's
consecutive-token gaps, as ``tails.window_metrics`` takes it. In a
backlog it lies where the scheduler's prefill stalls meet the plain
decode gaps, so a single host stall moves it; it is read here, beside
``prefill_stall_share``, and not held to a bound."""


def read(ctx):
    return ctx.e2e.get("itl_p95_ms")
