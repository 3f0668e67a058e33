"""device_idle_share.scheduler (%): device idle time of the traced window
that falls inside a ``tick`` span but outside its executor calls
(``prefill_batch``, ``decode_step``, ``spec_round``), over the window's
length: the scheduler's part of ``device_idle_share``. Idle stretches
are those of ``device_idle_share`` (first device), put on the host's
clock as ``spans.Window`` takes the offset. None when the program writes
no ``tick`` span. The harness waits for a decode step's tokens inside
``decode_step`` (not inside ``decode.wait``), which whole executor-call
spans take out either way."""
import spans


def read(ctx):
    w = spans.for_context(ctx)
    return None if w is None else w.scheduler_idle_share()
