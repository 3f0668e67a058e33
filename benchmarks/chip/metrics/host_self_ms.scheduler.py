"""host_self_ms.scheduler (ms): mean over the window's ``tick`` spans of
the tick's length less the parts its executor calls (``prefill_batch``,
``decode_step``, ``spec_round``) cover: host time per tick spent in the
scheduler's own code (admission, ``decode.prepare``, ``decode.retire``).
Read from the program's spans in the profiler trace (``spans.py``); None
when the program writes no ``tick`` span. The harness waits for a decode
step's tokens inside ``decode_step`` (not inside ``decode.wait``), which
whole executor-call spans take out either way."""
import spans


def read(ctx):
    w = spans.for_context(ctx)
    return None if w is None else w.host_self_ms()
