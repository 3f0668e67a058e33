"""kv_write_share.decode (%): device time of the decode programs' ops
whose scope path holds ``kv_write`` (every KV cache and position write),
over the decode programs' op time, ops that only contain other ops left
out. A decode program is a device program whose middle falls inside a
``decode_step`` span (``spans.Window.programs``). None when the program
writes no spans or no decode op carries the scope."""
import spans


def read(ctx):
    w = spans.for_context(ctx)
    return None if w is None else w.scope_share("kv_write", "decode_step")
