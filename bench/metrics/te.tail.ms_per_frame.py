"""The program's ``te.tail`` spans per frame of the window: each block
tail on the host, dequantization, activation and pooling
(``TraceExecutor._tail_np``)."""
UNIT = "ms"


def read(ctx):
    secs = ctx.spans.get("te.tail")
    if not secs or not ctx.frames:
        return None
    return sum(secs) / ctx.frames * 1e3
