"""The program's ``te.pad`` spans per frame of the window: the trace
executor's fill of each padded input raster (``TraceExecutor.run``)."""
UNIT = "ms"


def read(ctx):
    secs = ctx.spans.get("te.pad")
    if not secs or not ctx.frames:
        return None
    return sum(secs) / ctx.frames * 1e3
