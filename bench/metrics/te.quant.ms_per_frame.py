"""The program's ``te.quant`` spans per frame of the window: the
engine's activation quantization of each padded stream and its int8
cast (``TraceExecutor``'s quantized paths)."""
UNIT = "ms"


def read(ctx):
    secs = ctx.spans.get("te.quant")
    if not secs or not ctx.frames:
        return None
    return sum(secs) / ctx.frames * 1e3
