"""The program's ``engine.quantize_w`` spans (int8 quantization of a
layer's float weights) per Monte-Carlo trial of the window.  Every
handle rebuild counts: each trial's swap, and the zero-variation and
restoring swaps each sweep makes besides its trials."""
UNIT = "ms"


def read(ctx):
    secs = ctx.spans.get("engine.quantize_w")
    if not secs or not ctx.trials:
        return None
    return sum(secs) / ctx.trials * 1e3
