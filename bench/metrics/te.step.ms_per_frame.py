"""The program's ``te.step`` spans per frame of the window: each jitted
trace step up to the return of its dispatch, which copies the step's
numpy operands to the device (``TraceExecutor._run_jax_quant``)."""
UNIT = "ms"


def read(ctx):
    secs = ctx.spans.get("te.step")
    if not secs or not ctx.frames:
        return None
    return sum(secs) / ctx.frames * 1e3
