"""The program's ``te.fetch`` spans per frame of the window: the wait
for each jitted step, the copy of its code sums to the host and their
widening to float64 (``TraceExecutor._run_jax_quant``)."""
UNIT = "ms"


def read(ctx):
    secs = ctx.spans.get("te.fetch")
    if not secs or not ctx.frames:
        return None
    return sum(secs) / ctx.frames * 1e3
