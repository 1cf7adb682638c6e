"""The program's ``engine.draw`` spans (a layer's weight-cell
perturbation and per-subarray ADC parameter draw) per Monte-Carlo trial
of the window.  Every handle rebuild counts: each trial's swap, and the
zero-variation and restoring swaps each sweep makes besides its trials."""
UNIT = "ms"


def read(ctx):
    secs = ctx.spans.get("engine.draw")
    if not secs or not ctx.trials:
        return None
    return sum(secs) / ctx.trials * 1e3
