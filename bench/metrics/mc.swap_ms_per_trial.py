"""The program's ``engine_swap`` span (device-variation swap of every
engine handle) per Monte-Carlo trial of the window."""
UNIT = "ms"


def read(ctx):
    swaps = ctx.spans.get("engine_swap")
    if not swaps or not ctx.trials:
        return None
    return sum(swaps) / ctx.trials * 1e3
