"""Monte-Carlo trials completed in the window over its wall time."""
UNIT = "trials/s"


def read(ctx):
    return ctx.trials / ctx.window_s if ctx.window_s > 0 else None
