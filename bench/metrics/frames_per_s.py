"""Simulated frames completed in the window over its wall time."""
UNIT = "frames/s"


def read(ctx):
    return ctx.frames / ctx.window_s if ctx.window_s > 0 else None
