"""Process start to the start of the window: weights, planning and
trace lowering, calibration, compilation or cache loads, warm-up."""
UNIT = "s"


def read(ctx):
    return ctx.setup_s
