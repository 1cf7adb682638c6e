"""Seconds JAX spent compiling programs or loading them from the
persistent cache before the window."""
UNIT = "s"


def read(ctx):
    return ctx.compile_setup_s
