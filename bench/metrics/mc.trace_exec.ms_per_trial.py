"""Window time inside ``TraceExecutor.run`` per Monte-Carlo trial (the
sweep's nominal and zero-variation runs included)."""
from bench.readings import wrapped_ms

UNIT = "ms"


def read(ctx):
    return wrapped_ms(ctx, "trace_exec", ctx.trials)
