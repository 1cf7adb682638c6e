"""Window time inside ``TraceExecutor.run`` (quantize, host->device,
jitted step or kernel, device->host, ``_tail_np``) per frame."""
from bench.readings import wrapped_ms

UNIT = "ms"


def read(ctx):
    return wrapped_ms(ctx, "trace_exec", ctx.frames)
