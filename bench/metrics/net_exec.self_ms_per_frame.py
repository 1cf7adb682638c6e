"""Entry-call time outside the trace executor and the variation swap,
per frame: the network executor's and the front end's own host work
(accounting and timing replays, host FC, residual adds)."""
UNIT = "ms"


def read(ctx):
    inner = ctx.wrap.get("trace_exec")
    if inner is None or not ctx.frames:
        return None
    own = sum(ctx.call_s) - inner - ctx.wrap.get("set_variation", 0.0)
    return own / ctx.frames * 1e3
