"""The program's ``net.residual`` spans per frame of the window: the
float64 shortcut adds and ReLUs after each residual join
(``NetworkSimulator._exec_stage``)."""
UNIT = "ms"


def read(ctx):
    secs = ctx.spans.get("net.residual")
    if not secs or not ctx.frames:
        return None
    return sum(secs) / ctx.frames * 1e3
