"""The program's ``net.account`` spans per frame of the window: the
streaming executor's per-frame accounting replay
(``NetworkSimulator.run_stream``)."""
UNIT = "ms"


def read(ctx):
    secs = ctx.spans.get("net.account")
    if not secs or not ctx.frames:
        return None
    return sum(secs) / ctx.frames * 1e3
