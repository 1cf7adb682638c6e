"""The program's ``net.fc`` spans per frame of the window: each FC
layer's flatten (or mean) and its tile grid (``simulate_fc``), in
``NetworkSimulator._exec_stage``."""
UNIT = "ms"


def read(ctx):
    secs = ctx.spans.get("net.fc")
    if not secs or not ctx.frames:
        return None
    return sum(secs) / ctx.frames * 1e3
