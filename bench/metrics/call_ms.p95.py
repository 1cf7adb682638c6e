"""95th percentile of the wall time of one entry call, over every call
of the window."""
import numpy as np

UNIT = "ms"


def read(ctx):
    if not ctx.call_s:
        return None
    return float(np.percentile(np.asarray(ctx.call_s), 95.0)) * 1e3
