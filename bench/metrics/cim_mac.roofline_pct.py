"""The CIM kernel's share of its roofline over the traced calls
(``readings.kernel_roofline_pct``); stream cells."""
from bench.readings import kernel_roofline_pct

UNIT = "%"


def read(ctx):
    return kernel_roofline_pct(ctx)
