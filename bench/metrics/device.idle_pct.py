"""Share of the traced window in which no operation ran on the device;
stream cells."""
from bench.readings import idle_pct

UNIT = "%"


def read(ctx):
    return idle_pct(ctx)
