"""The whole step's share of the chip's int8 peak
(``readings.mfu_int8_pct``); stream cells."""
from bench.readings import mfu_int8_pct

UNIT = "%"


def read(ctx):
    return mfu_int8_pct(ctx)
