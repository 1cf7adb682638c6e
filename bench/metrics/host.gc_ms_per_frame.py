"""The program's ``gc`` spans (the Python garbage collections that ran
while its profiler was installed) per frame of the window: 0 where
none ran, None where the program does not record collections."""
UNIT = "ms"


def read(ctx):
    from repro.telemetry.spans import Profiler

    if not hasattr(Profiler, "_on_gc") or not ctx.frames:
        return None
    return sum(ctx.spans.get("gc", ())) / ctx.frames * 1e3
