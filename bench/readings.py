"""Arithmetic shared by the per-layer readers (``bench/metrics/``): a
cell family's readers differ only in which end-to-end metric they move."""
from __future__ import annotations

import sys
from typing import Optional

from bench.roofline import kernel_work, roofline

#: the kernel's events on the device's op line: the Pallas call that
#: ``kernels/cim_matmul.py::cim_matmul_pallas`` lowers to
KERNEL = ("cim_matmul_pallas", "custom-call", "tpu_custom_call")


def wrapped_ms(ctx, label: str, per: int) -> Optional[float]:
    """Window milliseconds inside the ``label`` wrapper, per ``per``."""
    secs = ctx.wrap.get(label)
    if not secs or not per:
        return None
    return secs / per * 1e3


def kernel_roofline_pct(ctx) -> Optional[float]:
    """The CIM kernel's share of its roofline over the traced calls: the
    least time the chip could take for the int8 ops and bytes the
    model's layers need (from the shapes) over the device time of the
    kernel's events."""
    tr = ctx.trace
    if tr is None or not ctx.peaks or not ctx.traced_frames:
        return None
    secs = tr.op_seconds(KERNEL)
    if secs <= 0:
        return None
    ops, nbytes = kernel_work(ctx.layers, ctx.traced_frames,
                              ctx.traced_calls * ctx.runs_per_call)
    pct, bound = roofline(ops, nbytes, secs, ctx.peaks)
    print(f"bench: cim_mac {ops!r} ops, {nbytes!r} bytes, {secs!r} s of "
          f"kernel: {bound}-bound", file=sys.stderr)
    return pct


def idle_pct(ctx) -> Optional[float]:
    """Share of the traced window in which no operation ran on the
    device."""
    tr = ctx.trace
    if tr is None or tr.window_s <= 0 or not tr.devices:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)


def mfu_int8_pct(ctx) -> Optional[float]:
    """The whole step's share of the chip's int8 peak: simulated frames
    per second of the window times 2 x the model's MACs per frame."""
    if not ctx.peaks or ctx.window_s <= 0:
        return None
    ops = 2.0 * ctx.spec.cfg["macs_per_frame"] * ctx.frames / ctx.window_s
    return 100.0 * ops / ctx.peaks["int8_ops_per_s"]
