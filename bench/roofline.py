"""The chip's peaks and the work the CIM kernel must do.

Peaks are keyed by ``device_kind`` (``peaks.json``, with its source);
a kind that is not in the table is an error, never a default.  The
kernel's required work is counted from the layer shapes, not from the
padded grid it runs: per conv layer ``E*F*M*C*K^2`` int8 MACs, and as
bytes the int8 patches (``E*F*C*K^2`` a frame), the int8 weights (once
a call) and the f32 code sums (``E*F*M`` a frame); an FC layer counts
its input, weights and outputs alike.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Sequence, Tuple

from bench.reference import Layer

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(kind: str) -> Dict[str, float]:
    table = json.loads(PEAKS_FILE.read_text())["kinds"]
    if kind not in table:
        raise KeyError(f"no peaks known for device kind {kind!r} "
                       f"(known: {sorted(table)})")
    return table[kind]


def kernel_work(layers: Sequence[Layer], frames: int, calls: int
                ) -> Tuple[float, float]:
    """(int8 ops, bytes) the kernel needs for ``frames`` frames served
    in ``calls`` calls."""
    ops = byt = 0.0
    for layer in layers:
        if layer.kind == "fc":
            rows, k_dim, m = 1, layer.c_in, layer.c_out
        else:
            rows = layer.e * layer.f
            k_dim, m = layer.c * layer.k * layer.k, layer.m
        ops += 2.0 * frames * rows * k_dim * m
        byt += frames * rows * k_dim + calls * k_dim * m \
            + 4.0 * frames * rows * m
    return ops, byt


def roofline(ops: float, nbytes: float, seconds: float,
             peak: Dict[str, float]) -> Tuple[float, str]:
    """(percent of the least time the chip could take, bound)."""
    t_ops = ops / peak["int8_ops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (100.0 * max(t_ops, t_mem) / seconds,
            "compute" if t_ops >= t_mem else "memory")
