"""A profiler trace of part of a run, reduced to what the metrics read.

``Trace`` holds plain tuples so the reduction can be tested on
synthetic events: per device, the operations that ran on it
``(name, start_ns, dur_ns, detail)``, and the host annotations the
benchmark's own wrappers wrote ``(label, start_ns, dur_ns)``.  Device
and host events share one clock in the profiler's trace.
"""
from __future__ import annotations

import glob
import heapq
import os
import shutil
import tempfile
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

#: prefix of the host annotations the benchmark writes
ANNOTATION = "bench:"
#: the device line that holds one event per executed operation
OPS_LINE = "XLA Ops"

Event = Tuple[str, int, int, str]


@dataclass
class Trace:
    devices: Dict[str, List[Event]] = field(default_factory=dict)
    host: List[Tuple[str, int, int]] = field(default_factory=list)
    t0_ns: int = 0
    t1_ns: int = 0

    @property
    def window_s(self) -> float:
        return (self.t1_ns - self.t0_ns) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        if not self.devices:
            return 0.0
        tot = sum(busy_ns(((s, s + d) for _, s, d, _ in evs),
                          self.t0_ns, self.t1_ns)
                  for evs in self.devices.values())
        return tot * 1e-9 / len(self.devices)

    def op_seconds(self, match: Sequence[str] = ()) -> float:
        """Device seconds (summed over devices) of the operations whose
        name or detail holds every string of ``match``."""
        return sum(d for evs in self.devices.values()
                   for name, _, d, detail in evs
                   if all(m in name or m in detail for m in match)) * 1e-9

    def top_ops(self, n: int = 10) -> List[List]:
        tot: Dict[str, int] = {}
        for evs in self.devices.values():
            for name, _, d, _ in evs:
                tot[name] = tot.get(name, 0) + d
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns * 1e-9 / max(1, len(self.devices))]
                for name, ns in top]

    def idle_by_label(self, n: int = 10) -> List[List]:
        """Idle seconds on the devices, each gap split over the host
        annotations (innermost first) that cover it."""
        tot: Dict[str, float] = {}
        for evs in self.devices.values():
            gaps = idle_gaps(((s, s + d) for _, s, d, _ in evs),
                             self.t0_ns, self.t1_ns)
            for label, ns in label_gaps(gaps, self.host).items():
                tot[label] = tot.get(label, 0.0) + ns
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[label, ns * 1e-9 / max(1, len(self.devices))]
                for label, ns in top]


def _merged(intervals: Iterable[Tuple[int, int]], lo: int, hi: int
            ) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def busy_ns(intervals: Iterable[Tuple[int, int]], lo: int, hi: int) -> int:
    """Length of the union of ``intervals`` clipped to ``[lo, hi)``."""
    return sum(e - s for s, e in _merged(intervals, lo, hi))


def idle_gaps(intervals: Iterable[Tuple[int, int]], lo: int, hi: int
              ) -> List[Tuple[int, int]]:
    """The complement of the union of ``intervals`` in ``[lo, hi)``."""
    gaps, cur = [], lo
    for s, e in _merged(intervals, lo, hi):
        if s > cur:
            gaps.append((cur, s))
        cur = e
    if cur < hi:
        gaps.append((cur, hi))
    return gaps


def label_gaps(gaps: Sequence[Tuple[int, int]],
               host: Sequence[Tuple[str, int, int]]) -> Dict[str, float]:
    """Nanoseconds of ``gaps`` under each host annotation: a stretch
    goes to the innermost (latest-starting) annotation covering it,
    and to ``"none"`` where none does."""
    bounds = sorted({b for s, e in gaps for b in (s, e)}
                    | {b for _, s, d in host for b in (s, s + d)})
    anns = sorted(host, key=lambda h: h[1])
    gaps = sorted(gaps)
    active: List[Tuple[int, int, str]] = []   # heap of (-start, end, label)
    out: Dict[str, float] = {}
    ai = gi = 0
    for a, b in zip(bounds, bounds[1:]):
        while ai < len(anns) and anns[ai][1] <= a:
            label, s, d = anns[ai]
            heapq.heappush(active, (-s, s + d, label))
            ai += 1
        while active and active[0][1] < b:
            heapq.heappop(active)
        while gi < len(gaps) and gaps[gi][1] <= a:
            gi += 1
        if gi == len(gaps) or not gaps[gi][0] <= a:
            continue
        label = active[0][2] if active else "none"
        out[label] = out.get(label, 0.0) + (b - a)
    return out


def start() -> str:
    """Start the JAX profiler (no Python tracer) into a fresh temporary
    directory (under ``$TMPDIR``)."""
    import jax

    path = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(path, profiler_options=opts)
    return path


def stop_and_load(path: str) -> Trace:
    """Stop the profiler, read its trace and delete the files."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.stop_trace()
    try:
        files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError("the profiler wrote no trace")
        data = ProfileData.from_file(files[0])
        return reduce_planes(data.planes)
    finally:
        shutil.rmtree(path, ignore_errors=True)


def _detail(ev) -> str:
    try:
        return " ".join(str(v) for _, v in ev.stats)
    except (TypeError, ValueError):
        return ""


def reduce_planes(planes) -> Trace:
    """The device op lines and the benchmark's host annotations; the
    window is the ``window`` annotation the traced calls run in."""
    tr = Trace()
    for plane in planes:
        if plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CPU"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    tr.devices[plane.name] = [
                        (ev.name, int(ev.start_ns), int(ev.duration_ns),
                         _detail(ev)) for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(ANNOTATION):
                        tr.host.append((ev.name[len(ANNOTATION):],
                                        int(ev.start_ns),
                                        int(ev.duration_ns)))
    outer = [(s, s + d) for label, s, d in tr.host if label == "window"]
    if not outer:
        raise RuntimeError("the trace holds no window annotation")
    tr.t0_ns, tr.t1_ns = outer[0]
    return tr
