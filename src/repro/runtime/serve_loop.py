"""Domino streaming front end: closed-loop serving over the pipelined
simulator.

:func:`build_stream_sim` builds a streaming ``NetworkSimulator``
(``core/network.py``) for float or int8-quantized CNN parameters, and
:func:`serve_stream` feeds it image frames from a request queue at a
configurable offered rate and reports closed-loop latency and
throughput histograms: the serving-side view of the paper's stream
computing.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from repro.core.engine import is_quantized_leaf as _is_q_leaf


def quantize_cnn_params_for_serving(params: Dict[str, Any]
                                    ) -> Dict[str, Any]:
    """Quantize every conv kernel / FC matrix to ``{"q": int8, "s": (M,)}``,
    with the per-output-column scale taken over the *flattened
    contraction* (K*K*C) — the crossbar-resident layout the
    ``CIMEngine`` consumes directly (``core/engine.py::quantize_weight``,
    so re-quantizing float params on the engine yields bit-identical
    weights)."""
    from repro.core.engine import quantize_weight

    out = {}
    for name, w in params.items():
        q, s = quantize_weight(np.asarray(w))
        out[name] = {"q": q, "s": s}
    return out


def dequantize_params(params):
    """The explicit float route for ``{"q", "s"}`` quantized leaves of a
    CNN name->array dict (or any pytree).  Non-quantized leaves pass
    through untouched."""
    def one(leaf):
        if _is_q_leaf(leaf):
            return np.asarray(leaf["q"], np.float32) * np.asarray(
                leaf["s"], np.float32)
        return leaf

    return jax.tree_util.tree_map(one, params, is_leaf=_is_q_leaf)


@dataclass
class StreamServeReport:
    """Closed-loop serving statistics from one streamed request trace.

    Latencies are arrival -> pipeline-exit, in step-clock cycles; the
    seconds-level views apply the Tab. 3 step clock.  ``latency_hist``
    is a ``numpy.histogram`` pair over the per-request latencies."""

    arrivals: np.ndarray              # (T,) request arrival cycles
    latency_cycles: np.ndarray        # (T,) closed-loop latency per request
    #: steady-state exit spacing (cycles); None on a single-request
    #: trace — one exit has no spacing to measure
    measured_ii: Optional[int]
    analytic_ii: int                  # plan_network's slowest-stage bound
    fill_latency: int                 # first request: arrival -> exit
    offered_inf_s: float              # request rate the queue injected
    throughput_inf_s: float           # measured completion rate
    clock_hz: float
    latency_hist: Tuple[np.ndarray, np.ndarray] = field(repr=False)
    #: frames the StragglerMonitor flagged (> threshold x EWMA latency)
    flagged_frames: Tuple[int, ...] = ()
    #: monitor tripped ``trip_limit`` consecutive flags: reshard advised
    straggler_escalate: bool = False
    #: realized numerics micro-batch sizes (frames per batched stage
    #: sweep, bounded by ``batch_window``); mirrors the
    #: ``serve_batch_size`` metrics histogram
    batch_sizes: Tuple[int, ...] = ()
    #: (T, classes) per-request logits, frame-indexed (None when no
    #: request arrived)
    logits: Optional[np.ndarray] = field(default=None, repr=False)

    @property
    def latency_s(self) -> np.ndarray:
        return self.latency_cycles / self.clock_hz

    @property
    def completed(self) -> int:
        """Requests that made it through the pipeline."""
        return int(self.latency_cycles.size)

    def latency_percentiles(self, qs=(50, 95, 99)) -> Dict[str, float]:
        """Per-request latency percentiles in cycles (keys ``p50``...).

        A zero-completed-request run reports ``{}`` — there is no
        latency distribution to summarize (``np.percentile`` would
        raise on the empty array)."""
        if self.latency_cycles.size == 0:
            return {}
        return {f"p{q}": float(np.percentile(self.latency_cycles, q))
                for q in qs}


def build_stream_sim(cnn, params: Dict[str, Any], engine=None,
                     chiplets: int = 1, noi: str = "mesh", **kw):
    """Serving-side constructor for the streaming simulator.

    Wires the quantized-weights serving route end-to-end: params carrying
    ``{"q", "s"}`` leaves (from :func:`quantize_cnn_params_for_serving`)
    run the ``CIMEngine`` path by default — the int8 weights stay
    resident, never dequantized — while float params run the exact
    engine.  Pass ``engine=`` to override (e.g. ``"pallas"``), or
    dequantize explicitly with :func:`dequantize_params` to serve a
    quantized checkpoint on the exact engine.

    ``chiplets > 1`` serves the model sharded over a two-level
    :class:`~repro.core.noc.ChipletFabric` (``noi`` names the interposer
    topology): the plan is cut at stage boundaries via
    :func:`~repro.core.noc.shard_network` and streamed OFM hand-offs
    between chiplets cross the NoI as ordinary routed transport traffic.
    An explicit ``placement=`` kwarg wins over these convenience knobs.

    Because this builds on ``backend="trace"``, quantized serving gets
    the fused integer-native lowering (``core/trace.py``) automatically:
    batched int8 gemms + one vectorized ADC conversion per layer,
    bitwise-equal to the per-tile interpreter fold and composing with
    the streaming executor's per-stage runs."""
    from repro.core.network import NetworkSimulator

    if engine is None:
        quantized = any(_is_q_leaf(v) for v in params.values())
        engine = "cim" if quantized else "exact"
    if chiplets > 1 and "placement" not in kw:
        from repro.core.mapping import plan_network
        from repro.core.noc import shard_network

        # mirror NetworkSimulator's own planning defaults so the sharded
        # placement's block spans match the simulator's plan exactly
        plan = plan_network(cnn, n_c=kw.get("n_c", 256),
                            n_m=kw.get("n_m", 256),
                            reuse=kw.get("reuse", 1),
                            dup_cap=kw.get("dup_cap", 64),
                            dup_overrides=kw.get("dup_overrides") or {})
        kw["placement"] = shard_network(plan, chiplets, noi=noi)
    return NetworkSimulator(cnn, params, backend="trace", streaming=True,
                            engine=engine, **kw)


#: serve-latency histogram bounds (step-clock cycles, geometric ladder
#: covering CIFAR pipelines through ImageNet fill latencies)
LATENCY_BUCKETS_CYCLES = (
    1e3, 2e3, 5e3, 1e4, 2e4, 5e4, 1e5, 2e5, 5e5, 1e6, 2e6, 5e6, 1e7)

#: ids of ``serve_stream`` calls in this process, the ``call`` argument
#: of each call's root span
_SERVE_CALLS = itertools.count()


def serve_stream(sim, frames: np.ndarray,
                 offered_inf_s: Optional[float] = None,
                 clock_hz: Optional[float] = None,
                 hist_bins: int = 16,
                 straggler: Optional["StragglerMonitor"] = None,
                 metrics: Optional["MetricsRegistry"] = None,
                 metric_labels: Optional[Dict[str, str]] = None,
                 batch_window: Optional[int] = None
                 ) -> StreamServeReport:
    """Request-queue front-end over the streaming simulator.

    ``sim`` is a ``NetworkSimulator(..., backend="trace",
    streaming=True)``; ``frames`` (T, H, W, C) are the queued requests.
    Arrivals are spaced at ``offered_inf_s`` (requests/second at the
    step clock); by default the queue offers exactly the analytic
    initiation-interval rate — the hardware's own steady-state ability —
    so any measured latency growth is queueing delay the pipeline could
    not hide.  Each request's closed-loop latency is measured from its
    arrival cycle to its pipeline exit in the simulated stage timeline.

    The per-frame latencies feed a :class:`StragglerMonitor`
    (``runtime/fault.py``; pass ``straggler=`` to tune or share one
    across calls): frames whose closed-loop latency exceeds
    ``threshold`` x the EWMA baseline are flagged in
    ``report.flagged_frames``, and ``trip_limit`` consecutive flags set
    ``report.straggler_escalate`` — a queue drifting past the pipeline's
    steady state, the serving-side analogue of a slow pod member.

    ``metrics`` (a ``repro.telemetry.MetricsRegistry``) registers
    Prometheus-style series — completed/flagged frame counters, the
    latency histogram, queue-depth distribution, realized micro-batch
    sizes (``serve_batch_size``) and goodput gauges.  ``metric_labels``
    (e.g. ``{"tenant": "a"}``) attaches every series to that label set,
    so multi-tenant serving scrapes per-tenant series from one shared
    registry without any refactor.

    ``batch_window`` is the micro-batching admission window: queued
    requests execute as one numerics batch of up to that many frames
    (``run_stream``'s frame-axis chunk).  Batching cannot change a
    reported bit — per-request latency comes from the unchanged
    analytic timing model, and the batched gemms are row-position
    invariant — so the knob trades simulator working set against
    per-request Python overhead only.  A lone queued request (T=1) is
    served as a stream with ``measured_ii=None``.
    """
    from repro.core.energy import STEP_CLOCK_HZ
    from repro.runtime.fault import StragglerMonitor
    from repro.telemetry.spans import span as _tspan

    if clock_hz is None:
        clock_hz = STEP_CLOCK_HZ
    frames = np.asarray(frames, np.float64)
    t_n = frames.shape[0]
    if offered_inf_s is None:
        spacing = float(sim.plan.initiation_interval)
    else:
        spacing = clock_hz / offered_inf_s
    if t_n == 0:
        # explicit empty report: nothing arrived, nothing completed —
        # downstream percentile/histogram consumers must not blow up,
        # and a metrics scrape still sees the zero-valued series
        empty = np.empty(0, np.int64)
        report = StreamServeReport(
            arrivals=empty, latency_cycles=empty,
            measured_ii=0, analytic_ii=sim.plan.initiation_interval,
            fill_latency=0, offered_inf_s=clock_hz / spacing,
            throughput_inf_s=0.0, clock_hz=clock_hz,
            latency_hist=np.histogram(empty, bins=hist_bins))
        if metrics is not None:
            _export_serve_metrics(metrics, dict(metric_labels or {}),
                                  report, None)
        return report
    arrivals = np.floor(np.arange(t_n) * spacing).astype(np.int64)
    # the call id ties every span of one call to this root span
    with _tspan(f"serve_stream:{sim.cnn.name}", frames=t_n,
                batch_window=batch_window or 0, call=next(_SERVE_CALLS)):
        res = sim.run_stream(frames, arrivals=arrivals, chunk=batch_window)
    lat = res.frame_latency
    exits = res.finish[:, -1]
    exit_span = int(exits[-1] - exits[0])
    throughput = (clock_hz * (t_n - 1) / exit_span) if exit_span > 0 \
        else float("inf")
    counts, edges = np.histogram(lat, bins=hist_bins)
    mon = StragglerMonitor() if straggler is None else straggler
    escalate = False
    for i, cycles in enumerate(lat):
        escalate = mon.observe(i, float(cycles) / clock_hz) or escalate
    report = StreamServeReport(
        arrivals=arrivals, latency_cycles=lat,
        measured_ii=res.measured_ii, analytic_ii=res.analytic_ii,
        fill_latency=res.fill_latency,
        offered_inf_s=clock_hz / spacing, throughput_inf_s=throughput,
        clock_hz=clock_hz, latency_hist=(counts, edges),
        flagged_frames=tuple(mon.flagged_steps),
        straggler_escalate=escalate, batch_sizes=res.batch_sizes,
        logits=res.logits)
    if metrics is not None:
        _export_serve_metrics(metrics, dict(metric_labels or {}),
                              report, res)
    return report


def _export_serve_metrics(metrics, labels: Dict[str, str],
                          report: StreamServeReport, res) -> None:
    """Register/update the serving series on a telemetry registry.

    ``res`` is the stream result (for exit times) or None for an
    empty run, which still registers every series at zero."""
    lnames = tuple(sorted(labels))

    def series(fam):
        return fam.labels(**labels)

    series(metrics.counter(
        "serve_frames_total", "requests completed", lnames)).inc(
            report.completed)
    series(metrics.counter(
        "serve_flagged_total", "straggler-flagged requests",
        lnames)).inc(len(report.flagged_frames))
    hist = series(metrics.histogram(
        "serve_latency_cycles", "closed-loop request latency (cycles)",
        lnames, buckets=LATENCY_BUCKETS_CYCLES))
    for cycles in report.latency_cycles:
        hist.observe(float(cycles))
    # queue depth sampled at each arrival: arrived minus already exited
    exits = np.sort(res.finish[:, -1]) if res is not None \
        else np.empty(0, np.int64)
    depth_hist = series(metrics.histogram(
        "serve_queue_depth", "frames in flight at each arrival", lnames,
        buckets=(1, 2, 4, 8, 16, 32, 64, 128)))
    peak = 0
    for i, a in enumerate(report.arrivals):
        depth = (i + 1) - int(np.searchsorted(exits, a, side="right"))
        peak = max(peak, depth)
        depth_hist.observe(depth)
    series(metrics.gauge(
        "serve_queue_depth_peak", "max frames in flight", lnames)).set(peak)
    series(metrics.gauge(
        "serve_goodput_inf_s", "measured completion rate", lnames)).set(
            report.throughput_inf_s)
    series(metrics.gauge(
        "serve_offered_inf_s", "offered request rate", lnames)).set(
            report.offered_inf_s)
    batch_hist = series(metrics.histogram(
        "serve_batch_size", "realized numerics micro-batch sizes", lnames,
        buckets=(1, 2, 4, 8, 16, 32, 64)))
    for size in (res.batch_sizes if res is not None else ()):
        batch_hist.observe(float(size))
    series(metrics.gauge(
        "serve_measured_ii_cycles", "steady-state exit spacing",
        lnames)).set(float(report.measured_ii)
                     if report.measured_ii is not None else 0.0)
    series(metrics.gauge(
        "serve_straggler_escalate", "monitor escalation tripped",
        lnames)).set(1.0 if report.straggler_escalate else 0.0)

