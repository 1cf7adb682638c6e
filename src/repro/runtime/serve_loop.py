"""Serve-step construction: prefill + decode with sharded KV caches.

Cache PartitionSpecs are auto-derived exactly like params (global vs
per-device shapes of ``init_cache``), covering every cache flavor:
GQA (sharded / group-trick / replicated heads), MLA compressed latents,
mamba states, sliding-window ring buffers, int8 quantized caches.

This module also hosts the **Domino streaming front-end**
(:func:`serve_stream`): a request-queue loop that feeds image frames
into the pipelined streaming simulator (``core/network.py``) at a
configurable offered rate and reports closed-loop latency/throughput
histograms — the serving-side view of the paper's stream computing.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig
from repro.core.engine import is_quantized_leaf as _is_q_leaf
from repro.models import encdec as ED
from repro.models import transformer as T
from repro.models.common import ShardingPlan, resolve_w
from repro.runtime.partition import derive_specs, shardings_from_specs
from repro.runtime.train_loop import _batch_pspec, _shard_map, make_plan


#: leaf names that are true matmul weights (safe to int8-quantize with
#: per-output-column scales).  Name-allowlisted: scan-stacking makes shape
#: heuristics ambiguous (a stacked bias (count, d) looks like a matrix).
QUANTIZABLE = frozenset({
    "wq", "wk", "wv", "wo", "w_in", "w_out", "w_gate",
    "w_uq", "w_uk", "w_uv", "w_dq", "w_dkv", "head",
    "shared_in", "shared_out", "shared_gate", "frontend_proj",
    "w_in_x", "w_in_z", "x_proj", "dt_proj", "proj",
})


def quantize_decisions(params, min_size: int = 1 << 14) -> Dict[str, bool]:
    """Which leaves get int8 CIM residency — decided on *global* shapes so
    the rule is independent of the tp shard factor."""
    import re

    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = "/".join(str(p) for p in path)
        last = re.sub(r"[^\w]", "", str(path[-1]))
        out[name] = bool(
            last in QUANTIZABLE and leaf.ndim >= 2
            and leaf.shape[-1] >= 16 and leaf.shape[-2] >= 16
            and leaf.size >= min_size)
    return out


def quantize_params_for_serving(params, min_size: int = 1 << 14,
                                decisions: Optional[Dict[str, bool]] = None):
    """Quantize selected matmul weights to int8 + per-column scale
    (Domino: 8-bit weights resident in the arrays).

    Consumers of the ``{"q", "s"}`` leaves: the LM layers dequantize on
    use through ``models/common.py::resolve_w``; the Domino CNN serving
    path (:func:`build_stream_sim`) hands them to the quantized
    ``CIMEngine`` which keeps the int8 weights resident.  The explicit
    float route is :func:`dequantize_params`."""
    from repro.core.cim import quantize_symmetric

    if decisions is None:
        decisions = quantize_decisions(params, min_size)

    def one(path, leaf):
        name = "/".join(str(p) for p in path)
        if decisions.get(name, False):
            q, s = quantize_symmetric(leaf.astype(jnp.float32), 8, axis=-2)
            return {"q": q, "s": s}
        return leaf

    return jax.tree_util.tree_map_with_path(one, params)


def quantize_cnn_params_for_serving(params: Dict[str, Any]
                                    ) -> Dict[str, Any]:
    """Domino CNN flavor of :func:`quantize_params_for_serving`: every
    conv kernel / FC matrix becomes ``{"q": int8, "s": (M,)}`` with the
    per-output-column scale taken over the *flattened contraction*
    (K*K*C) — the crossbar-resident layout the ``CIMEngine`` consumes
    directly (``core/engine.py::quantize_weight``, so re-quantizing
    float params on the engine yields bit-identical weights)."""
    from repro.core.engine import quantize_weight

    out = {}
    for name, w in params.items():
        q, s = quantize_weight(np.asarray(w))
        out[name] = {"q": q, "s": s}
    return out


def dequantize_params(params):
    """The explicit float route for ``{"q", "s"}`` quantized leaves —
    works on both the LM pytree and the Domino CNN name->array dict.
    Non-quantized leaves pass through untouched."""
    def one(leaf):
        if _is_q_leaf(leaf):
            return np.asarray(leaf["q"], np.float32) * np.asarray(
                leaf["s"], np.float32)
        return leaf

    return jax.tree_util.tree_map(one, params, is_leaf=_is_q_leaf)


@dataclass
class ServeProgram:
    cfg: ModelConfig
    plan: ShardingPlan
    mesh: Any
    param_specs: Any
    cache_specs: Any
    cache_global_sds: Any  # ShapeDtypeStructs of the global cache arrays
    prefill_fn: Callable   # (params, batch) -> (logits, caches)
    decode_fn: Callable    # (params, token, caches, pos) -> (logits, caches)


def build_serve_program(cfg: ModelConfig, mesh, pcfg: ParallelConfig,
                        batch: int, s_max: int,
                        kv_dtype: str = "bfloat16",
                        cim_weights: bool = False,
                        quant_min_size: int = 1 << 14) -> ServeProgram:
    plan = make_plan(cfg, mesh, pcfg)
    is_ed = cfg.is_encdec
    init_fn_model = ED.init_params if is_ed else T.init_params

    decisions = None
    if cim_weights:
        raw_g = jax.eval_shape(
            lambda k: init_fn_model(k, cfg, plan.as_global()),
            jax.random.PRNGKey(0))
        decisions = quantize_decisions(raw_g, quant_min_size)

    def make(k, p):
        params = init_fn_model(k, cfg, p)
        if cim_weights:
            params = quantize_params_for_serving(params, quant_min_size,
                                                 decisions)
        return params

    g_shapes = jax.eval_shape(
        lambda k: make(k, plan.as_global()), jax.random.PRNGKey(0))
    l_shapes = jax.eval_shape(
        lambda k: make(k, plan), jax.random.PRNGKey(0))
    param_specs = derive_specs(g_shapes, l_shapes, plan.tp, plan.tp_axis)

    # cache specs: model sharding from (global vs local) shapes, batch dim
    # located structurally by comparing shapes at batch vs 2*batch
    def cache_shapes(p, b):
        if is_ed:
            return jax.eval_shape(lambda: ED.init_cache(
                cfg, p, b, s_max, t_enc=s_max, kv_dtype=kv_dtype))
        return jax.eval_shape(lambda: T.init_cache(
            cfg, p, b, s_max, kv_dtype))

    cg = cache_shapes(plan.as_global(), batch)
    cl = cache_shapes(plan, batch)
    c2 = cache_shapes(plan, 2 * batch)
    cache_specs = derive_specs(cg, cl, plan.tp, plan.tp_axis)
    from repro.runtime.train_loop import dp_size_of
    dpn = dp_size_of(mesh, plan)
    dp = None
    if plan.dp_axes and batch % dpn == 0:
        dp = plan.dp_axes if len(plan.dp_axes) != 1 else plan.dp_axes[0]

    def add_batch(spec, a, b2):
        lst = list(spec)
        for i, (da, db) in enumerate(zip(a.shape, b2.shape)):
            if da != db and lst[i] is None and dp is not None:
                lst[i] = dp
        return P(*lst)

    cache_specs = jax.tree.map(add_batch, cache_specs, cl, c2)

    def prefill_dev(params, batch_in):
        if is_ed:
            return ED.prefill(params, batch_in, cfg, plan,
                              kv_dtype=kv_dtype, s_max=s_max)
        extras = {k: v for k, v in batch_in.items() if k != "tokens"}
        return T.prefill(params, batch_in["tokens"], cfg, plan,
                         extras=extras or None, kv_dtype=kv_dtype,
                         s_max=s_max)

    def decode_dev(params, token, caches, pos):
        if is_ed:
            return ED.decode_step(params, token, caches, pos, cfg, plan,
                                  kv_dtype=kv_dtype)
        return T.decode_step(params, token, caches, pos, cfg, plan,
                             kv_dtype=kv_dtype)

    return ServeProgram(
        cfg=cfg, plan=plan, mesh=mesh, param_specs=param_specs,
        cache_specs=cache_specs, cache_global_sds=cg,
        prefill_fn=_build_prefill(prefill_dev, mesh, plan, param_specs,
                                  cache_specs),
        decode_fn=_build_decode(decode_dev, mesh, plan, param_specs,
                                cache_specs),
    )


def _dp_entry(plan, n, dpn):
    """data-axis spec entry for a batch of size n (None if it can't shard)."""
    if not plan.dp_axes or n % dpn != 0:
        return None
    return plan.dp_axes if len(plan.dp_axes) != 1 else plan.dp_axes[0]


def _build_prefill(prefill_dev, mesh, plan, param_specs, cache_specs):
    from repro.runtime.train_loop import dp_size_of
    dpn = dp_size_of(mesh, plan)

    def fn(params, batch_in):
        bspecs = _batch_pspec(batch_in, plan, dp_size=dpn)
        dp = _dp_entry(plan, batch_in["tokens"].shape[0], dpn)
        sm = _shard_map(
            prefill_dev, mesh,
            in_specs=(param_specs, bspecs),
            out_specs=(P(dp, None), cache_specs),
        )
        return sm(params, batch_in)

    return fn


def _build_decode(decode_dev, mesh, plan, param_specs, cache_specs):
    from repro.runtime.train_loop import dp_size_of
    dpn = dp_size_of(mesh, plan)

    def fn(params, token, caches, pos):
        dp = _dp_entry(plan, token.shape[0], dpn)
        sm = _shard_map(
            decode_dev, mesh,
            in_specs=(param_specs, P(dp), cache_specs, P()),
            out_specs=(P(dp, None), cache_specs),
        )
        return sm(params, token, caches, pos)

    return fn


# ---------------------------------------------------------------------------
# Domino streaming front-end (closed-loop serving over the pipelined sim)
# ---------------------------------------------------------------------------


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
    with _tspan(f"serve_stream:{sim.cnn.name}", frames=t_n,
                batch_window=batch_window or 0):
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


def greedy_generate(serve: ServeProgram, params, batch_in, steps: int):
    """Batched greedy generation loop for the examples."""
    logits, caches = jax.jit(serve.prefill_fn)(params, batch_in)
    pos = batch_in["tokens"].shape[1]
    decode = jax.jit(serve.decode_fn)
    token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out = [token]
    for i in range(steps - 1):
        logits, caches = decode(params, token, caches, jnp.int32(pos + i))
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(token)
    return jnp.stack(out, axis=1)
