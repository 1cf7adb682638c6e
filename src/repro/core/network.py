"""Whole-network Domino simulation (the tentpole of the compile ->
place -> route -> simulate -> energy path).

Chains per-layer block simulators tail-to-head on the *placed* mesh from
``place_network``: every CONV layer runs from its compiled instruction
tables (``core/schedule.py``) through the shared routed transport, FC
layers run the Fig. 4 grid dataflow, and each block's OFM streams to the
next block's head tile over its routed NoC link — so a whole
``configs/cnn.py`` model executes end-to-end from 16-bit instruction
words and is checked against the jax reference forward pass
(``models/cnn.py::cnn_forward``).

Two execution backends share the placement, schedules and transport:

* ``backend="interp"`` — the per-cycle interpreter
  (``core/simulator.py``), the oracle: every (tile, cycle) event is
  decoded and executed literally;
* ``backend="trace"`` — the trace-compiled fast path
  (``core/trace.py``): each block's schedule is lowered once to
  gather/gemm form and executed as a handful of batched ops, bitwise-
  equal to the interpreter (``tests/test_trace.py``).  It removes the
  cycle loop entirely; what remains is the conv arithmetic.  On the
  quantized engines ``trace_jit=True`` additionally routes each layer's
  integer MAC through a ``jax.jit`` step, bitwise-equal to the numpy
  trace.

Batching: the IFM batch rides each routed packet as ``(B, C)`` lanes, so
one simulated pass serves a whole batch (see ``core/simulator.py``).

Stream computing (``streaming=True`` + ``backend="trace"``): the paper's
headline throughput numbers (Tab. 4, Fig. 7) come from *pipelined*
inference — successive input frames overlap across the layer pipeline,
so steady-state throughput is bound by the slowest stage's initiation
interval, not the end-to-end latency.  :meth:`NetworkSimulator.run_stream`
executes that mode: each layer (plus its projection shortcut) is one
pipeline stage, frames advance in wavefront order (stage *k* consumes
frame *t* while stage *k+1* consumes frame *t-1*), inter-stage OFM
hand-off flows through the routed transport with per-frame
``TrafficCounters``, and residual shortcuts are buffered across the
pipeline skew (the paper's FIFO forwarding).  The executor *measures*
the steady-state initiation interval from the simulated stage timeline
— the per-stage occupancies come from the compiled schedules'
:class:`~repro.core.schedule.StageHandoff` metadata, and the measured
II must emerge equal to ``plan_network``'s analytic slowest-stage bound
(cross-checked in ``tests/test_streaming.py`` and the ``stream_*``
benchmark rows).

Functional notes:

* weight-duplicated copies share weights and split the pixel stream for
  *throughput*; functionally one copy of each block computes the full
  OFM, which is what we simulate (copy 0's placement), while the energy
  model accounts all copies;
* residual networks are wired: a ``residual_from`` layer's block runs
  with a bare tail (no activation), the saved block input — through the
  ``*_sc`` projection block when the config has one — streams to the add
  site as ``RESIDUAL``-class routed traffic, and the tail unit applies
  ReLU after the add (``resnet18-cifar10`` matches the jax forward
  exactly);
* ResNet's global average pool before the FC head is computed at the FC
  block boundary (the jax reference's ``jnp.mean``), VGG flattens;
* layers whose schedule period W + 2P exceeds the 128-entry table (Tab.
  3) cannot compile as one schedule, exactly like the hardware — the
  simulator width-tiles them (``compile_conv_strips``): the same tile
  chain runs per-strip tables back to back, halo input columns are
  re-streamed at strip boundaries, and output strips concatenate.  This
  is how the ImageNet models (e.g. ``resnet50-imagenet``) run
  end-to-end.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.configs.cnn import CNNConfig, ConvLayer, FCLayer
from repro.core.cim import CIMSpec
from repro.core.energy import STEP_CLOCK_HZ
from repro.core.engine import (
    PEEngine,
    calibrate_engine,
    conv_tile_slices,
    dequantize_weight,
    is_quantized_leaf,
    make_engine,
)
from repro.core.instructions import TABLE_CAPACITY
from repro.core.mapping import LayerPlan, NetworkPlan, plan_network
from repro.core.noc import Placement, block_spans, place_network
from repro.core.schedule import (
    BlockSchedule,
    ConvStrip,
    compile_conv_block,
    compile_conv_strips,
)
from repro.core.simulator import BlockSimulator, SimCounters, simulate_fc
from repro.core.trace import (
    TracePlan, TraceExecutor, compile_trace, scratch_buf)
from repro.telemetry.spans import span
from repro.core.transport import (
    OFM,
    RESIDUAL,
    NoCTransport,
    TrafficCounters,
)

BACKENDS = ("interp", "trace")


@dataclass
class NetworkSimResult:
    logits: np.ndarray            # (B, classes)
    counters: SimCounters         # aggregated tile events, per inference
    traffic: TrafficCounters      # routed byte-hops per traffic class


@dataclass(frozen=True)
class _Stage:
    """One stage of the layer pipeline: a conv layer (plus its projection
    shortcut, which runs concurrently on its own placed tiles) or an FC
    layer.  ``occupancy`` is the stage's initiation interval — cycles
    between successive frames entering it, its output-pixel stream split
    over the weight-duplicated copies; ``latency`` is first-input to
    last-output of one frame (stream occupancy + chain fill/drain)."""

    li: int                    # main layer index
    sc_li: Optional[int]       # projection shortcut folded into this stage
    kind: str                  # "conv" | "fc"
    prev_li: Optional[int]     # main layer index of the upstream stage
    occupancy: int
    latency: int


@dataclass
class StreamResult:
    """Measured pipelined (stream-computing) execution of ``T`` frames.

    ``start``/``finish`` are the simulated stage timeline: cycle each
    stage initiated / completed each frame, from which the steady-state
    initiation interval is *measured* (``finish`` deltas at the exit
    stage) rather than asserted.  With back-to-back arrivals the measured
    II is throughput-bound (the slowest stage); spaced arrivals make it
    arrival-bound — the closed-loop serve front-end uses that.

    ``measured_ii`` is Optional: a single-frame stream (``T == 1``, the
    serve loop executing one queued request) has no exit-to-exit spacing
    to measure, so it reports ``None`` while every other field (timeline,
    counters, fill latency) stays populated."""

    logits: np.ndarray                    # (T, classes), frame-indexed
    frame_counters: List[SimCounters]     # per-frame tile events
    frame_traffic: List[TrafficCounters]  # per-frame routed traffic
    arrivals: np.ndarray                  # (T,) frame arrival cycles
    start: np.ndarray                     # (T, S) stage initiation cycles
    finish: np.ndarray                    # (T, S) stage completion cycles
    occupancy: Tuple[int, ...]            # per-stage initiation interval
    measured_ii: Optional[int]            # steady-state exit-to-exit cycles
    analytic_ii: int                      # plan_network slowest-stage bound
    fill_latency: int                     # frame 0: arrival -> pipeline exit
    residual_fifo_depth: int              # max shortcut frames buffered
    #: realized numerics micro-batches: frames per batched stage sweep
    #: (all ones on the per-cell oracle path)
    batch_sizes: Tuple[int, ...] = ()

    @property
    def total_cycles(self) -> int:
        return int(self.finish[-1, -1])

    @property
    def frame_latency(self) -> np.ndarray:
        """Per-frame closed-loop latency: arrival -> pipeline exit."""
        return self.finish[:, -1] - self.arrivals

    @property
    def drain_latency(self) -> int:
        """Cycles to empty the pipeline after the last frame initiates."""
        return int(self.finish[-1, -1] - self.start[-1, 0])

    def inferences_per_s(self, clock_hz: float = STEP_CLOCK_HZ) -> float:
        """Measured steady-state throughput at the Tab. 3 step clock."""
        if self.measured_ii is None:
            raise ValueError(
                "a single-frame stream has no measured initiation "
                "interval (measured_ii is None) — throughput needs T >= 2")
        return clock_hz / self.measured_ii


def _is_shortcut(layer) -> bool:
    """The config convention for ResNet projection shortcuts."""
    return isinstance(layer, ConvLayer) and layer.name.endswith("_sc")


def compile_conv_layer(layer: ConvLayer, lp: LayerPlan
                       ) -> BlockSchedule | Tuple[ConvStrip, ...]:
    """One conv layer's schedule under its plan ``lp``: a single
    block, or width strips run back to back on the same tile chain when
    the period W + 2P exceeds the 128-entry table.  Residual targets and
    projection shortcuts compile with a bare tail: their activation fires
    *after* the shortcut add."""
    act = None if (layer.residual_from or _is_shortcut(layer)) else "relu"
    kw = dict(h=layer.h, w=layer.w, c_in=layer.c, c_out=layer.m,
              k=layer.k, stride=layer.s, pad=layer.p, pack=lp.pack,
              c_splits=lp.c_splits, pool_k=layer.pool_k,
              pool_s=layer.pool_s, activation=act)
    if layer.w + 2 * layer.p > TABLE_CAPACITY:
        return compile_conv_strips(layer.name, **kw)
    return compile_conv_block(layer.name, **kw)


#: default numerics micro-batch for the batched streaming path: frames
#: per stage-major sweep (bounds the working set; chunk boundaries
#: cannot change a bit — see ``run_stream``)
DEFAULT_STREAM_CHUNK = 16


def stream_timeline(arrivals: np.ndarray, occupancy, latency
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """The wavefront timing recurrence, vectorized over frames.

    The per-cell streaming executor computes, cell by cell::

        ready[t]      = finish[t, k-1] if k else arrivals[t]
        start[t, k]   = ready[t] if t == 0
                        else max(ready[t], start[t-1, k] + occ[k])
        finish[t, k]  = start[t, k] + lat[k]

    For a fixed stage ``k`` the ``start`` recurrence is a max-plus
    prefix scan; substituting ``g[t] = start[t] - t * occ[k]`` turns it
    into ``g[t] = max(ready[t] - t * occ[k], g[t-1])`` — a plain running
    maximum — so one ``np.maximum.accumulate`` per stage replaces the
    T x S Python loop, bit-identical (integer arithmetic throughout).
    ``tests/test_streaming.py`` asserts equality against the scalar
    loop over random arrival vectors."""
    arr = np.asarray(arrivals, np.int64)
    t_n, s_n = arr.shape[0], len(occupancy)
    tidx = np.arange(t_n, dtype=np.int64)
    start = np.empty((t_n, s_n), np.int64)
    finish = np.empty((t_n, s_n), np.int64)
    ready = arr
    for k in range(s_n):
        shift = tidx * int(occupancy[k])
        st = np.maximum.accumulate(ready - shift) + shift
        start[:, k] = st
        finish[:, k] = st + int(latency[k])
        ready = finish[:, k]
    return start, finish


def stream_timeline_scalar(arrivals: np.ndarray, occupancy, latency
                           ) -> Tuple[np.ndarray, np.ndarray]:
    """Reference scalar form of :func:`stream_timeline` — the exact
    per-cell recurrence the interleaved oracle executes, kept as the
    differential-test oracle for the vectorized scan."""
    arr = np.asarray(arrivals, np.int64)
    t_n, s_n = arr.shape[0], len(occupancy)
    start = np.zeros((t_n, s_n), np.int64)
    finish = np.zeros((t_n, s_n), np.int64)
    for t in range(t_n):
        for k in range(s_n):
            ready = finish[t, k - 1] if k else arr[t]
            init = ready if t == 0 \
                else max(ready, start[t - 1, k] + occupancy[k])
            start[t, k] = init
            finish[t, k] = init + latency[k]
    return start, finish


class NetworkSimulator:
    """Execute a whole CNN from compiled instruction tables over the
    placed, routed NoC."""

    def __init__(self, cnn: CNNConfig, params: Dict[str, np.ndarray],
                 n_c: int = 256, n_m: int = 256, reuse: int = 1,
                 dup_cap: int = 64, backend: str = "interp",
                 trace_jit: bool = False, streaming: bool = False,
                 placement: Optional[Placement] = None,
                 dup_overrides: Optional[Dict[str, int]] = None,
                 engine: "str | PEEngine" = "exact",
                 cim_spec: Optional[CIMSpec] = None,
                 calib_images: Optional[np.ndarray] = None):
        """params: layer name -> (K, K, C, M) conv kernel or (C_in, C_out)
        FC matrix (the ``models/cnn.py::init_cnn`` convention) — or a
        ``{"q": int8, "s": scale}`` quantized leaf (the CIM-resident
        serving format); quantized leaves require a quantized engine.

        ``placement`` injects an alternative tile layout (a DSE strategy's
        output) instead of the snake default.  Its block spans must match
        this plan's, and its tile-id curve must keep consecutive chain
        tiles within the interpreter's rendezvous slack (any unit-step
        curve qualifies — ``repro.dse.placements.validate_placement``
        checks); placement changes hops and energy, never the math.

        ``engine`` selects the PE numerics (``core/engine.py``):
        ``"exact"`` (float64, bit-for-bit the pre-engine behavior),
        ``"cim"`` (w8a8 + per-subarray ADC, per-layer gain calibrated at
        build from ``calib_images`` — default: a seeded synthetic batch),
        ``"pallas"`` (the same numerics through the Pallas kernel,
        ADC-code-exact vs ``"cim"``), or a prebuilt ``PEEngine``
        instance.  ``cim_spec`` overrides the quantized engines' crossbar
        spec (adc_bits etc.) when ``engine`` is a name.

        On ``backend="trace"`` the quantized engines run the fused
        integer-native lowering (one batch-of-tiles gemm + one
        vectorized ADC conversion per layer chunk — see
        ``core/trace.py``), ADC-code-bitwise with the interpreter;
        ``trace_jit=True`` selects their jitted flavor, which is also
        bitwise and therefore composes with ``streaming=True``.  The
        exact engine has no jitted flavor.
        """
        if backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}: {backend}")
        if trace_jit and backend != "trace":
            raise ValueError(
                "trace_jit=True requires backend='trace' (the default "
                "backend is the per-cycle interpreter)")
        if streaming and backend != "trace":
            raise ValueError(
                "streaming=True requires backend='trace' (the pipelined "
                "executor advances compiled per-stage trace plans)")
        self.pe_engine: PEEngine = make_engine(engine, cim_spec)
        if trace_jit and self.pe_engine.name == "exact":
            raise ValueError(
                "trace_jit=True needs a quantized engine (cim/pallas): the "
                "exact engine's float64 numerics run on the host only")
        # residual wiring follows the configs/cnn.py naming convention the
        # jax reference uses (save at `*_a`, add at `residual_from`,
        # project through an immediately-following `*_sc`) — reject
        # anything else loudly instead of silently mis-wiring a stale
        # shortcut or diverging from cnn_forward
        last_save: Optional[str] = None
        prev: Optional[ConvLayer] = None
        for layer in cnn.layers:
            if not isinstance(layer, ConvLayer):
                prev = None
                continue
            if layer.name.endswith("_a"):
                last_save = layer.name
            if layer.residual_from is not None:
                if layer.residual_from != last_save:
                    raise NotImplementedError(
                        f"{cnn.name}: {layer.name} takes its shortcut from "
                        f"{layer.residual_from!r}, but the most recent saved "
                        f"block input is {last_save!r} — only the *_a/"
                        "residual_from/*_sc convention is wired")
                if layer.pool_s:
                    raise NotImplementedError(
                        f"{cnn.name}: {layer.name} pools in the same block "
                        "as a shortcut add — the reference pools after the "
                        "post-add ReLU, which is not wired")
            if _is_shortcut(layer) and (
                    prev is None or prev.residual_from is None):
                raise NotImplementedError(
                    f"{cnn.name}: {layer.name} is a projection shortcut "
                    "but does not immediately follow its residual-target "
                    "layer, so it would run inline on the main path")
            prev = layer
        self.cnn = cnn
        # optional telemetry hook (repro.telemetry.LinkRecorder): attach
        # to resolve routed traffic to individual mesh links; None (the
        # default) keeps every transport on the zero-overhead path
        self.recorder = None
        # split quantized {"q","s"} leaves (CIM-resident serving) from the
        # float view: quantized engines consume the int8 weights directly,
        # the float view feeds the exact engine and gain calibration
        self._prequant: Dict[str, Tuple[np.ndarray, np.ndarray]] = {}
        fparams: Dict[str, np.ndarray] = {}
        for name, leaf in params.items():
            if is_quantized_leaf(leaf):
                q = np.asarray(leaf["q"])
                s = np.asarray(leaf["s"], np.float64).reshape(-1)
                self._prequant[name] = (q, s)
                fparams[name] = dequantize_weight(q, s)
            else:
                fparams[name] = np.asarray(leaf, np.float64)
        if self._prequant and self.pe_engine.name == "exact":
            raise ValueError(
                f"{cnn.name}: params carry quantized {{'q','s'}} leaves "
                f"({sorted(self._prequant)[:3]}...) — run them on a "
                "quantized engine (engine='cim'/'pallas') or dequantize "
                "explicitly (repro.runtime.serve_loop.dequantize_params)")
        self.params = fparams
        self.n_c, self.n_m = n_c, n_m
        self.backend = backend
        self.trace_jit = trace_jit
        self.streaming = streaming
        self.plan: NetworkPlan = plan_network(cnn, n_c=n_c, n_m=n_m,
                                              reuse=reuse, dup_cap=dup_cap,
                                              dup_overrides=dup_overrides)
        if placement is None:
            placement = place_network(self.plan)
        else:
            spans = block_spans(self.plan)
            if (placement.block_start, placement.block_end) != spans:
                raise ValueError(
                    f"{cnn.name}: injected placement's block spans do not "
                    "match this plan (was it built from the same "
                    "n_c/n_m/reuse/dup_cap?)")
            if placement.noc.num_tiles < self.plan.total_tiles:
                raise ValueError(
                    f"{cnn.name}: {self.plan.total_tiles} tiles do not fit "
                    f"the injected {placement.noc.rows}x"
                    f"{placement.noc.cols} mesh")
        self.placement: Placement = placement
        self.schedules: List[Optional[BlockSchedule]] = []
        # width-tiled layers: their strips, in column order
        self._strips: Dict[int, Tuple[ConvStrip, ...]] = {}
        for li, (layer, lp) in enumerate(zip(cnn.layers, self.plan.layers)):
            sched = None  # FC runs the Fig. 4 grid
            if isinstance(layer, ConvLayer):
                sched = compile_conv_layer(layer, lp)
                if isinstance(sched, tuple):
                    self._strips[li], sched = sched, None
            self.schedules.append(sched)
        # trace backend: lower every schedule once; executors are
        # stateless and reused across runs (keeps jitted fns warm too)
        self._trace_plans: Dict[Tuple[int, int], TracePlan] = {}
        self._executors: Dict[Tuple[int, int], TraceExecutor] = {}
        # zero-bordered padded inputs of width-tiled layers, reused
        self._strip_pads: Dict[int, np.ndarray] = {}
        if backend == "trace":
            with span(f"trace_lower:{cnn.name}",
                      layers=len(self.schedules) + len(self._strips)):
                for li, sched in enumerate(self.schedules):
                    if sched is not None:
                        self._trace_plans[li, 0] = compile_trace(sched)
                for li, strips in self._strips.items():
                    for si, strip in enumerate(strips):
                        self._trace_plans[li, si] = compile_trace(strip.sched)
        # the layer pipeline as explicit stages — the sequential run walks
        # them one frame at a time, the streaming executor overlaps frames
        self._stages: Tuple[_Stage, ...] = self._build_stages()
        # quantized engines: per-layer calibration (activation scale +
        # ADC integration gain) runs ONCE at network build, then every
        # layer's engine handle (resident quantized weights, dequant
        # multipliers) is built and shared by all executors/strips
        if self.pe_engine.needs_calibration:
            if calib_images is None:
                hw = cnn.input_hw
                calib_images = np.random.default_rng(0).random((2, hw, hw, 3))
            calibrate_engine(self.pe_engine, cnn, self.params, calib_images)
        elif calib_images is not None:
            raise ValueError(
                "calib_images has no effect on the exact engine")
        self._handles: Dict[int, object] = {}
        self._build_handles()
        # trace backend: construct every per-stage executor (compiled
        # closures + scratch) once, here — run/run_stream/serve_stream
        # calls then only reassign each executor's transport/counters,
        # so repeated serving on one simulator pays setup exactly once
        # (asserted via Profiler spans in tests/test_streaming.py)
        if backend == "trace":
            self._build_executors()

    def _build_executors(self) -> None:
        """Eagerly instantiate the per-(layer, strip) trace executors."""
        sink_t = NoCTransport(self.placement.noc)
        sink_c = SimCounters()
        with span(f"executor_build:{self.cnn.name}",
                  executors=len(self._trace_plans)):
            for li, sched in enumerate(self.schedules):
                if sched is not None:
                    self._executor(li, 0, sched, sink_t, sink_c)
            for li, strips in self._strips.items():
                for si, strip in enumerate(strips):
                    self._executor(li, si, strip.sched, sink_t, sink_c)

    def _build_handles(self) -> None:
        """(Re)build every layer's engine handle — the only per-trial
        work a device-variation swap needs (schedules, trace plans,
        placement and calibration all survive unchanged)."""
        for li, layer in enumerate(self.cnn.layers):
            if isinstance(layer, ConvLayer):
                sched0 = self.schedules[li]
                if sched0 is None:
                    # width strips run the same tile chain (same taps /
                    # channel slices), so one engine handle serves all
                    strips = self._strips[li]
                    sched0 = strips[0].sched
                    slices0 = conv_tile_slices(sched0)
                    assert all(conv_tile_slices(s.sched) == slices0
                               for s in strips[1:]), layer.name
                self._handles[li] = self.pe_engine.conv_handle(
                    layer.name, self.params[layer.name],
                    conv_tile_slices(sched0),
                    prequant=self._prequant.get(layer.name))
            else:
                self._handles[li] = self.pe_engine.fc_handle(
                    layer.name, self.params[layer.name],
                    prequant=self._prequant.get(layer.name))

    def set_variation(self, variation) -> None:
        """Swap the quantized engine's device-variation model
        (``core/variation.py``) and rebuild only the engine handles —
        the cheap per-trial path of the Monte-Carlo robustness harness
        (``runtime/robustness.py``).  Cached trace executors keep their
        compiled plans; their handle references and jitted closures
        (which bake the perturbed weights / ADC parameters) are
        refreshed so the very next run reflects the new draw."""
        if not hasattr(self.pe_engine, "variation"):
            raise ValueError(
                "set_variation requires a quantized engine "
                "(cim/pallas); the exact engine has no device physics")
        self.pe_engine.variation = variation
        self._build_handles()
        for (li, _si), ex in self._executors.items():
            ex.handle = self._handles[li]
            ex._jax_fn = None

    def _executor(self, li: int, si: int, sched: BlockSchedule,
                  transport: NoCTransport, counters: SimCounters):
        """A block executor for (layer, strip) on the chosen backend (all
        strips of a layer share one engine handle — same tile chain)."""
        layer = self.cnn.layers[li]
        if self.backend == "interp":
            return BlockSimulator(
                sched,
                np.asarray(self.params[layer.name], np.float64),
                bias=None, transport=transport, counters=counters,
                engine=self.pe_engine, handle=self._handles[li])
        ex = self._executors.get((li, si))
        if ex is None:
            ex = TraceExecutor(
                sched,
                np.asarray(self.params[layer.name], np.float64),
                bias=None, transport=transport, counters=counters,
                plan=self._trace_plans[li, si], use_jax=self.trace_jit,
                engine=self.pe_engine, handle=self._handles[li])
            self._executors[li, si] = ex
        else:
            ex.transport, ex.counters = transport, counters
        return ex

    def _run_layer(self, li: int, transport: NoCTransport,
                   counters: SimCounters, x: np.ndarray,
                   account: bool = True) -> np.ndarray:
        """Run one conv layer's block — whole, or strip by strip when the
        layer is width-tiled (same chain, per-strip tables, halo columns
        re-streamed; output strips concatenate along the width).

        ``account=False`` (trace backend only) computes the math without
        counters/transport side effects — the streaming numerics pass."""
        kw = {} if account else {"account": False}
        strips = self._strips.get(li)
        if strips is None:
            return self._executor(li, 0, self.schedules[li], transport,
                                  counters).run(x, **kw)
        layer = self.cnn.layers[li]
        b, p = x.shape[0], layer.p
        with span("net.pad", cat="network", layer=layer.name):
            padded = scratch_buf(
                self._strip_pads, li,
                (b, layer.h + 2 * p, layer.w + 2 * p, layer.c), np.float64)
            padded[:, p:p + layer.h, p:p + layer.w] = x
        outs = [
            self._executor(li, si, strip.sched, transport, counters)
            .run(padded[:, :, strip.lo:strip.hi], **kw)
            for si, strip in enumerate(strips)
        ]
        return np.concatenate(outs, axis=2)

    # -- the layer pipeline as stages ---------------------------------------

    def _stage_timing(self, li: int) -> Tuple[int, int]:
        """(occupancy, latency) of one layer's stage in step-clock cycles.

        Conv: the compiled schedules' hand-off metadata (summed over
        width strips, which run back to back on the same chain), with
        the pixel stream split over the weight-duplicated copies — so
        occupancy is exactly the paper's per-stage initiation-interval
        bound.  FC: the grid is fully pipelined (a new input vector can
        enter every cycle); its psum-chain depth is pure fill latency.
        """
        lp = self.plan.layers[li]
        if lp.kind == "fc":
            return 1, max(1, lp.chain_len)
        strips = self._strips.get(li)
        hands = ([s.sched.handoff for s in strips] if strips is not None
                 else [self.schedules[li].handoff])
        dup = lp.duplication
        occ = max(1, math.ceil(sum(h.out_elems for h in hands) / dup))
        stream = math.ceil(sum(h.stream_len for h in hands) / dup)
        return occ, max(occ, stream) + max(h.drain for h in hands)

    def _build_stages(self) -> Tuple[_Stage, ...]:
        layers = self.cnn.layers
        stages: List[_Stage] = []
        prev_li: Optional[int] = None
        li = 0
        while li < len(layers):
            layer = layers[li]
            step = 1
            if isinstance(layer, ConvLayer):
                sc_li = None
                if layer.residual_from is not None and li + 1 < len(layers) \
                        and _is_shortcut(layers[li + 1]):
                    sc_li = li + 1  # projection runs concurrently in-stage
                    step = 2
                occ, lat = self._stage_timing(li)
                if sc_li is not None:
                    occ_sc, lat_sc = self._stage_timing(sc_li)
                    occ, lat = max(occ, occ_sc), max(lat, lat_sc)
                stages.append(_Stage(li=li, sc_li=sc_li, kind="conv",
                                     prev_li=prev_li, occupancy=occ,
                                     latency=lat))
            else:
                occ, lat = self._stage_timing(li)
                stages.append(_Stage(li=li, sc_li=None, kind="fc",
                                     prev_li=prev_li, occupancy=occ,
                                     latency=lat))
            prev_li = li
            li += step
        return tuple(stages)

    def _exec_stage(self, stage: _Stage, x: np.ndarray,
                    saved: Dict[str, Tuple[np.ndarray, Optional[int]]],
                    counters: SimCounters,
                    traffic: TrafficCounters,
                    account: bool = True) -> np.ndarray:
        """Execute one pipeline stage on one (possibly batched) value.

        Shared verbatim by the sequential :meth:`run` and the streaming
        :meth:`run_stream`, so per-frame math and per-frame routed
        traffic are identical on both paths by construction.  ``saved``
        holds residual block inputs (name -> (value, producing layer))
        between the ``*_a`` save and the shortcut add; the streaming
        executor keeps one such dict per in-flight frame — the paper's
        FIFO forwarding across the pipeline skew.

        ``account=False`` computes the math with zero accounting side
        effects (no counter increments, no transport records, no
        recorder/link-traffic writes): the batched streaming numerics
        pass, whose per-frame accounting is replayed analytically by
        :meth:`_account_stage`."""
        placement = self.placement
        noc = placement.noc
        li = stage.li
        layer = self.cnn.layers[li]
        transport = NoCTransport(noc, base=placement.block_start[li],
                                 counters=traffic, recorder=self.recorder)
        if stage.kind == "fc":
            assert isinstance(layer, FCLayer)
            act = "relu" if li < len(self.cnn.layers) - 1 else None
            with span("net.fc", cat="network", layer=layer.name):
                if x.ndim == 4:
                    if self.cnn.name.startswith("resnet"):
                        x = x.mean(axis=(1, 2))  # global average pool
                    else:
                        x = x.reshape(x.shape[0], -1)  # VGG flattens
                return simulate_fc(
                    x, np.asarray(self.params[layer.name], np.float64),
                    self.n_c, self.n_m, activation=act,
                    counters=counters,
                    transport=transport if account else None,
                    engine=self.pe_engine, handle=self._handles[li])

        mesh_root = NoCTransport(noc, base=0, counters=traffic,
                                 recorder=self.recorder)
        if layer.name.endswith("_a"):
            saved[layer.name] = (x, stage.prev_li)  # residual save (Fig. 2)
        y = self._run_layer(li, transport, counters, x, account=account)
        if layer.residual_from is not None:
            block_in, block_in_src = saved.pop(layer.residual_from)
            res_bytes = int(np.prod(block_in.shape[1:]))  # per frame, 8b
            if stage.sc_li is not None:
                # projection shortcut: its own placed block, driven by
                # the saved block input
                sc_li = stage.sc_li
                sc_tr = NoCTransport(noc, base=placement.block_start[sc_li],
                                     counters=traffic,
                                     recorder=self.recorder)
                if account:
                    self._record_residual(mesh_root, block_in_src,
                                          placement.block_start[sc_li],
                                          res_bytes)
                shortcut = self._run_layer(sc_li, sc_tr, counters, block_in,
                                           account=account)
                if account:
                    lp = self.plan.layers[sc_li]
                    mesh_root.record(placement.block_end[sc_li],
                                     placement.block_end[li], RESIDUAL,
                                     lp.out_pixels * lp.c_out)
            else:
                # identity shortcut streams straight to the add
                if account:
                    self._record_residual(mesh_root, block_in_src,
                                          placement.block_end[li], res_bytes)
                shortcut = block_in
            # tail adder + activation after the shortcut join
            with span("net.residual", cat="network", layer=layer.name):
                y = y + shortcut
                y = np.maximum(y, 0.0)
            counters.act_ops += y.shape[1] * y.shape[2] * y.shape[3]
        return y

    def _record_ofm(self, src_li: int, dst_li: int,
                    traffic: TrafficCounters) -> None:
        """OFM tail -> next consumer's head over the routed mesh link
        (same accounting as ``noc.inter_block_byte_hops``)."""
        placement = self.placement
        lp = self.plan.layers[src_li]
        nbytes = lp.out_pixels * lp.c_out  # 8b activations
        NoCTransport(placement.noc, base=0, counters=traffic,
                     recorder=self.recorder).record(
            placement.block_end[src_li], placement.block_start[dst_li],
            OFM, nbytes)

    def run(self, images: np.ndarray) -> NetworkSimResult:
        """images: (B, H, W, 3) or (H, W, 3) -> logits (B, classes)."""
        squeeze = images.ndim == 3
        x = np.asarray(images, np.float64)
        if squeeze:
            x = x[None]
        counters = SimCounters()
        traffic = TrafficCounters()
        self.placement.noc.link_traffic.clear()  # per-run link stats
        saved: Dict[str, Tuple[np.ndarray, Optional[int]]] = {}
        for s, stage in enumerate(self._stages):
            x = self._exec_stage(stage, x, saved, counters, traffic)
            if s + 1 < len(self._stages):
                self._record_ofm(stage.li, self._stages[s + 1].li, traffic)
        return NetworkSimResult(
            logits=x[0] if squeeze else x,
            counters=counters, traffic=traffic)

    def run_stream(self, frames: np.ndarray,
                   arrivals: Optional[np.ndarray] = None,
                   batched: bool = True,
                   chunk: Optional[int] = None) -> StreamResult:
        """Pipelined stream computing: overlap ``T`` frames across the
        layer pipeline and *measure* the steady-state initiation
        interval from the simulated stage timeline.

        ``frames``: (T, H, W, 3) — each frame is one inference (the
        serving direction streams frames, not batches).  ``arrivals``
        optionally gives each frame's arrival cycle (non-decreasing; the
        request-queue front-end in ``runtime/serve_loop.py`` uses it);
        by default all frames are ready at cycle 0 and the pipeline runs
        back-pressure-limited, so the measured II is the slowest stage's
        initiation interval — the quantity ``plan_network`` bounds
        analytically (cross-checked via :attr:`StreamResult.analytic_ii`).
        A single frame is accepted (``measured_ii=None`` — there is no
        exit spacing to measure).

        Two equal-by-construction execution strategies:

        * ``batched=True`` (default) decouples numerics from timing.
          The *numerics pass* runs all frames stage-major — stage ``k``
          consumes the ``(T, ...)`` tensor stage ``k-1`` produced — in
          micro-batches of ``chunk`` frames (default
          ``DEFAULT_STREAM_CHUNK``), riding the same batched trace
          gathers/gemms the sequential :meth:`run` uses.  Bitwise-free:
          ``gemm_rows`` pads remainder row blocks so a frame's bits
          never depend on its batch neighbours, hence neither batching
          nor chunk boundaries can change an OFM bit.  The *timing /
          accounting pass* is purely analytic: the wavefront recurrence
          vectorizes over frames (:func:`stream_timeline`), the
          residual-FIFO depth has a closed form over (save, add) stage
          pairs, and per-frame counters/transport records replay the
          same analytic accounting the trace executors emit per frame —
          every increment is batch- and value-independent, so the replay
          is bit-identical to interleaved execution.
        * ``batched=False`` is the per-cell oracle: the original
          interleaved wavefront loop, one ``_exec_stage`` call per
          (frame, stage) cell with timing and accounting inline.  The
          differential suite (``tests/test_streaming.py``,
          ``--stream-smoke``) holds the batched path bitwise to it.

        Per-frame OFMs are bitwise-equal to the sequential trace run of
        the same frames on both paths, and each frame carries its own
        ``SimCounters``/``TrafficCounters``.
        """
        if not self.streaming:
            raise ValueError(
                "run_stream requires NetworkSimulator(..., "
                "backend='trace', streaming=True)")
        frames = np.asarray(frames, np.float64)
        if frames.ndim != 4:
            raise ValueError(f"frames must be (T, H, W, C): {frames.shape}")
        t_n = frames.shape[0]
        if t_n < 1:
            raise ValueError("run_stream needs at least one frame")
        stages = self._stages
        s_n = len(stages)
        if arrivals is None:
            arr = np.zeros(t_n, np.int64)
        else:
            arr = np.asarray(arrivals, np.int64)
            if arr.shape != (t_n,):
                raise ValueError(
                    f"arrivals must be one cycle per frame: {arr.shape}")
            if not (np.diff(arr) >= 0).all():
                raise ValueError("arrivals must be in FIFO order")
        occ = [st.occupancy for st in stages]
        lat = [st.latency for st in stages]
        self.placement.noc.link_traffic.clear()  # per-stream link stats
        counters = [SimCounters() for _ in range(t_n)]
        traffic = [TrafficCounters() for _ in range(t_n)]
        if batched:
            logits, batch_sizes = self._stream_numerics(frames, chunk)
            with span("net.account", cat="network", frames=t_n):
                for t in range(t_n):
                    self._account_frame(counters[t], traffic[t])
            with span("net.timeline", cat="network", frames=t_n):
                start, finish = stream_timeline(arr, occ, lat)
                fifo_depth = self._residual_fifo_depth(t_n)
        else:
            logits, start, finish, fifo_depth = self._stream_percell(
                frames, arr, occ, lat, counters, traffic)
            batch_sizes = (1,) * t_n
        exits = finish[:, -1]
        return StreamResult(
            logits=logits, frame_counters=counters,
            frame_traffic=traffic, arrivals=arr, start=start, finish=finish,
            occupancy=tuple(occ),
            measured_ii=int(exits[-1] - exits[-2]) if t_n >= 2 else None,
            analytic_ii=self.plan.initiation_interval,
            fill_latency=int(exits[0] - arr[0]),
            residual_fifo_depth=fifo_depth,
            batch_sizes=batch_sizes)

    # -- streaming: batched numerics pass ------------------------------------

    def _stream_numerics(self, frames: np.ndarray, chunk: Optional[int]
                         ) -> Tuple[np.ndarray, Tuple[int, ...]]:
        """Stage-major batched execution of all frames, math only.

        Counters and traffic go to throwaway sinks and ``account=False``
        suppresses every transport record, so this pass leaves the NoC
        link stats, the telemetry recorder and the per-frame counters
        untouched — the accounting pass owns those."""
        chunk = DEFAULT_STREAM_CHUNK if chunk is None else int(chunk)
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1: {chunk}")
        sink_c, sink_t = SimCounters(), TrafficCounters()
        outs: List[np.ndarray] = []
        sizes: List[int] = []
        for lo in range(0, frames.shape[0], chunk):
            x = frames[lo:lo + chunk]
            sizes.append(x.shape[0])
            saved: Dict[str, Tuple[np.ndarray, Optional[int]]] = {}
            for stage in self._stages:
                x = self._exec_stage(stage, x, saved, sink_c, sink_t,
                                     account=False)
            assert not saved
            outs.append(x)
        return np.concatenate(outs, axis=0), tuple(sizes)

    # -- streaming: analytic timing / accounting pass ------------------------

    def _account_frame(self, counters: SimCounters,
                       traffic: TrafficCounters) -> None:
        """Replay one frame's accounting — the exact counter increments
        and routed transport records the per-cell wavefront emits for a
        single frame, without executing any numerics.  Every increment
        is a function of the plan alone (``TraceExecutor._account`` is
        fully analytic; ``simulate_fc``'s accounting is batch- and
        value-independent, so a zero probe row replays it)."""
        saved: Dict[str, Tuple[Optional[int], int]] = {}
        stages = self._stages
        for s, stage in enumerate(stages):
            self._account_stage(stage, saved, counters, traffic)
            if s + 1 < len(stages):
                self._record_ofm(stage.li, stages[s + 1].li, traffic)

    def _account_stage(self, stage: _Stage,
                       saved: Dict[str, Tuple[Optional[int], int]],
                       counters: SimCounters,
                       traffic: TrafficCounters) -> None:
        """Accounting-only mirror of :meth:`_exec_stage` for one frame.
        ``saved`` maps residual saves to (producing layer, frame bytes)."""
        placement = self.placement
        noc = placement.noc
        li = stage.li
        layer = self.cnn.layers[li]
        transport = NoCTransport(noc, base=placement.block_start[li],
                                 counters=traffic, recorder=self.recorder)
        if stage.kind == "fc":
            # account_only walks the grid dataflow and emits its
            # (value-independent) increments without the weight gemm —
            # the probe row only sets the batch shape
            c_in = self.params[layer.name].shape[0]
            act = "relu" if li < len(self.cnn.layers) - 1 else None
            simulate_fc(
                np.zeros((1, c_in)),
                np.asarray(self.params[layer.name], np.float64),
                self.n_c, self.n_m, activation=act,
                counters=counters, transport=transport,
                engine=self.pe_engine, handle=self._handles[li],
                account_only=True)
            return
        mesh_root = NoCTransport(noc, base=0, counters=traffic,
                                 recorder=self.recorder)
        if layer.name.endswith("_a"):
            # the saved value is the *input* to the `_a` layer
            saved[layer.name] = (stage.prev_li, layer.h * layer.w * layer.c)
        self._account_layer(li, transport, counters)
        if layer.residual_from is not None:
            src_li, res_bytes = saved.pop(layer.residual_from)
            if stage.sc_li is not None:
                sc_li = stage.sc_li
                sc_tr = NoCTransport(noc, base=placement.block_start[sc_li],
                                     counters=traffic,
                                     recorder=self.recorder)
                self._record_residual(mesh_root, src_li,
                                      placement.block_start[sc_li],
                                      res_bytes)
                self._account_layer(sc_li, sc_tr, counters)
                lp = self.plan.layers[sc_li]
                mesh_root.record(placement.block_end[sc_li],
                                 placement.block_end[li], RESIDUAL,
                                 lp.out_pixels * lp.c_out)
            else:
                self._record_residual(mesh_root, src_li,
                                      placement.block_end[li], res_bytes)
            lp = self.plan.layers[li]
            counters.act_ops += lp.out_pixels * lp.c_out  # post-add ReLU

    def _account_layer(self, li: int, transport: NoCTransport,
                       counters: SimCounters) -> None:
        """One conv layer's analytic accounting (every strip)."""
        strips = self._strips.get(li)
        if strips is None:
            self._executor(li, 0, self.schedules[li], transport,
                           counters)._account()
        else:
            for si, strip in enumerate(strips):
                self._executor(li, si, strip.sched, transport,
                               counters)._account()

    def _residual_fifo_depth(self, t_n: int) -> int:
        """Closed form of the per-cell loop's FIFO occupancy maximum.

        A (save stage ``ks``, add stage ``ka``) entry for frame ``t`` is
        alive after wavefront step ``m`` iff ``ks <= m - t < ka`` (saved
        when cell ``(t, ks)`` executes at step ``t + ks``, popped inside
        cell ``(t, ka)``), so the depth at step ``m`` counts the frames
        in that window for each pair."""
        pairs: List[Tuple[int, int]] = []
        save_stage: Dict[str, int] = {}
        for k, st in enumerate(self._stages):
            if st.kind != "conv":
                continue
            layer = self.cnn.layers[st.li]
            if layer.name.endswith("_a"):
                save_stage[layer.name] = k
            if layer.residual_from is not None:
                pairs.append((save_stage[layer.residual_from], k))
        if not pairs:
            return 0
        depth = 0
        for m in range(t_n + len(self._stages) - 1):
            d = 0
            for ks, ka in pairs:
                lo, hi = max(0, m - ka + 1), min(t_n - 1, m - ks)
                d += max(0, hi - lo + 1)
            depth = max(depth, d)
        return depth

    # -- streaming: interleaved per-cell oracle ------------------------------

    def _stream_percell(self, frames: np.ndarray, arr: np.ndarray,
                        occ: List[int], lat: List[int],
                        counters: List[SimCounters],
                        traffic: List[TrafficCounters]
                        ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
        """The original interleaved wavefront loop, kept verbatim as the
        differential-testing oracle: one ``_exec_stage`` call per
        (frame, stage) cell, timing recurrence and accounting inline."""
        t_n, s_n = frames.shape[0], len(self._stages)
        stages = self._stages
        saved: List[Dict[str, Tuple[np.ndarray, Optional[int]]]] = [
            {} for _ in range(t_n)]
        inflight: Dict[int, np.ndarray] = {}  # frame -> inter-stage value
        logits: List[Optional[np.ndarray]] = [None] * t_n
        start = np.zeros((t_n, s_n), np.int64)
        finish = np.zeros((t_n, s_n), np.int64)
        fifo_depth = 0
        for step in range(t_n + s_n - 1):
            # wavefront: deeper stages hold older frames (t = step - k)
            for k in range(s_n - 1, -1, -1):
                t = step - k
                if not 0 <= t < t_n:
                    continue
                stage = stages[k]
                x = inflight.pop(t) if k else frames[t:t + 1]
                y = self._exec_stage(stage, x, saved[t], counters[t],
                                     traffic[t])
                # stage timeline: a stage initiates frame t when its
                # input is ready AND one initiation interval has passed
                # since it accepted frame t-1
                ready = finish[t, k - 1] if k else arr[t]
                init = ready if t == 0 \
                    else max(ready, start[t - 1, k] + occ[k])
                start[t, k] = init
                finish[t, k] = init + lat[k]
                if k + 1 < s_n:
                    self._record_ofm(stage.li, stages[k + 1].li, traffic[t])
                    inflight[t] = y
                else:
                    logits[t] = y[0]
            # shortcut FIFO occupancy across all in-flight frames
            fifo_depth = max(fifo_depth, sum(len(d) for d in saved))
        assert not inflight and all(lg is not None for lg in logits)
        return np.stack(logits), start, finish, fifo_depth

    def _record_residual(self, mesh_root: NoCTransport,
                         src_layer: Optional[int], dst_tile: int,
                         nbytes: int) -> None:
        """Shortcut stream: the saved block input travels from its
        producer block's tail to the join/projection site (8b acts).
        ``nbytes`` is one frame's saved-input footprint (H*W*C)."""
        if src_layer is None:
            return  # shortcut of the very first layer: off-chip input
        mesh_root.record(self.placement.block_end[src_layer], dst_tile,
                         RESIDUAL, nbytes)
