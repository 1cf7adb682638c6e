"""Pipelined streaming executor: frames overlapping across the layer
pipeline must be *bitwise* indistinguishable from the sequential trace
backend per frame (logits, ``SimCounters``, ``TrafficCounters``), the
steady-state initiation interval measured from the simulated stage
timeline must equal ``plan_network``'s analytic slowest-stage bound,
and the retired B=1 BLAS caveat must stay retired (``gemm_rows``
pins every product to a row-position-invariant gemm path).

The batched streaming path (numerics decoupled from the timing model)
is held bitwise to the per-cell oracle (``batched=False``) by the
differential suite below: per-frame logits, counters, traffic, the
start/finish timeline, residual-FIFO depth and per-link heatmaps."""
import numpy as np
import pytest
from conftest import int_params as _int_params

from repro.configs.cnn import CNN_BENCHMARKS, ConvLayer
from repro.core.network import (
    NetworkSimulator,
    stream_timeline,
    stream_timeline_scalar,
)
from repro.core.schedule import compile_conv_block
from repro.core.simulator import BlockSimulator, gemm_rows, simulate_fc
from repro.core.trace import TraceExecutor
from repro.core.transport import RESIDUAL
from repro.telemetry.heatmap import LinkRecorder, check_conservation


def _stream_setup(name, t_n, seed=0, **sim_kw):
    rng = np.random.default_rng(seed)
    cnn = CNN_BENCHMARKS[name]()
    params = _int_params(cnn, rng)
    hw = cnn.input_hw
    frames = rng.integers(0, 2, (t_n, hw, hw, 3)).astype(np.float64)
    sim = NetworkSimulator(cnn, params, backend="trace", streaming=True,
                           **sim_kw)
    return sim, frames


# ---------------------------------------------------------------------------
# Streaming vs sequential: per-frame bitwise equality
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,t_n", [("vgg11-cifar10", 5),
                                      ("resnet18-cifar10", 4)])
def test_stream_bitwise_equals_sequential(name, t_n):
    """Per-frame OFMs from the pipeline equal both the batched
    sequential run (frames as batch lanes) and T independent B=1
    sequential runs — bitwise, with per-frame counters preserved."""
    sim, frames = _stream_setup(name, t_n)
    res = sim.run_stream(frames)
    assert res.logits.shape[0] == t_n
    seq = sim.run(frames)
    assert res.logits.tobytes() == seq.logits.tobytes()
    for t in range(t_n):
        one = sim.run(frames[t])
        assert np.array_equal(one.logits, res.logits[t])
        assert one.counters == res.frame_counters[t]
        assert one.traffic.byte_hops == res.frame_traffic[t].byte_hops
        assert one.traffic.packets == res.frame_traffic[t].packets
        assert one.traffic.hops == res.frame_traffic[t].hops


def test_stream_bitwise_under_profiler():
    """The hot path's spans and counters change no bit: the pipelined
    run under an installed profiler equals the sequential run without
    one, logits and per-frame counters."""
    from repro.telemetry.spans import Profiler

    sim, frames = _stream_setup("resnet18-cifar10", 4)
    seq = sim.run(frames)
    with Profiler(annotate=False) as prof:
        res = sim.run_stream(frames)
    assert res.logits.tobytes() == seq.logits.tobytes()
    for t in range(4):
        one = sim.run(frames[t])
        assert one.counters == res.frame_counters[t]
        assert one.traffic.byte_hops == res.frame_traffic[t].byte_hops
    assert any(e["name"] == "te.tail" for e in prof.events)


def test_stream_residuals_cross_the_skew():
    """ResNet shortcuts are buffered across the pipeline skew (the
    paper's FIFO forwarding): with several frames in flight, more than
    one saved block input is alive at once, and every frame still
    carries its own RESIDUAL-class routed traffic."""
    sim, frames = _stream_setup("resnet18-cifar10", 4)
    res = sim.run_stream(frames)
    assert res.residual_fifo_depth >= 2  # overlapping frames, not just 1
    for t in range(4):
        assert res.frame_traffic[t].byte_hops[RESIDUAL] > 0
        assert res.frame_traffic[t].packets[RESIDUAL] > 0


# ---------------------------------------------------------------------------
# Measured initiation interval == analytic bound
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["vgg11-cifar10", "resnet18-cifar10"])
def test_stream_measured_ii_equals_analytic(name):
    sim, frames = _stream_setup(name, 5)
    res = sim.run_stream(frames)
    assert res.measured_ii == res.analytic_ii \
        == sim.plan.initiation_interval
    # the steady state is reached from frame 1 on: every exit-to-exit
    # delta equals the measured II, not just the last pair
    deltas = np.diff(res.finish[:, -1])
    assert (deltas == res.measured_ii).all()
    # throughput at the Tab. 3 step clock reproduces the Tab. 4 rate
    assert res.inferences_per_s(10e6) == pytest.approx(
        10e6 / sim.plan.initiation_interval)
    # fill is pipeline depth, far above the steady-state interval
    assert res.fill_latency > res.measured_ii
    assert res.total_cycles == res.fill_latency + \
        (len(frames) - 1) * res.measured_ii


def test_stream_arrival_limited_vs_backpressure_limited():
    """Spaced arrivals: when requests arrive slower than the pipeline's
    initiation interval, exits are arrival-limited and every frame sees
    the bare fill latency; back-to-back arrivals queue instead."""
    sim, frames = _stream_setup("vgg11-cifar10", 4)
    ii = sim.plan.initiation_interval
    spaced = sim.run_stream(
        frames, arrivals=np.arange(4, dtype=np.int64) * (ii * 50))
    assert (spaced.frame_latency == spaced.fill_latency).all()
    assert spaced.measured_ii == ii * 50  # exit spacing = arrival spacing
    burst = sim.run_stream(frames)  # all at cycle 0
    lat = burst.frame_latency
    assert (np.diff(lat) == burst.measured_ii).all()  # queueing delay grows
    # arrivals never change the math
    assert spaced.logits.tobytes() == burst.logits.tobytes()


def test_stream_flag_validation():
    rng = np.random.default_rng(2)
    cnn = CNN_BENCHMARKS["vgg11-cifar10"]()
    params = _int_params(cnn, rng)
    with pytest.raises(ValueError):  # streaming needs the trace backend
        NetworkSimulator(cnn, params, streaming=True)
    with pytest.raises(ValueError, match="quantized engine"):
        # the exact engine has no jit flavor, streaming or not
        NetworkSimulator(cnn, params, backend="trace", trace_jit=True,
                         streaming=True)
    sim = NetworkSimulator(cnn, params, backend="trace")
    x = rng.integers(0, 2, (2, 32, 32, 3)).astype(np.float64)
    with pytest.raises(ValueError):  # run_stream needs streaming=True
        sim.run_stream(x)
    stream_sim = NetworkSimulator(cnn, params, backend="trace",
                                  streaming=True)
    with pytest.raises(ValueError):  # zero frames is still rejected
        stream_sim.run_stream(x[:0])
    with pytest.raises(ValueError):  # so is a degenerate chunk
        stream_sim.run_stream(x, chunk=0)


def test_stream_accepts_single_frame():
    """A lone queued request runs as a stream: full timeline, counters
    and fill latency, with ``measured_ii=None`` (one exit has no
    spacing to measure) on both execution paths."""
    sim, frames = _stream_setup("vgg11-cifar10", 1)
    res = sim.run_stream(frames)
    oracle = sim.run_stream(frames, batched=False)
    assert res.measured_ii is None and oracle.measured_ii is None
    assert res.logits.tobytes() == oracle.logits.tobytes()
    seq = sim.run(frames)
    assert res.logits.tobytes() == seq.logits.tobytes()
    assert res.frame_counters[0] == seq.counters
    assert res.fill_latency == int(res.finish[0, -1] - res.arrivals[0]) > 0
    with pytest.raises(ValueError):  # no steady-state throughput at T=1
        res.inferences_per_s()


# ---------------------------------------------------------------------------
# Request-queue front-end (closed-loop serving stats)
# ---------------------------------------------------------------------------


def test_serve_stream_report():
    from repro.runtime.serve_loop import serve_stream

    sim, frames = _stream_setup("vgg11-cifar10", 6)
    rep = serve_stream(sim, frames)  # offered rate = the analytic II rate
    ii = sim.plan.initiation_interval
    # offered exactly at the pipeline's own rate: no queueing delay, so
    # every request sees the bare fill latency and throughput equals the
    # steady-state rate
    assert (rep.latency_cycles == rep.fill_latency).all()
    assert rep.measured_ii == rep.analytic_ii == ii
    assert rep.throughput_inf_s == pytest.approx(rep.clock_hz / ii)
    counts, edges = rep.latency_hist
    assert counts.sum() == len(frames)
    pct = rep.latency_percentiles()
    assert pct["p50"] == pct["p99"] == rep.fill_latency
    # each request's answer is the sequential run's, bit for bit
    assert rep.logits.tobytes() == sim.run(frames).logits.tobytes()
    # oversubscribed queue: latency grows linearly with position
    hot = serve_stream(sim, frames, offered_inf_s=4 * rep.clock_hz / ii)
    assert hot.latency_cycles[-1] > hot.latency_cycles[0]


# ---------------------------------------------------------------------------
# The retired B=1 BLAS caveat (gemv / remainder-row-block dispatch)
# ---------------------------------------------------------------------------


def test_b1_float_block_bitwise_regression():
    """Unbatched runs with inexact float data: trace must equal interp
    bitwise — this was the documented gemv caveat before ``gemm_rows``
    pinned single-row products to the gemm path."""
    rng = np.random.default_rng(42)
    for c in (5, 64, 256):
        h = w = 9
        m, k = 8, 3
        ifm = rng.standard_normal((h, w, c))
        wts = rng.standard_normal((k, k, c, m))
        sched = compile_conv_block(f"b1-{c}", h, w, c, m, k, 1, 1)
        out_i = BlockSimulator(sched, wts).run(ifm)
        out_t = TraceExecutor(sched, wts).run(ifm)
        assert out_i.tobytes() == out_t.tobytes(), f"c_in={c}"


def test_b1_float_network_bitwise_regression():
    """Whole-network float-data B=1: interp == trace bitwise, and the
    single frame equals its own lane of a batched run."""
    rng = np.random.default_rng(5)
    cnn = CNN_BENCHMARKS["vgg11-cifar10"]()
    params = {
        l.name: (rng.standard_normal((l.k, l.k, l.c, l.m))
                 if isinstance(l, ConvLayer)
                 else rng.standard_normal((l.c_in, l.c_out)))
        for l in cnn.layers
    }
    x = rng.standard_normal((3, 32, 32, 3))
    one_i = NetworkSimulator(cnn, params).run(x[0])
    tr = NetworkSimulator(cnn, params, backend="trace")
    one_t = tr.run(x[0])
    assert one_i.logits.tobytes() == one_t.logits.tobytes()
    batched = tr.run(x)  # B=3: a remainder row block before gemm_rows
    assert np.array_equal(batched.logits[0], one_t.logits)


def test_gemm_rows_is_row_position_invariant():
    """The primitive underneath the guarantee: any row of any product
    equals the same row computed alone, including remainder-block row
    counts (1..3 and tails like 6 or 81) and the narrow FC head."""
    rng = np.random.default_rng(9)
    for n in (10, 64):  # 10: the output width that exposed edge kernels
        w = rng.standard_normal((256, n))
        a = rng.standard_normal((81, 256)) * 1e15  # inexact everywhere
        full = gemm_rows(a, w)
        for m in (1, 2, 3, 4, 6, 81):
            sub = gemm_rows(a[:m], w)
            assert np.array_equal(sub, full[:m]), (n, m)
    # and the out= flavor the trace executor uses
    a, w = rng.standard_normal((3, 64)), rng.standard_normal((64, 7))
    out = np.empty((3, 7))
    assert gemm_rows(a, w, out=out) is out
    assert np.array_equal(out, gemm_rows(a, w))


def test_fc_b1_equals_batched_lane():
    """simulate_fc shares gemm_rows: a single request's FC result equals
    its lane of a batched run even for inexact data (the 10-class head
    previously hit a different BLAS edge kernel per batch size)."""
    rng = np.random.default_rng(3)
    x = rng.standard_normal((6, 512)) * 1e12
    w = rng.standard_normal((512, 10))
    full = simulate_fc(x, w, 256, 256)
    for b in (1, 2, 3, 6):
        sub = simulate_fc(x[:b], w, 256, 256)
        assert np.array_equal(sub, full[:b]), b


# ---------------------------------------------------------------------------
# Batched streaming vs the per-cell oracle: the differential suite
# ---------------------------------------------------------------------------

# one simulator is shared across the T sweep of each (model, engine)
# combo; a single-slot cache keeps peak memory at one model's weights
_SIM_SLOT = {"key": None, "sim": None, "hw": None}


def _diff_sim(name, engine):
    if _SIM_SLOT["key"] != (name, engine):
        rng = np.random.default_rng(0)
        cnn = CNN_BENCHMARKS[name]()
        kw = {}
        if engine == "cim":
            kw = dict(engine="cim", calib_images=rng.random(
                (2, cnn.input_hw, cnn.input_hw, 3)))
        _SIM_SLOT["key"] = (name, engine)
        _SIM_SLOT["sim"] = NetworkSimulator(
            cnn, _int_params(cnn, rng), backend="trace", streaming=True,
            **kw)
        _SIM_SLOT["hw"] = cnn.input_hw
    return _SIM_SLOT["sim"], _SIM_SLOT["hw"]


def _traffic_views(ft):
    return (dict(ft.byte_hops), dict(ft.packets), dict(ft.hops))


def _stream_with_recorder(sim, frames, batched):
    rec = LinkRecorder(sim.placement.noc)
    sim.recorder = rec
    try:
        res = sim.run_stream(frames, batched=batched)
    finally:
        sim.recorder = None
    return res, rec, dict(sim.placement.noc.link_traffic)


_DIFF_CASES = [
    pytest.param(name, engine, t_n,
                 marks=([pytest.mark.slow] if "imagenet" in name else []),
                 id=f"{name}-{engine}-T{t_n}")
    for name in ("vgg11-cifar10", "resnet18-cifar10", "vgg16-imagenet",
                 "vgg19-imagenet", "resnet50-imagenet")
    for engine in ("exact", "cim")
    for t_n in (1, 2, 6)
]


@pytest.mark.parametrize("name,engine,t_n", _DIFF_CASES)
def test_stream_batched_equals_percell(name, engine, t_n):
    """The decoupled batched path is bitwise-identical to the per-cell
    oracle in every observable: per-frame logits, per-frame counters
    and routed traffic, the start/finish timeline, the residual-FIFO
    depth, the NoC link stats and the per-link telemetry heatmap —
    which also passes exact-integer conservation against the summed
    per-frame traffic."""
    sim, hw = _diff_sim(name, engine)
    rng = np.random.default_rng(7)
    frames = rng.integers(0, 2, (t_n, hw, hw, 3)).astype(np.float64)
    res_b, rec_b, links_b = _stream_with_recorder(sim, frames, True)
    res_o, rec_o, links_o = _stream_with_recorder(sim, frames, False)
    assert res_b.logits.tobytes() == res_o.logits.tobytes()
    assert np.array_equal(res_b.start, res_o.start)
    assert np.array_equal(res_b.finish, res_o.finish)
    assert np.array_equal(res_b.arrivals, res_o.arrivals)
    assert res_b.residual_fifo_depth == res_o.residual_fifo_depth
    assert res_b.measured_ii == res_o.measured_ii
    assert (res_b.measured_ii is None) == (t_n == 1)
    for t in range(t_n):
        assert res_b.frame_counters[t] == res_o.frame_counters[t], t
        assert _traffic_views(res_b.frame_traffic[t]) == \
            _traffic_views(res_o.frame_traffic[t]), t
    # NoC link stats and telemetry heatmaps agree link-for-link
    assert links_b == links_o
    assert rec_b.link_bytes == rec_o.link_bytes
    # and the heatmap conserves exactly against the summed frame traffic
    total = {}
    for ft in res_b.frame_traffic:
        for kind, v in ft.byte_hops.items():
            total[kind] = total.get(kind, 0) + v

    class _Total:
        byte_hops = total
    assert check_conservation(rec_b.heatmap(), _Total) == []
    # the batched path really batched (and the oracle really did not)
    assert sum(res_b.batch_sizes) == t_n
    assert res_o.batch_sizes == (1,) * t_n


def test_stream_chunk_boundaries_are_bitwise_free():
    """Any frame-axis chunking of the numerics pass produces identical
    results (gemm_rows row-position invariance), and the realized
    micro-batch sizes are reported."""
    sim, frames = _stream_setup("resnet18-cifar10", 5)
    whole = sim.run_stream(frames)
    assert whole.batch_sizes == (5,)
    for chunk in (1, 2, 3, 16):
        res = sim.run_stream(frames, chunk=chunk)
        assert res.logits.tobytes() == whole.logits.tobytes(), chunk
        assert sum(res.batch_sizes) == 5
        assert max(res.batch_sizes) <= chunk
    assert sim.run_stream(frames, chunk=2).batch_sizes == (2, 2, 1)


# ---------------------------------------------------------------------------
# The vectorized timing recurrence == the scalar loop (property test)
# ---------------------------------------------------------------------------


def _assert_timeline_equal(rng):
    s_n = int(rng.integers(1, 8))
    t_n = int(rng.integers(1, 12))
    occ = rng.integers(1, 60, s_n).tolist()
    lat = [int(o + d) for o, d in zip(occ, rng.integers(0, 80, s_n))]
    arr = np.sort(rng.integers(0, 400, t_n)).astype(np.int64)
    start_v, finish_v = stream_timeline(arr, occ, lat)
    start_s, finish_s = stream_timeline_scalar(arr, occ, lat)
    assert np.array_equal(start_v, start_s)
    assert np.array_equal(finish_v, finish_s)


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=80, deadline=None)
    @given(st.integers(0, 2**32 - 1))
    def test_stream_timeline_vectorized_equals_scalar(seed):
        """Property over random arrival vectors / stage shapes: the
        max-plus prefix-scan timeline equals the per-cell recurrence."""
        _assert_timeline_equal(np.random.default_rng(seed))
except ImportError:  # hypothesis not installed: seeded fuzz fallback
    @pytest.mark.parametrize("seed", range(80))
    def test_stream_timeline_vectorized_equals_scalar(seed):
        """Property over random arrival vectors / stage shapes: the
        max-plus prefix-scan timeline equals the per-cell recurrence."""
        _assert_timeline_equal(np.random.default_rng(seed))


def test_stream_timeline_matches_percell_run():
    """The analytic timeline is the one the per-cell executor measures,
    including spaced (arrival-limited) injection."""
    sim, frames = _stream_setup("resnet18-cifar10", 4)
    arr = np.array([0, 10, 5000, 5001], np.int64)
    batched = sim.run_stream(frames, arrivals=arr)
    oracle = sim.run_stream(frames, arrivals=arr, batched=False)
    assert np.array_equal(batched.start, oracle.start)
    assert np.array_equal(batched.finish, oracle.finish)
    occ = [st_.occupancy for st_ in sim._stages]
    lat = [st_.latency for st_ in sim._stages]
    start, finish = stream_timeline(arr, occ, lat)
    assert np.array_equal(start, oracle.start)
    assert np.array_equal(finish, oracle.finish)


# ---------------------------------------------------------------------------
# Per-stage setup happens once, at construction (Profiler span assertion)
# ---------------------------------------------------------------------------


def test_stage_setup_happens_once_per_simulator():
    """Compiled closures/scratch are built in ``__init__`` — repeated
    ``serve_stream``/``run_stream`` calls on one simulator must emit no
    further lowering/executor/jit-build spans, and the executor objects
    (with their scratch and compiled plans) stay the same instances."""
    from repro.runtime.serve_loop import serve_stream
    from repro.telemetry.spans import Profiler

    prof_build = Profiler()
    with prof_build:
        sim, frames = _stream_setup("vgg11-cifar10", 3)
    built = [e["name"] for e in prof_build.events]
    assert any(n.startswith("trace_lower:") for n in built)
    assert any(n.startswith("executor_build:") for n in built)
    assert sim._executors  # eager, not lazy
    ids_before = {k: id(v) for k, v in sim._executors.items()}

    prof_run = Profiler()
    with prof_run:
        serve_stream(sim, frames)
        serve_stream(sim, frames, batch_window=2)
        sim.run_stream(frames, batched=False)
    names = [e["name"] for e in prof_run.events]
    assert not any(n.startswith(("trace_lower:", "executor_build:",
                                 "jit_build:")) for n in names), names
    assert {k: id(v) for k, v in sim._executors.items()} == ids_before


# ---------------------------------------------------------------------------
# serve_stream micro-batching window
# ---------------------------------------------------------------------------


def test_serve_stream_batch_window_and_metrics():
    """The admission window chunks the numerics batch without changing
    any reported number, a lone request serves cleanly, and the metrics
    registry exposes the realized micro-batch sizes."""
    from repro.runtime.serve_loop import serve_stream
    from repro.telemetry.metrics import MetricsRegistry

    sim, frames = _stream_setup("vgg11-cifar10", 6)
    base = serve_stream(sim, frames)
    reg = MetricsRegistry()
    rep = serve_stream(sim, frames, batch_window=2, metrics=reg)
    assert np.array_equal(rep.latency_cycles, base.latency_cycles)
    assert rep.measured_ii == base.measured_ii
    hist = reg.snapshot()["metrics"]["serve_batch_size"]["series"][0]
    assert hist["count"] == 3 and hist["sum"] == 6.0  # 6 frames / window 2

    lone = serve_stream(sim, frames[:1], metrics=reg)
    assert lone.measured_ii is None
    assert lone.completed == 1
    assert lone.latency_cycles[0] == lone.fill_latency
