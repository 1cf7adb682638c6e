"""Telemetry subsystem: per-link heatmaps, Chrome traces, metrics.

The contracts under test:

* **Conservation** — the ``LinkRecorder``'s per-link byte-hop sums must
  equal the simulator's ``TrafficCounters`` per-class totals AND the
  energy model's analytic routed byte-hops *exactly* (integer
  equality), for random models and random DSE placements.  The
  recorder walks the same memoized XY routes the transports use, so
  this is equal-by-construction — the test guards the construction.
* **Zero overhead when off** — with no recorder and no profiler (the
  default), logits and traffic counters are bitwise-identical to a
  run with telemetry attached, on both the interp oracle and the
  compiled trace path.
* **Chrome traces** — emitted event streams are valid trace-event
  JSON: known phases, monotone timestamps, properly nested B/E pairs;
  the validator also rejects corrupted documents.
* **Hot-path spans and counters** — the trace executor's steps nest
  inside one ``serve_stream:`` root per call, the host->device byte
  counter equals what the layer shapes say it must, garbage collections
  are spans, and annotated spans reach the JAX profiler's own trace.
* **Metrics registry** — Prometheus data-model semantics: idempotent
  family creation, labelled series, cumulative histogram buckets,
  JSON-serializable snapshots.
"""
import gc
import glob
import json
import math
import os

import numpy as np
import pytest
from conftest import int_params as _int_params

from repro.configs.cnn import CNN_BENCHMARKS, CNNConfig, ConvLayer, FCLayer
from repro.core.energy import routed_byte_hops_per_class
from repro.core.engine import CIMEngine
from repro.core.mapping import plan_network
from repro.core.network import NetworkSimulator
from repro.core.schedule import compile_conv_block
from repro.core.trace import TraceExecutor
from repro.dse.placements import strategies
from repro.runtime.serve_loop import serve_stream
from repro.telemetry import (MetricsRegistry, Profiler, active_profiler,
                             check_conservation, chrome_trace, count,
                             record_run, span, stream_timeline_events,
                             validate_chrome_trace)

def _setup(name, batch=1, seed=0, **kw):
    rng = np.random.default_rng(seed)
    cnn = CNN_BENCHMARKS[name]()
    params = _int_params(cnn, rng)
    hw = cnn.input_hw
    x = rng.integers(0, 2, (batch, hw, hw, 3)).astype(np.float64)
    sim = NetworkSimulator(cnn, params, backend="trace", **kw)
    return cnn, params, x, sim


def _assert_conserved(cnn, sim, x):
    res, rec = record_run(sim, x)
    analytic = routed_byte_hops_per_class(cnn, sim.plan, sim.placement)
    problems = check_conservation(rec.heatmap(), res.traffic, analytic,
                                  flows=rec.flows.values())
    assert problems == [], "\n".join(problems)
    return res, rec


# ---------------------------------------------------------------------------
# Per-link conservation: heatmap == TrafficCounters == analytic, exactly
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["vgg11-cifar10", "resnet18-cifar10"])
def test_link_conservation_baseline(name):
    cnn, _, x, sim = _setup(name)
    res, rec = _assert_conserved(cnn, sim, x)
    hm = rec.heatmap()
    # the heatmap really is per-link: traffic spread over many links,
    # per-class totals match the simulator's counters integer-for-integer
    assert len(hm.combined()) > 10
    assert hm.class_totals() == {k: v for k, v in
                                 res.traffic.byte_hops.items() if v}


def test_link_conservation_random_placements():
    """Property sweep: random (model, placement, seed) draws — the
    three-way conservation holds under every DSE placement strategy,
    where routes (and so per-link attribution) differ from snake."""
    rng = np.random.default_rng(1234)
    models = ["vgg11-cifar10", "resnet18-cifar10"]
    built = {}
    for _ in range(4):
        name = models[rng.integers(len(models))]
        if name not in built:
            cnn = CNN_BENCHMARKS[name]()
            built[name] = (cnn, _int_params(cnn, rng), plan_network(cnn))
        cnn, params, plan = built[name]
        strat_name = list(strategies(cnn))[
            rng.integers(len(strategies(cnn)))]
        placement = strategies(cnn)[strat_name].place(plan)
        hw = cnn.input_hw
        x = rng.integers(0, 2, (1, hw, hw, 3)).astype(np.float64)
        sim = NetworkSimulator(cnn, params, backend="trace",
                               placement=placement)
        _assert_conserved(cnn, sim, x)


@pytest.mark.slow
@pytest.mark.parametrize("name,dup_cap", [
    ("vgg16-imagenet", 64), ("vgg19-imagenet", 64),
    ("resnet50-imagenet", 128)])
def test_link_conservation_all_models(name, dup_cap):
    """The remaining benchmark models (vgg19's trace run alone is
    ~45 s): conservation must be exact on width-striped stems,
    bottleneck projections and deep chains too."""
    cnn, _, x, sim = _setup(name, dup_cap=dup_cap)
    _assert_conserved(cnn, sim, x)


def test_recorder_detached_after_record_run():
    """record_run attaches a fresh recorder and always detaches it —
    subsequent runs pay zero accounting."""
    cnn, _, x, sim = _setup("vgg11-cifar10")
    _, rec = record_run(sim, x)
    assert sim.recorder is None
    assert rec.flows  # but the recorder kept its flows
    before = {k: dict(v) for k, v in rec.heatmap().per_class.items()}
    sim.run(x)  # recorder is detached: nothing accumulates
    after = {k: dict(v) for k, v in rec.heatmap().per_class.items()}
    assert before == after


# ---------------------------------------------------------------------------
# Telemetry off (the default): bitwise-identical results
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["interp", "trace"])
def test_telemetry_off_bitwise(backend):
    """Recorder attached / profiler installed / plain — all three give
    bitwise-equal logits and equal traffic counters on vgg11, on both
    the per-cycle interp oracle and the compiled trace path."""
    cnn, params, x, _ = _setup("vgg11-cifar10")
    sim = NetworkSimulator(cnn, params, backend=backend)
    plain = sim.run(x)
    recorded, _ = record_run(sim, x)
    with Profiler():
        profiled = sim.run(x)
    assert plain.logits.tobytes() == recorded.logits.tobytes()
    assert plain.logits.tobytes() == profiled.logits.tobytes()
    for other in (recorded, profiled):
        assert plain.traffic.byte_hops == other.traffic.byte_hops
        assert plain.traffic.packets == other.traffic.packets
        assert plain.counters == other.counters


def test_span_is_null_without_profiler():
    """The module-level span() is the hot-path hook: with no profiler
    installed it returns the shared null span (no allocation, no
    timestamps) and swallows nothing."""
    s1 = span("anything", cat="host", arg=1)
    s2 = span("else")
    assert s1 is s2  # the shared singleton
    with s1:
        pass
    with pytest.raises(RuntimeError):
        with span("propagates"):
            raise RuntimeError("through")


# ---------------------------------------------------------------------------
# Chrome trace-event JSON: emission and validation
# ---------------------------------------------------------------------------


def test_profiler_spans_nest_and_validate():
    prof = Profiler()
    with prof:
        with span("outer", cat="host", depth=0):
            with span("inner", cat="jit", depth=1):
                count("queue", 3)
        count("queue", 2)
    doc = chrome_trace(prof.events + [prof.counts_event()])
    assert validate_chrome_trace(doc) == []
    names = [e["name"] for e in doc["traceEvents"]]
    assert names.count("outer") == 2 and names.count("inner") == 2
    # nesting: inner closes before outer (LIFO), args survive
    b_outer = next(e for e in doc["traceEvents"]
                   if e["name"] == "outer" and e["ph"] == "B")
    assert b_outer["args"] == {"depth": 0}
    # the totals export as one counter event, after every span
    c = doc["traceEvents"][-1]
    assert c["ph"] == "C" and c["args"] == {"queue": 5}


def test_count_totals_and_nothing_without_profiler():
    """count() adds to the installed profiler's totals, and with no
    profiler installed it records nothing anywhere."""
    idle = Profiler()
    assert active_profiler() is None
    count("h2d_bytes", 10)          # no profiler: a no-op
    with Profiler() as prof:
        for n in (1, 2, 3):
            count("h2d_bytes", n)
        count("dispatches", 1)
    count("h2d_bytes", 100)         # uninstalled again
    assert prof.counts == {"h2d_bytes": 6, "dispatches": 1}
    assert idle.counts == {} and idle.events == []


def test_gc_spans_nest_and_validate():
    """While a profiler is installed each garbage collection is a ``gc``
    span carrying its generation, nested wherever it ran; uninstalling
    removes the hook."""
    prof = Profiler()
    gc.disable()                    # only the explicit collections run
    try:
        with prof:
            assert prof._on_gc in gc.callbacks
            with span("outer"):
                gc.collect()
            gc.collect(0)
        assert prof._on_gc not in gc.callbacks
        gc.collect()                # not recorded
    finally:
        gc.enable()
    doc = chrome_trace(prof.events)
    assert validate_chrome_trace(doc) == []
    # (parent names, generation) of each gc span, in order
    stack, gcs = [], []
    for e in prof.events:
        if e["ph"] == "B":
            if e["name"] == "gc":
                gcs.append((list(stack), e["args"]["generation"]))
            stack.append(e["name"])
        elif e["ph"] == "E":
            stack.pop()
    assert gcs == [(["outer"], 2), ([], 0)]


def test_annotated_spans_reach_the_jax_trace(tmp_path):
    """Profiler(annotate=True) writes each span into the JAX profiler's
    trace as a ``repro:`` host event, on the trace's own clock."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with Profiler(annotate=True):
            with span("outer"):
                with span("inner"):
                    jnp.ones(3).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(files[0])
    got = {}
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro:"):
                    got[ev.name] = (ev.start_ns, ev.duration_ns)
    assert {"repro:outer", "repro:inner"} <= set(got)
    (o0, od), (i0, idur) = got["repro:outer"], got["repro:inner"]
    assert o0 <= i0 and i0 + idur <= o0 + od


def test_hot_path_spans_nest_in_one_call_and_count_h2d():
    """On the pallas jit path, every trace-executor and network span of a
    ``serve_stream`` call lies inside that call's one root span, and the
    host->device bytes are what the shapes say: per (layer, strip) the
    int8 stream plus the kernel's (tiles x n_c, M) int8 weight operand;
    per FC grid call its int8 input slice and weight block."""
    from repro.runtime.serve_loop import build_stream_sim

    rng = np.random.default_rng(3)
    cnn = CNN_BENCHMARKS["resnet18-cifar10"]()
    params = {k: v * 0.1 for k, v in _int_params(cnn, rng).items()}
    frames = rng.random((2, 32, 32, 3))
    sim = build_stream_sim(cnn, params, engine="pallas", trace_jit=True,
                           calib_images=frames[:1])
    plain = serve_stream(sim, frames)          # compiles outside the trace
    with Profiler() as prof:
        profiled = serve_stream(sim, frames)
    assert profiled.logits.tobytes() == plain.logits.tobytes()
    assert validate_chrome_trace(chrome_trace(prof.events)) == []

    roots, stack, inside = [], [], {}
    for e in prof.events:
        if e["ph"] == "B":
            if not stack:
                roots.append(e)
            elif e["name"].startswith(("te.", "net.")):
                assert stack[0]["name"] == "serve_stream:resnet18-cifar10"
                inside[e["name"]] = inside.get(e["name"], 0) + 1
            stack.append(e)
        elif e["ph"] == "E":
            stack.pop()
    assert [r["name"] for r in roots if r["name"] != "gc"] == [
        "serve_stream:resnet18-cifar10"]
    assert "call" in roots[0]["args"]
    n_ex = len(sim._executors)
    for name in ("te.pad", "te.quant", "te.step", "te.fetch", "te.tail"):
        assert inside[name] == n_ex, name
    assert inside["net.residual"] == 8 and inside["net.account"] == 1

    b, n_c = len(frames), sim.pe_engine.spec.n_c
    want_h2d = want_d2h = calls = 0
    for ex in sim._executors.values():
        sched = ex.sched
        want_h2d += b * ex.plan.n_pix * sched.c_in \
            + len(ex.plan.tiles) * n_c * sched.c_out
        want_d2h += 4 * b * ex.plan.fires * sched.c_out
        calls += 1
    for layer in cnn.layers:
        if not isinstance(layer, FCLayer):
            continue
        m_t, m_a = math.ceil(layer.c_in / n_c), math.ceil(layer.c_out
                                                          / sim.n_m)
        want_h2d += b * layer.c_in * m_a + layer.c_in * layer.c_out
        want_d2h += 4 * b * layer.c_out * m_t
        calls += m_t * m_a
    assert prof.counts["h2d_bytes"] == want_h2d
    assert prof.counts["d2h_bytes"] == want_d2h
    assert prof.counts["dispatches"] == calls
    # every scratch buffer was made in the first call
    assert prof.counts.get("scratch_alloc_bytes", 0) == 0


@pytest.mark.parametrize("use_jax", [False, True])
def test_scratch_retained_above_old_element_cap(use_jax):
    """A 16-frame 56x56x256 layer input — 12.8 M elements, above the 4 M
    float64 elements the scratch cap once kept — stages into an int8
    raster that is kept: from the second call on nothing is allocated,
    on the jit path and on the host path, whose gather buffer serves a
    fire axis cut into chunks of two lengths."""
    sched = compile_conv_block("wide", 56, 56, 256, 8, 1, 1, 0)
    rng = np.random.default_rng(4)
    x = rng.standard_normal((16, 56, 56, 256))
    w = rng.standard_normal((1, 1, 256, 8))
    eng = CIMEngine().set_layer("wide", a_scale=0.02)
    ex = TraceExecutor(sched, w, engine=eng, use_jax=use_jax)
    with Profiler() as first:
        want = ex.run(x)
    assert ex._scratch["raster8"].size > 1 << 22
    assert first.counts["scratch_alloc_bytes"] >= x.size
    if not use_jax:
        lens = {hi - lo for lo, hi in ex._quant_chunks(ex.plan.fires, 16)}
        assert len(lens) == 2
    with Profiler() as prof:
        got = ex.run(x)
    assert prof.counts.get("scratch_alloc_bytes", 0) == 0
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("engine", ["exact", "cim"])
def test_strip_pad_retained_across_calls(engine):
    """A layer wider than one instruction table runs as width strips over
    one padded input: that pad, like each strip executor's raster, is
    kept, so the second call allocates no scratch and reads the same."""
    cnn = CNNConfig("wide", "cifar10", 130, (
        ConvLayer("c1", 6, 130, 3, 4), FCLayer("fc", 6 * 130 * 4, 10)))
    rng = np.random.default_rng(6)
    params = _int_params(cnn, rng)
    x = rng.standard_normal((2, 6, 130, 3))
    kw = {} if engine == "exact" else {"engine": engine,
                                       "calib_images": x[:1]}
    sim = NetworkSimulator(cnn, params, backend="trace", **kw)
    assert 0 in sim._strips
    with Profiler() as first:
        want = sim.run(x)
    assert first.counts["scratch_alloc_bytes"] > 0
    with Profiler() as prof:
        got = sim.run(x)
    assert prof.counts.get("scratch_alloc_bytes", 0) == 0
    assert got.logits.tobytes() == want.logits.tobytes()


def test_stream_timeline_trace_is_valid():
    cnn, _, x, sim = _setup("vgg11-cifar10", batch=3, streaming=True)
    res = sim.run_stream(x)
    stage_names = [cnn.layers[st.li].name for st in sim._stages]
    events = stream_timeline_events(res, stage_names)
    doc = chrome_trace(events)
    assert validate_chrome_trace(doc) == []
    by_ph = {}
    for e in doc["traceEvents"]:
        by_ph[e["ph"]] = by_ph.get(e["ph"], 0) + 1
    # per-stage occupancy slices, per-frame async tracks, queue counters
    assert by_ph["X"] == len(stage_names) * len(x)
    assert by_ph["b"] == by_ph["e"] == len(x) * (len(stage_names) + 1)
    assert by_ph.get("C", 0) >= 2
    # timestamps are emitted monotone after the stable sort
    ts = [e["ts"] for e in doc["traceEvents"] if e["ph"] != "M"]
    assert ts == sorted(ts)


def test_trace_round_trips_through_json(tmp_path):
    from repro.telemetry import load_chrome_trace, write_chrome_trace

    prof = Profiler()
    with prof, span("roundtrip", cat="host"):
        pass
    path = tmp_path / "t.json"
    write_chrome_trace(str(path), prof.events)
    doc = load_chrome_trace(str(path))
    assert validate_chrome_trace(doc) == []
    assert doc["traceEvents"] == chrome_trace(prof.events)["traceEvents"]


@pytest.mark.parametrize("doc,fragment", [
    ("nope", "top-level"),                                 # not dict/list
    ({"nope": 1}, "traceEvents"),                          # key missing
    ({"traceEvents": [{"ph": "Z", "name": "x", "ts": 0.0,
                       "pid": 1, "tid": 1}]}, "unknown ph"),
    ({"traceEvents": [{"ph": "X", "name": 3, "ts": 0.0, "dur": 1.0,
                       "pid": 1, "tid": 1}]}, "name"),     # non-string name
    ({"traceEvents": [
        {"ph": "B", "name": "a", "ts": 1.0, "pid": 1, "tid": 1},
        {"ph": "E", "name": "b", "ts": 2.0, "pid": 1, "tid": 1},
    ]}, "closes"),                                         # B/E mismatch
    ({"traceEvents": [
        {"ph": "B", "name": "a", "ts": 5.0, "pid": 1, "tid": 1},
        {"ph": "E", "name": "a", "ts": 1.0, "pid": 1, "tid": 1},
    ]}, "previous"),                                       # ts goes back
    ({"traceEvents": [
        {"ph": "B", "name": "a", "ts": 1.0, "pid": 1, "tid": 1},
    ]}, "unclosed"),                                       # dangling B
])
def test_validator_rejects_corrupt_traces(doc, fragment):
    errors = validate_chrome_trace(doc)
    assert errors, f"expected errors for {doc!r}"
    assert any(fragment in e for e in errors), errors


# ---------------------------------------------------------------------------
# Metrics registry: Prometheus data-model semantics
# ---------------------------------------------------------------------------


def test_counter_and_gauge_semantics():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests")
    c.inc()
    c.inc(4.0)
    with pytest.raises(ValueError):
        c.inc(-1.0)
    g = reg.gauge("depth")
    g.set(7.0)
    g.inc(2.0)
    g.dec(3.0)
    snap = reg.snapshot()["metrics"]
    assert snap["reqs_total"]["series"][0]["value"] == 5.0
    assert snap["depth"]["series"][0]["value"] == 6.0
    assert snap["reqs_total"]["type"] == "counter"


def test_histogram_buckets_cumulative():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(1.0, 5.0, 10.0))
    for v in (0.5, 1.0, 3.0, 10.0, 99.0):  # 1.0 lands IN the le=1 bucket
        h.observe(v)
    rec = reg.snapshot()["metrics"]["lat"]["series"][0]
    assert rec["count"] == 5
    assert rec["sum"] == pytest.approx(113.5)
    assert rec["buckets"] == {"1.0": 2, "5.0": 3, "10.0": 4, "+Inf": 5}
    # cumulative counts are monotone and end at count
    vals = list(rec["buckets"].values())
    assert vals == sorted(vals) and vals[-1] == rec["count"]


def test_histogram_rejects_unsorted_buckets():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.histogram("bad", buckets=(5.0, 1.0))


def test_labelled_series_and_idempotent_families():
    reg = MetricsRegistry()
    fam = reg.counter("frames_total", labelnames=("tenant",))
    fam.labels(tenant="a").inc(2.0)
    fam.labels(tenant="b").inc()
    # idempotent: same (name, kind, labels) returns the same family
    again = reg.counter("frames_total", labelnames=("tenant",))
    assert again is fam
    again.labels(tenant="a").inc()
    snap = reg.snapshot()["metrics"]["frames_total"]
    assert snap["labelnames"] == ["tenant"]
    by_tenant = {s["labels"]["tenant"]: s["value"] for s in snap["series"]}
    assert by_tenant == {"a": 3.0, "b": 1.0}
    # wrong/missing labels and unlabelled proxy use are errors
    with pytest.raises(ValueError):
        fam.labels(nope="x")
    with pytest.raises(ValueError):
        fam.inc()


def test_registry_conflicts_raise():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(ValueError):
        reg.gauge("x")  # kind conflict
    reg.gauge("y", labelnames=("a",))
    with pytest.raises(ValueError):
        reg.gauge("y", labelnames=("b",))  # labelnames conflict


def test_snapshot_is_json_serializable(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.histogram("h").observe(3.0)
    reg.gauge("g", labelnames=("t",)).labels(t="0").set(1.5)
    path = reg.to_json(str(tmp_path / "m.json"))
    with open(path) as f:
        assert json.load(f) == json.loads(json.dumps(reg.snapshot()))


# ---------------------------------------------------------------------------
# Serving integration: metrics export and the zero-completed edge
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def stream_sim():
    cnn, params, x, sim = _setup("vgg11-cifar10", batch=4, streaming=True)
    return cnn, x, sim


def test_serve_stream_exports_metrics(stream_sim):
    _, frames, sim = stream_sim
    reg = MetricsRegistry()
    rep = serve_stream(sim, frames, metrics=reg,
                       metric_labels={"tenant": "t0"})
    snap = reg.snapshot()["metrics"]
    assert snap["serve_frames_total"]["series"][0]["value"] == len(frames)
    assert snap["serve_frames_total"]["series"][0]["labels"] \
        == {"tenant": "t0"}
    lat = snap["serve_latency_cycles"]["series"][0]
    assert lat["count"] == rep.completed == len(frames)
    assert lat["buckets"]["+Inf"] == len(frames)
    assert snap["serve_queue_depth"]["series"][0]["count"] == len(frames)
    assert snap["serve_goodput_inf_s"]["series"][0]["value"] \
        == pytest.approx(rep.throughput_inf_s)
    # a second tenant registers its own series with no refactor
    serve_stream(sim, frames[:2], metrics=reg,
                 metric_labels={"tenant": "t1"})
    series = reg.snapshot()["metrics"]["serve_frames_total"]["series"]
    assert {s["labels"]["tenant"] for s in series} == {"t0", "t1"}


def test_serve_stream_zero_requests(stream_sim):
    cnn, frames, sim = stream_sim
    reg = MetricsRegistry()
    rep = serve_stream(sim, frames[:0], metrics=reg)
    assert rep.completed == 0
    assert rep.latency_percentiles() == {}  # no np.percentile raise
    assert rep.throughput_inf_s == 0.0
    assert rep.latency_cycles.size == 0
    assert int(rep.latency_hist[0].sum()) == 0
    snap = reg.snapshot()["metrics"]
    assert snap["serve_frames_total"]["series"][0]["value"] == 0
