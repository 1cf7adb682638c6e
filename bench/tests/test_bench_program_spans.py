"""The readers of the program's own spans (``telemetry/spans.py``) on
synthetic contexts, and the trace reduction's indifference to the
program's ``repro:`` annotations.  None of this touches a TPU."""
import gc
from types import SimpleNamespace

import pytest

from bench import cell, readings, tracing

STREAM = "resnet18.stream-b4"
MC = "resnet18.mc-all"

#: per-frame readers of one program span each
SPAN_READERS = {
    "te.pad.ms_per_frame": "te.pad",
    "te.quant.ms_per_frame": "te.quant",
    "te.step.ms_per_frame": "te.step",
    "te.fetch.ms_per_frame": "te.fetch",
    "te.tail.ms_per_frame": "te.tail",
    "net.residual.ms_per_frame": "net.residual",
    "net.account.ms_per_frame": "net.account",
}
#: per-trial readers of one program span each
TRIAL_READERS = {
    "mc.swap.quantize_ms_per_trial": "engine.quantize_w",
    "mc.swap.draw_ms_per_trial": "engine.draw",
}


def _ctx(workload=STREAM, **kw):
    return cell.Context(spec=cell.load_spec(workload), **kw)


@pytest.mark.parametrize("metric,span", sorted(SPAN_READERS.items()))
def test_span_reader_per_frame(metric, span):
    ctx = _ctx(frames=8, spans={span: [0.010, 0.006], "other": [5.0]})
    unit, value = cell.read_metric(metric, ctx)
    assert unit == "ms"
    assert value == pytest.approx(16.0 / 8)
    # a window without the span, or without frames, reads nothing
    assert cell.read_metric(metric, _ctx(frames=8))[1] is None
    assert cell.read_metric(metric, _ctx(spans={span: [1.0]}))[1] is None


@pytest.mark.parametrize("metric,span", sorted(TRIAL_READERS.items()))
def test_span_reader_per_trial(metric, span):
    ctx = _ctx(MC, trials=5, frames=56, spans={span: [0.1] * 7})
    unit, value = cell.read_metric(metric, ctx)
    assert unit == "ms"
    assert value == pytest.approx(700.0 / 5)
    assert cell.read_metric(metric, _ctx(MC, trials=5))[1] is None


@pytest.mark.parametrize("metric,per", [("host.gc_ms_per_frame", "frames"),
                                        ("mc.host.gc_ms_per_trial",
                                         "trials")])
def test_gc_readers(metric, per):
    """The program records collections, so a window without one reads 0,
    not nothing."""
    wl = MC if per == "trials" else STREAM
    ctx = _ctx(wl, spans={"gc": [0.001, 0.003]}, **{per: 4})
    assert cell.read_metric(metric, ctx) == ("ms", pytest.approx(1.0))
    assert cell.read_metric(metric, _ctx(wl, **{per: 4}))[1] == 0.0
    assert cell.read_metric(metric, _ctx(wl))[1] is None


def test_window_spans_nest_with_gc_spans():
    """The harness pairs the program's B/E events into durations; a
    collection inside a span neither breaks the pairing nor leaks into
    the enclosing span's name."""
    from repro.telemetry.spans import Profiler, span

    prof = Profiler()
    with prof:
        with span("te.tail"):
            gc.collect()
        with span("te.pad"):
            pass
    durs = cell._span_durations(prof.events)
    assert len(durs["te.tail"]) == len(durs["te.pad"]) == 1
    assert len(durs["gc"]) >= 1
    assert durs["gc"][0] <= durs["te.tail"][0]


def _ev(name, start, dur, stats=()):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                           stats=list(stats))


def _planes(program_events):
    host = [_ev("bench:window", 0, 1000), _ev("bench:call", 0, 1000),
            _ev("bench:tail_np", 600, 300)] + program_events
    dev = [_ev("fusion.1", 100, 100), _ev("fusion.2", 400, 100)]
    return [
        SimpleNamespace(name="/host:CPU", lines=[
            SimpleNamespace(name="python", events=host)]),
        SimpleNamespace(name="/device:TPU:0", lines=[
            SimpleNamespace(name=tracing.OPS_LINE, events=dev),
            SimpleNamespace(name="XLA Modules", events=[])]),
    ]


def test_reduce_planes_with_program_annotations():
    """The program's ``repro:`` annotations sit beside the benchmark's
    ``bench:`` ones in the profiler's host plane; the reduction keeps
    the benchmark's labels and window, and every trace metric reads the
    same with the program's annotations present as without."""
    program = [_ev("repro:serve_stream:resnet18-cifar10", 5, 990),
               _ev("repro:te.step", 90, 30), _ev("repro:te.fetch", 150, 400),
               _ev("repro:gc", 620, 50)]
    plain = tracing.reduce_planes(_planes([]))
    annotated = tracing.reduce_planes(_planes(program))
    assert (annotated.t0_ns, annotated.t1_ns) == (0, 1000)
    assert {label for label, _, _ in annotated.host} == {
        "window", "call", "tail_np"}
    assert annotated.busy_s() == plain.busy_s() == pytest.approx(200e-9)
    assert annotated.idle_by_label() == plain.idle_by_label()
    assert annotated.top_ops() == plain.top_ops()
    ctx = _ctx(trace=annotated)
    assert readings.idle_pct(ctx) == readings.idle_pct(_ctx(trace=plain))
