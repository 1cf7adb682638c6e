"""One benchmark cell on the chip: set up, measure a window, check.

    python3 bench/cell.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/``) and a traffic mix (``bench/traffic/``).  The run:

1. refuses to go on unless JAX finds a TPU (as many chips as the cell
   asks for) whose peaks are known (``peaks.json``);
2. makes the weights on the device from the seed, builds the simulator
   through the program's entry points, draws the inputs of every call
   from the seed, and warms up the cell's own shapes (set-up,
   ``setup_s``);
3. runs a closed loop of calls for ``--seconds`` (the window ends when
   the last call started before the deadline returns) and counts the
   programs compiled inside it, which should be none;
4. with ``--trace 1``, times the calls into the program's layers
   (``wrappers.json``, the program's own spans) during the window, then
   traces a few more calls with the JAX profiler;
5. frees the simulator and recomputes a sample of the window's calls
   with the plain reference (``correct.py``).

It prints each compared number beside its limit as the last lines of
standard error, and as the last line of standard output one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``; each read by ``bench/metrics/<name>.py``), ``device``,
``breakdown`` (traced runs) and ``checks``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# the bench package and the program, never bench/'s modules as top-level
sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
    p for p in sys.path if Path(p or ".").resolve() != BENCH]

# the TPU runtime logs to a fixed /tmp path unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"


@dataclass
class Spec:
    """A cell as ``BENCHMARK.json`` and its files define it."""

    name: str
    chips: int
    cfg: dict
    traffic: dict
    limits: dict
    end_to_end: List[str]
    per_layer: List[str]


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_spec(workload: str, root: Path = ROOT) -> Spec:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = json.loads((BENCH / "limits" / f"{workload}.json").read_text())
    return Spec(
        name=workload, chips=cell["chips"], cfg=cfg, traffic=traffic,
        limits=limits,
        end_to_end=[m["name"] for m in bench["end_to_end"]
                    if _applies(m, workload)],
        per_layer=[m["name"] for m in bench["per_layer"]
                   if _applies(m, workload)])


class Compiles:
    """Seconds and count of XLA compilations and persistent-cache loads
    (``jax.monitoring``); ``take`` returns and resets the tally."""

    def __init__(self):
        import jax

        self.n = 0
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == _COMPILE:
            self.n += 1
            self.secs += secs
        elif event == _CACHE_LOAD:
            self.secs += secs

    def take(self):
        out = (self.n, self.secs)
        self.n, self.secs = 0, 0.0
        return out


class Wrappers:
    """Host-clock wrappers around calls into the program's layers:
    inclusive seconds per label, and (while ``annotate``) a profiler
    annotation per call so idle gaps can be labelled."""

    def __init__(self, targets: Dict[str, str], required=()):
        import jax

        self.seconds: Dict[str, float] = {k: 0.0 for k in targets}
        self.annotate = False
        self._undo: List[Callable[[], None]] = []
        self._ann = jax.profiler.TraceAnnotation
        for label, target in targets.items():
            owner, attr = _resolve(target)
            if owner is not None:
                self._wrap(owner, attr, label)
            elif label in required:
                raise SystemExit(f"bench: wrapper {label!r} feeds a metric, "
                                 f"but the program has no {target}")
            else:
                print(f"bench: WARNING the program has no {target}; idle "
                      f"gaps go unlabelled by {label!r}", file=sys.stderr)

    def _wrap(self, owner, attr: str, label: str) -> None:
        orig = getattr(owner, attr)
        ann = self._ann
        tag = "bench:" + label

        @functools.wraps(orig)
        def timed(*a, **kw):
            t = time.perf_counter()
            try:
                if self.annotate:
                    with ann(tag):
                        return orig(*a, **kw)
                return orig(*a, **kw)
            finally:
                self.seconds[label] += time.perf_counter() - t

        setattr(owner, attr, timed)
        self._undo.append(lambda: setattr(owner, attr, orig))

    def remove(self) -> None:
        for undo in reversed(self._undo):
            undo()
        self._undo.clear()


def _resolve(target: str):
    """``module:Class.attr`` or ``module:function`` -> (owner, attr), or
    (None, None) where the program no longer has it."""
    mod_name, path = target.split(":")
    try:
        owner: Any = importlib.import_module(mod_name)
    except ImportError:
        return None, None
    *parents, attr = path.split(".")
    for p in parents:
        owner = getattr(owner, p, None)
        if owner is None:
            return None, None
    found = attr in owner.__dict__ if isinstance(owner, type) \
        else hasattr(owner, attr)
    return (owner, attr) if found else (None, None)


@dataclass
class Context:
    """What the metric readers read (``bench/metrics/<name>.py``)."""

    spec: Spec
    setup_s: float = 0.0
    window_s: float = 0.0
    frames: int = 0
    calls: int = 0
    call_s: List[float] = field(default_factory=list)
    trials: int = 0
    compile_setup_s: float = 0.0
    wrap: Dict[str, float] = field(default_factory=dict)
    spans: Dict[str, List[float]] = field(default_factory=dict)
    trace: Any = None
    traced_frames: int = 0
    traced_calls: int = 0
    runs_per_call: int = 1
    peaks: Optional[Dict[str, float]] = None
    layers: List[Any] = field(default_factory=list)


def read_metric(name: str, ctx: Context):
    path = BENCH / "metrics" / f"{name}.py"
    modspec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(modspec)
    modspec.loader.exec_module(mod)
    return mod.UNIT, mod.read(ctx)


def _span_durations(events) -> Dict[str, List[float]]:
    """Seconds of each closed program span, by name."""
    out: Dict[str, List[float]] = {}
    stack: List[dict] = []
    for ev in events:
        if ev.get("ph") == "B":
            stack.append(ev)
        elif ev.get("ph") == "E" and stack:
            b = stack.pop()
            out.setdefault(b["name"], []).append((ev["ts"] - b["ts"]) * 1e-6)
    return out


def _check_program_config(cnn, layers) -> None:
    """The program's model must be the configuration file's, layer for
    layer, or the reference would check another model."""
    from repro.configs.cnn import FCLayer

    got = []
    for layer in cnn.layers:
        if isinstance(layer, FCLayer):
            got.append(("fc", layer.name, layer.c_in, layer.c_out))
        else:
            got.append(("conv", layer.name, layer.h, layer.w, layer.c,
                        layer.m, layer.k, layer.s, layer.p, layer.pool_k,
                        layer.pool_s, layer.residual_from))
    want = []
    for l in layers:
        if l.kind == "fc":
            want.append(("fc", l.name, l.c_in, l.c_out))
        else:
            want.append(("conv", l.name, l.h, l.w, l.c, l.m, l.k, l.s, l.p,
                         l.pool_k, l.pool_s, l.residual_from))
    if got != want:
        raise SystemExit(f"the program's {cnn.name} is not the "
                         "configuration file's model")


def require_device(chips: int):
    """(first device, count, its peaks); exits non-zero without a TPU
    whose peaks are known.  Turns the persistent compilation cache on."""
    import jax

    from bench.roofline import peaks

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(f"bench: needs {chips} TPU chip(s); JAX found "
                         f"{len(devs)} {devs[0].platform} device(s) "
                         f"({devs[0].device_kind})")
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    return devs[0], len(devs), peaks(devs[0].device_kind)


def run(spec: Spec, seed: int, seconds: float, trace: bool, *,
        device=None, count: int = 1, peaks=None, low_bits=None,
        fault: Optional[Callable[[], Callable[[], None]]] = None,
        t_start: float = T_START) -> dict:
    """One run of a cell; returns the result object.  ``device``/``peaks``
    come from :func:`main`'s chip check.  ``low_bits`` serves every layer
    on the program's own narrower-precision path (the control);
    ``fault`` installs a broken timed path and returns its undo (the
    harness's tests)."""
    import numpy as np

    compiles = Compiles()

    from bench import entries
    from bench.correct import check
    from bench.inputs import make_weights, rngs
    from bench.reference import layers_of, macs_per_frame
    from repro.configs.cnn import CNN_BENCHMARKS

    cfg, traffic = spec.cfg, spec.traffic
    layers = layers_of(cfg)
    if macs_per_frame(cfg) != cfg["macs_per_frame"]:
        raise SystemExit("the configuration's macs_per_frame disagrees "
                         "with its layer shapes")
    cnn = CNN_BENCHMARKS[cfg["name"]]()
    _check_program_config(cnn, layers)

    rng, key_seed = rngs(seed)
    params = make_weights(layers, key_seed, traffic["weights"])
    entry = entries.ENTRIES[traffic["entry"]](cnn, params, cfg, traffic,
                                              rng, layers, low_bits)
    undo = fault() if fault is not None else None
    for i in range(traffic["warmup_calls"]):
        entry.call(i)
    n_setup, compile_setup_s = compiles.take()
    entry.outputs.clear()

    ctx = Context(spec=spec, peaks=peaks, layers=layers,
                  compile_setup_s=compile_setup_s)
    wrappers = prof = None
    if trace:
        from repro.telemetry.spans import Profiler

        wrap = json.loads((BENCH / "wrappers.json").read_text())
        wrappers = Wrappers(wrap["wrap"], wrap["required"])
        prof = Profiler().install()

    # -- the window --------------------------------------------------------
    t0 = time.perf_counter()
    ctx.setup_s = t0 - t_start
    deadline = t0 + seconds
    i = 0
    while True:
        s = time.perf_counter()
        if s >= deadline and i > 0:
            break
        ctx.frames += entry.call(i)
        ctx.call_s.append(time.perf_counter() - s)
        i += 1
    ctx.window_s = time.perf_counter() - t0
    ctx.calls = i
    ctx.runs_per_call = entry.runs_per_call()
    ctx.trials = i * entry.trials_per_call()
    n_window, _ = compiles.take()
    fifths = [float(np.mean(part)) for part in
              np.array_split(np.asarray(ctx.call_s), min(5, ctx.calls))]
    print(f"bench: set-up {ctx.setup_s!r} s, {n_setup} programs compiled or loaded "
          f"({compile_setup_s!r} s); window "
          f"{ctx.window_s!r} s, {ctx.calls} calls, {ctx.frames} frames, "
          f"{n_window} programs compiled in the window; mean call s by "
          f"fifth of the window {fifths!r}", file=sys.stderr, flush=True)
    stats = device.memory_stats() if device is not None else None
    dev_out = {"platform": getattr(device, "platform", "none"),
               "kind": getattr(device, "device_kind", "none"),
               "count": count,
               "memory_peak_bytes": int((stats or {}).get(
                   "peak_bytes_in_use", 0))}

    breakdown = None
    if trace:
        prof.uninstall()
        ctx.wrap = dict(wrappers.seconds)
        ctx.spans = _span_durations(prof.events)
        ctx.trace, ctx.traced_calls, ctx.traced_frames = _traced_calls(
            entry, wrappers, i, traffic["trace_calls"])
        wrappers.remove()
        dev_out["busy_s"] = ctx.trace.busy_s()
        dev_out["window_s"] = ctx.trace.window_s
        breakdown = {"device_ops": ctx.trace.top_ops(10),
                     "idle_gaps": ctx.trace.idle_by_label(10)}
    if undo is not None:
        undo()

    names = spec.per_layer if trace else spec.end_to_end
    metrics = {}
    for name in names:
        unit, value = read_metric(name, ctx)
        if value is None:   # left out: a line without it is refused
            print(f"bench: WARNING {spec.name} lists {name}, but its reader "
                  "found nothing to read", file=sys.stderr)
            continue
        metrics[name] = {"value": value, "unit": unit}

    # -- the check, once the program's state is freed ----------------------
    sim = entry.sim
    entry.sim = None
    del sim
    gc.collect()
    checks, failed = check(entry, cfg, traffic, spec.limits, ctx.calls,
                           params, np.random.default_rng(
                               np.random.SeedSequence([int(seed), 7])))
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct, "attempted": ctx.frames, "failed": failed,
              "metrics": metrics, "device": dev_out}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def _traced_calls(entry, wrappers, first: int, n: int):
    """Profile ``n`` more calls, each inside a ``call`` annotation and
    all inside ``window``; (trace, calls, frames)."""
    import jax

    from bench import tracing as tr

    wrappers.annotate = True
    path = tr.start()
    frames = 0
    try:
        with jax.profiler.TraceAnnotation("bench:window"):
            for k in range(n):
                with jax.profiler.TraceAnnotation("bench:call"):
                    frames += entry.call(first + k)
    finally:
        wrappers.annotate = False
        trace = tr.stop_and_load(path)
    return trace, n, frames


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be a non-negative integer")
    spec = load_spec(args.workload)
    device, count, pk = require_device(spec.chips)
    result = run(spec, args.seed, args.seconds, bool(args.trace),
                 device=device, count=count, peaks=pk)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
