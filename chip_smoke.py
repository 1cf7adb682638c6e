"""Chip smoke test: the simulator's quantized device path on one TPU.

Drives resnet50-imagenet (``configs/cnn.py``: 224x224x3 frames, the
published widths, seeded random weights) through the simulator's own
entry points, all in this one process:

1. streamed serving on the jitted trace path
   (``build_stream_sim(..., engine="cim", trace_jit=True)`` +
   ``serve_stream``, 4 frames): per-frame logits bitwise equal to the
   host fused path on the same calibrated engine, measured II equal to
   the analytic II (98 at the duplication the streaming bench serves
   resnet50 with);
2. the Pallas engine (``NetworkSimulator(..., engine="pallas")``, 2
   frames) on the host fused path and on the jitted path: ADC codes and
   logits bitwise equal to ``engine="cim"`` on the same calibration, and
   the kernel lowers to a compiled Mosaic call (``tpu_custom_call``),
   not to interpret mode;
3. one Monte-Carlo trial of the "all" device-variation corner on the
   Pallas engine (``monte_carlo_sweep``): the zero-variation run is
   bitwise nominal and the trial's codes equal the cim engine's under
   the same seed.

Run from the repository root::

    python chip_smoke.py

Without a TPU it exits non-zero and prints no result.  Any failed check
raises.  Set-up (build, calibration, compilation) and run seconds and
the number of compiled programs are printed per phase; the last stdout
line is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import hashlib
import json
import sys
import time
from pathlib import Path

import numpy as np

MODEL = "resnet50-imagenet"
#: weight duplication cap the streaming bench serves resnet50 with
DUP_CAP = 128
#: plan_network's analytic initiation interval at DUP_CAP
ANALYTIC_II = 98
SEED = 0
FRAMES = 4

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _require_tpu():
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found platform "
                 f"{dev.platform!r} ({dev.device_kind})")
    return dev, len(devices)


class Compiles:
    """Counts XLA backend compilations and their seconds
    (``jax.monitoring``); :meth:`take` returns and resets the tally."""

    def __init__(self):
        import jax

        self.n, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == _COMPILE_EVENT:
            self.n += 1
            self.secs += secs

    def take(self):
        out = (self.n, self.secs)
        self.n, self.secs = 0, 0.0
        return out


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke: {what}")


def _report(phase: str, compiles: Compiles, **secs) -> None:
    n, csecs = compiles.take()
    times = " ".join(f"{k}={v!r}" for k, v in secs.items())
    print(f"{phase}: {times} compiled_programs={n} compile_s={csecs!r}",
          flush=True)


def tap_codes(sim):
    """Log a digest of every ADC code-sum array ``sim``'s engine returns
    from its host MAC entry points, one list per ``sim.run`` call."""
    runs = []
    engine = sim.pe_engine
    for name in ("tiles_mac", "fc_mac"):
        def logged(*args, _mac=getattr(engine, name), **kw):
            codes = _mac(*args, **kw)
            runs[-1].append(hashlib.sha256(
                np.ascontiguousarray(codes).tobytes()).hexdigest())
            return codes
        setattr(engine, name, logged)
    run = sim.run

    def logged_run(*args, **kw):
        runs.append([])
        return run(*args, **kw)

    sim.run = logged_run
    return runs


def _with_calib(cls, calibrated):
    """A fresh ``cls`` engine sharing ``calibrated``'s spec and
    calibration (no second calibration forward runs)."""
    out = cls(calibrated.spec)
    out.calib = dict(calibrated.calib)
    return out


def phase_stream(cnn, params, frames, compiles, dup_cap, expect_ii):
    """Streamed serving on the jitted trace path vs the host fused path;
    returns the calibrated cim engine."""
    from repro.runtime.serve_loop import build_stream_sim, serve_stream

    t0 = time.perf_counter()
    sim = build_stream_sim(cnn, params, engine="cim", trace_jit=True,
                           dup_cap=dup_cap)
    t1 = time.perf_counter()
    first = serve_stream(sim, frames)
    t2 = time.perf_counter()
    rep = serve_stream(sim, frames)
    t3 = time.perf_counter()
    _report("phase1 stream jit setup", compiles, build_s=t1 - t0,
            first_call_s=t2 - t1)
    _report("phase1 stream jit run", compiles, serve_s=t3 - t2)
    host = build_stream_sim(cnn, params, engine=sim.pe_engine,
                            dup_cap=dup_cap)
    t4 = time.perf_counter()
    ref = serve_stream(host, frames)
    t5 = time.perf_counter()
    _report("phase1 host fused reference", compiles, build_s=t4 - t3,
            serve_s=t5 - t4)
    _check(rep.logits.shape == (len(frames), cnn.layers[-1].c_out)
           and np.isfinite(rep.logits).all(),
           f"jit logits shape {rep.logits.shape} or non-finite")
    _check(first.logits.tobytes() == rep.logits.tobytes(),
           "jit logits differ between two serve_stream calls")
    _check(rep.logits.tobytes() == ref.logits.tobytes(),
           "jit-path logits differ bitwise from the host fused path")
    _check(rep.measured_ii == rep.analytic_ii == ref.measured_ii
           == expect_ii,
           f"measured II {rep.measured_ii} (host {ref.measured_ii}) vs "
           f"analytic {rep.analytic_ii}, expected {expect_ii}")
    print(f"phase1 ok: {len(frames)} frames, jit logits bitwise == host "
          f"fused path, measured II {rep.measured_ii} == analytic "
          f"{rep.analytic_ii}", flush=True)
    return sim.pe_engine


def _kernel_compiled(spec) -> None:
    """Lower the chain kernel (plain and variation flavors) at
    resnet50's widest chain (18 subarrays x 512 outputs) and check it
    is a compiled Mosaic call, not interpret mode."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.cim_matmul import cim_chain_codes_pallas

    t = 18
    x = jax.ShapeDtypeStruct((256, t * spec.n_c), jnp.int8)
    w = jax.ShapeDtypeStruct((t * spec.n_c, 512), jnp.int8)
    av = jax.ShapeDtypeStruct((t, 2), jnp.float32)
    for args, fn in (
            ((x, w), lambda a, b: cim_chain_codes_pallas(a, b, spec)),
            ((x, w, av), lambda a, b, c: cim_chain_codes_pallas(
                a, b, spec, adc_var=c))):
        jaxpr = str(jax.make_jaxpr(fn)(*args))
        _check("pallas_call" in jaxpr and "interpret=False" in jaxpr
               and "interpret=True" not in jaxpr,
               "the Pallas kernel is traced in interpret mode")
        _check("tpu_custom_call" in jax.jit(fn).lower(*args).as_text(),
               "the lowered kernel holds no tpu_custom_call")


def phase_pallas(cnn, params, frames, compiles, cim, dup_cap):
    """The Pallas engine vs the cim engine on one calibration."""
    from repro.core.engine import CIMEngine, PallasEngine
    from repro.core.network import NetworkSimulator

    t0 = time.perf_counter()
    ref_sim = NetworkSimulator(cnn, params, backend="trace",
                               engine=_with_calib(CIMEngine, cim),
                               dup_cap=dup_cap)
    pal_sim = NetworkSimulator(cnn, params, backend="trace",
                               engine=_with_calib(PallasEngine, cim),
                               dup_cap=dup_cap)
    jit_sim = NetworkSimulator(cnn, params, backend="trace", trace_jit=True,
                               engine=_with_calib(PallasEngine, cim),
                               dup_cap=dup_cap)
    ref_codes, pal_codes = tap_codes(ref_sim), tap_codes(pal_sim)
    t1 = time.perf_counter()
    _report("phase2 build", compiles, build_s=t1 - t0)
    ref = ref_sim.run(frames).logits
    t2 = time.perf_counter()
    _report("phase2 cim host reference", compiles, run_s=t2 - t1)
    pal = pal_sim.run(frames).logits
    t3 = time.perf_counter()
    pal_again = pal_sim.run(frames).logits
    t4 = time.perf_counter()
    _report("phase2 pallas host path", compiles, first_call_s=t3 - t2,
            warm_call_s=t4 - t3)
    jit = jit_sim.run(frames).logits
    t5 = time.perf_counter()
    jit_again = jit_sim.run(frames).logits
    t6 = time.perf_counter()
    _report("phase2 pallas jit path", compiles, first_call_s=t5 - t4,
            warm_call_s=t6 - t5)
    _kernel_compiled(cim.spec)
    _check(len(ref_codes[0]) > 0 and ref_codes[0] == pal_codes[0]
           and pal_codes[0] == pal_codes[1],
           "pallas ADC code sums differ from the cim engine's")
    _check(np.isfinite(ref).all(), "non-finite cim logits")
    for name, got in (("pallas", pal), ("pallas rerun", pal_again),
                      ("pallas jit", jit), ("pallas jit rerun", jit_again)):
        _check(got.tobytes() == ref.tobytes(),
               f"{name} logits differ bitwise from engine='cim'")
    print(f"phase2 ok: {len(ref_codes[0])} code-sum arrays bitwise == "
          "cim, host and jit logits bitwise == cim, kernel compiled "
          "(tpu_custom_call, interpret=False)", flush=True)


def phase_variation(cnn, params, frames, compiles):
    """One Monte-Carlo trial of the "all" corner on the Pallas engine vs
    the cim engine under the same seed."""
    from repro.core.variation import VARIATION_PRESETS
    from repro.runtime.robustness import build_robust_sim, monte_carlo_sweep

    var = VARIATION_PRESETS["all"]
    t0 = time.perf_counter()
    csim = build_robust_sim(cnn, params, frames, engine="cim")
    psim = build_robust_sim(cnn, params, frames, engine="pallas")
    t1 = time.perf_counter()
    _report("phase3 build", compiles, build_s=t1 - t0)
    _check(psim.pe_engine.calib == csim.pe_engine.calib,
           "two calibrations of the same frames differ")
    p_runs = tap_codes(psim)
    rep = monte_carlo_sweep(cnn, params, frames, var, trials=1,
                            engine="pallas", sim=psim)
    t2 = time.perf_counter()
    _report("phase3 pallas sweep", compiles, sweep_s=t2 - t1)
    c_runs = tap_codes(csim)
    csim.set_variation(var.reseed(var.seed))
    csim.run(frames)
    csim.set_variation(None)
    t3 = time.perf_counter()
    _report("phase3 cim trial reference", compiles, run_s=t3 - t2)
    _check(rep.zero_var_bitwise is True,
           "zero-magnitude variation diverged from the nominal engine")
    # the sweep runs nominal, zero-variation, then trial 0
    _check(len(p_runs) == 3 and len(c_runs[0]) > 0
           and p_runs[2] == c_runs[0],
           "pallas trial codes differ from the cim engine's")
    _check(p_runs[2] != p_runs[0], "the variation trial changed no code")
    print(f"phase3 ok: zero_var_bitwise=True, trial codes "
          f"({len(c_runs[0])} arrays) bitwise == cim, "
          f"nominal_agree={rep.nominal_agree} "
          f"trial_agree={rep.per_trial}", flush=True)


def main() -> None:
    dev, count = _require_tpu()
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    entries = len(list(Path(cache).iterdir())) if Path(cache).is_dir() else 0
    print(f"device: kind={dev.device_kind} platform={dev.platform} "
          f"count={count} compile_cache={cache} "
          f"cache_entries_at_start={entries}", flush=True)

    import jax

    from repro.configs.cnn import CNN_BENCHMARKS
    from repro.models.cnn import init_cnn

    compiles = Compiles()
    cnn = CNN_BENCHMARKS[MODEL]()
    params = {k: np.asarray(v, np.float64) for k, v in
              init_cnn(jax.random.PRNGKey(SEED), cnn).items()}
    hw = cnn.input_hw
    frames = np.random.default_rng(SEED).random((FRAMES, hw, hw, 3))
    _report("weights", compiles)

    cim = phase_stream(cnn, params, frames, compiles, DUP_CAP, ANALYTIC_II)
    phase_pallas(cnn, params, frames[:2], compiles, cim, DUP_CAP)
    phase_variation(cnn, params, frames[:2], compiles)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))


if __name__ == "__main__":
    main()
