"""The program entries a traffic mix drives, one class per ``entry``.

Each builds the simulator through the program's public entry points,
then serves ``call(i)``: one closed-loop call, returning the frames it
completed.  Each keeps what the calls produced (logits, the per-frame
simulated counters, the measured initiation interval) for the check
that decides ``correct``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, List, Optional

import numpy as np

from bench.inputs import frames


@dataclass
class Output:
    """One call's products, one entry per simulated batch: its logits,
    each frame's counters, and the variation seed it ran under (None:
    the nominal model, which a zero-variation draw must equal)."""

    frames: np.ndarray                     # the frames served
    run_seeds: List[Optional[int]] = field(default_factory=lambda: [None])
    logits: List[np.ndarray] = field(default_factory=list)
    counters: List[List[Any]] = field(default_factory=list)
    measured_ii: Optional[int] = None
    analytic_ii: Optional[int] = None


def _engine(name: str, low_bits: Optional[int], layers):
    """The engine by name, or an instance whose every layer runs the
    program's own narrower-precision path (the lower-precision
    control)."""
    if low_bits is None:
        return name
    from repro.core.engine import CIMEngine, PallasEngine

    eng = {"cim": CIMEngine, "pallas": PallasEngine}[name]()
    for layer in layers:
        eng.set_layer_spec(layer.name, w_bits=low_bits, a_bits=low_bits)
    return eng


class Stream:
    """``serve_stream`` over ``build_stream_sim``: a pool of distinct
    frame batches drawn before the window, served in turn."""

    def __init__(self, cnn, params, cfg, traffic, rng, layers,
                 low_bits=None):
        from repro.runtime.serve_loop import build_stream_sim

        self.cfg, self.traffic = cfg, traffic
        b, hw = traffic["frames_per_call"], cfg["input_hw"]
        self.calib = frames(rng, traffic["calib_frames"], hw)
        self.pool = frames(rng, traffic["pool_calls"] * b, hw).reshape(
            traffic["pool_calls"], b, hw, hw, 3)
        self.sim = build_stream_sim(
            cnn, params, engine=_engine(traffic["engine"], low_bits, layers),
            trace_jit=traffic["trace_jit"], dup_cap=cfg["dup_cap"],
            chiplets=traffic["chiplets"], noi=traffic["noi"],
            calib_images=self.calib)
        self.outputs: List[Output] = []
        run_stream = self.sim.run_stream

        def tapped(*a, **kw):
            res = run_stream(*a, **kw)
            out = self.outputs[-1]
            out.counters = [list(zip(res.frame_counters,
                                     res.frame_traffic))]
            return res

        self.sim.run_stream = tapped

    def call(self, i: int) -> int:
        from repro.runtime.serve_loop import serve_stream

        x = self.pool[i % len(self.pool)]
        self.outputs.append(Output(frames=x))
        rep = serve_stream(self.sim, x,
                           batch_window=self.traffic["batch_window"])
        out = self.outputs[-1]
        out.logits = [rep.logits]
        out.measured_ii, out.analytic_ii = rep.measured_ii, rep.analytic_ii
        return x.shape[0]

    def runs_per_call(self) -> int:
        return 1

    def trials_per_call(self) -> int:
        return 0


class Sweep:
    """``monte_carlo_sweep`` on one prebuilt ``build_robust_sim``: every
    call sweeps ``trials_per_call`` seeded draws of the traffic's
    variation corner on one image set, after the sweep's nominal run and,
    with ``check_zero``, its zero-variation run."""

    def __init__(self, cnn, params, cfg, traffic, rng, layers,
                 low_bits=None):
        from repro.core.variation import VariationModel
        from repro.runtime.robustness import build_robust_sim

        self.cnn, self.params = cnn, params
        self.cfg, self.traffic = cfg, traffic
        self.images = frames(rng, traffic["frames_per_call"],
                             cfg["input_hw"])
        self.calib = self.images     # the sweep calibrates on its images
        self.seed_rng = np.random.default_rng(rng.integers(0, 2 ** 63))
        self.variation = VariationModel(seed=0, **traffic["variation"])
        kw = {}
        if low_bits is not None:
            kw["layer_specs"] = {layer.name: (low_bits, low_bits,
                                              cfg["adc_bits"])
                                 for layer in layers}
        self.sim = build_robust_sim(cnn, params, self.images,
                                    engine=traffic["engine"], **kw)
        self.outputs: List[Output] = []
        run = self.sim.run

        def tapped(images):
            res = run(images)
            out = self.outputs[-1]
            out.logits.append(res.logits)
            # the run's counters are per inference, alike for every frame
            out.counters.append([(res.counters, res.traffic)] * len(images))
            return res

        self.sim.run = tapped

    def call(self, i: int) -> int:
        from repro.runtime.robustness import monte_carlo_sweep

        seed0 = int(self.seed_rng.integers(0, 2 ** 31 - 1))
        trials, zero = self.trials_per_call(), bool(self.traffic["check_zero"])
        self.outputs.append(Output(
            frames=self.images,
            run_seeds=[None] * (2 if zero else 1)
            + [seed0 + t for t in range(trials)]))
        monte_carlo_sweep(self.cnn, self.params, self.images, self.variation,
                          trials=trials, seed0=seed0, check_zero=zero,
                          sim=self.sim)
        return self.images.shape[0] * len(self.outputs[-1].logits)

    def runs_per_call(self) -> int:
        return (2 if self.traffic["check_zero"] else 1) \
            + self.trials_per_call()

    def trials_per_call(self) -> int:
        return self.traffic["trials_per_call"]


ENTRIES = {"serve_stream": Stream, "monte_carlo_sweep": Sweep}
