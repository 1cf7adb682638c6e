"""The comparison that decides ``correct``.

A sample of the window's calls, drawn from the seed, is recomputed by
the plain reference (``reference.py``) from the same inputs, and each
frame's logits are compared: ``logit_gap`` is the widest gap over the
sampled frames, ``max |program - reference| / rms(reference)`` per
frame.  Beside it, the simulated statistics the same calls reported
are held to what the configuration implies (``accounting.py``, from the
layer shapes): every counter of every frame of the window, and the
stream's measured and analytic initiation intervals.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from bench.accounting import flatten, frame_counters, initiation_interval
from bench.reference import Reference


def _gap(prog: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per frame: widest logit gap over the reference's rms logit."""
    prog = np.asarray(prog, np.float64)
    if prog.shape != ref.shape or not np.isfinite(prog).all():
        return np.full(ref.shape[0], np.inf)
    rms = np.sqrt(np.mean(ref * ref, axis=-1))
    return np.max(np.abs(prog - ref), axis=-1) / np.maximum(rms, 1e-30)


def _counter_error(counters, want: Dict[str, int]) -> int:
    """Widest gap between one frame's counters and the derived ones."""
    sim, traffic = counters
    got = flatten(vars(sim), {k: dict(v) for k, v in vars(traffic).items()})
    return max(abs(got.get(k, 0) - want.get(k, 0))
               for k in set(got) | set(want))


def check(entry, cfg: dict, traffic: dict, limits: Dict[str, float],
          window_calls: int, params, rng: np.random.Generator
          ) -> Tuple[Dict[str, Dict[str, float]], int]:
    """(``{name: {"value", "limit"}}``, sampled frames outside the
    logit limit).  ``entry.outputs[:window_calls]`` are the window's."""
    outs = entry.outputs[:window_calls]
    n_check = min(traffic["check_calls"], len(outs))
    picks = np.sort(rng.choice(len(outs), size=n_check, replace=False))
    ref = Reference(cfg, params, entry.calib)
    gaps: List[np.ndarray] = []
    var = traffic.get("variation")
    for i in picks:
        out = outs[int(i)]
        for logits, seed in zip(out.logits, out.run_seeds):
            want = ref.logits(out.frames) if seed is None \
                else ref.logits(out.frames, var, seed)
            gaps.append(_gap(logits, want))
        if len(out.logits) != len(out.run_seeds):
            gaps.append(np.array([np.inf]))
    gap = np.concatenate(gaps) if gaps else np.array([np.inf])

    want = frame_counters(cfg)
    frames = [c for out in outs for run in out.counters for c in run]
    short = sum(out.frames.shape[0] * len(out.run_seeds) for out in outs) \
        - len(frames)
    checks = {
        "logit_gap": float(gap.max()),
        "counter_error": float(max((_counter_error(c, want) for c in frames),
                                   default=np.inf) + abs(short)),
    }
    if traffic["entry"] == "serve_stream":
        ii = initiation_interval(cfg)
        checks["ii_error"] = float(max(
            (abs(out.measured_ii - ii) + abs(out.analytic_ii - ii)
             for out in outs), default=np.inf))
    result = {name: {"value": v, "limit": float(limits[name])}
              for name, v in checks.items()}
    failed = int(np.sum(gap > limits["logit_gap"]))
    return result, failed
