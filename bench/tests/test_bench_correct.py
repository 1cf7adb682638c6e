"""The comparison that decides ``correct``, driven through the harness
on the CPU at a test-run size (the harness's look for a chip skipped):
the program passes, the lower-precision control fails, and so does a
run whose timed path alters an answer where it is produced: the
logits, the simulated counters (on some frames, or alike on every
frame), or the stream's exit timeline.

The cells' own traffic is cut to two frames a call on the cim engine's
host path so each run takes seconds here; on the chip the same
readings are taken at the cells' own sizes (``bench/control.py``).
"""
import dataclasses

import numpy as np
import pytest

from bench import cell


def _small(workload):
    spec = cell.load_spec(workload)
    traffic = dict(spec.traffic, engine="cim", warmup_calls=1,
                   check_calls=1, trials_per_call=1)
    if traffic["entry"] == "serve_stream":
        traffic.update(trace_jit=False, frames_per_call=2, batch_window=2,
                       pool_calls=2)
    else:
        traffic.update(frames_per_call=2)
    return dataclasses.replace(spec, traffic=traffic)


def _alter_answer():
    """The final layer's output altered where it is produced: the top
    class of the first frame of every batch moves to its lowest one."""
    import repro.core.network as network

    orig = network.simulate_fc

    def altered(x, w, *a, **kw):
        out = orig(x, w, *a, **kw)
        if kw.get("activation") is None and not kw.get("account_only"):
            out = np.array(out)
            row = out[0]
            row[np.argmin(row)] = row.max() + np.sqrt(np.mean(row * row))
        return out

    network.simulate_fc = altered
    return lambda: setattr(network, "simulate_fc", orig)


@pytest.mark.parametrize("workload", ["resnet18.stream-b4",
                                      "resnet18.mc-all"])
def test_program_passes_and_control_fails(workload):
    spec = _small(workload)
    ok = cell.run(spec, 2 ** 31 + 11, 0.2, False)
    assert ok["correct"], ok["checks"]
    control = cell.run(spec, 2 ** 31 + 11, 0.2, False, low_bits=4)
    assert not control["correct"]
    gap = control["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
    others = {k: v for k, v in control["checks"].items() if k != "logit_gap"}
    assert all(v["value"] <= v["limit"] for v in others.values())


def _alter_counts():
    """A conv layer's accounting counts one MAC too many on its 2**k-th
    call, so some frames' counters differ from the model's."""
    from repro.core.trace import TraceExecutor

    orig = TraceExecutor._account
    seen = [0]

    def altered(self):
        orig(self)
        seen[0] += 1
        if seen[0] & (seen[0] - 1) == 0:
            self.counters.macs += 1

    TraceExecutor._account = altered
    return lambda: setattr(TraceExecutor, "_account", orig)


def _shift_counts():
    """Every conv layer's accounting routes its group sums one hop
    longer: every frame's counters off by the same amount."""
    from repro.core.trace import TraceExecutor

    orig = TraceExecutor._account

    def shifted(self):
        orig(self)
        self.counters.group_hops += 1

    TraceExecutor._account = shifted
    return lambda: setattr(TraceExecutor, "_account", orig)


def _alter_ii():
    """The stream's last exit one cycle late: a measured II off by one."""
    import repro.core.network as network

    orig = network.stream_timeline

    def altered(*a, **kw):
        start, finish = orig(*a, **kw)
        finish = finish.copy()
        finish[-1, -1] += 1
        return start, finish

    network.stream_timeline = altered
    return lambda: setattr(network, "stream_timeline", orig)


FAULTS = {
    "answer": (_alter_answer, ("logit_gap",)),
    "counts": (_alter_counts, ("counter_error",)),
    "shift": (_shift_counts, ("counter_error",)),
    "ii": (_alter_ii, ("ii_error",)),
}


@pytest.mark.parametrize("workload,fault", [
    ("resnet18.stream-b4", "answer"), ("resnet18.mc-all", "answer"),
    ("resnet18.stream-b4", "counts"), ("resnet18.mc-all", "counts"),
    ("resnet18.stream-b4", "shift"), ("resnet18.mc-all", "shift"),
    ("resnet18.stream-b4", "ii")])
def test_altered_answer_fails(workload, fault):
    install, caught = FAULTS[fault]
    res = cell.run(_small(workload), 5, 0.2, False, fault=install)
    assert not res["correct"]
    for name in caught:
        c = res["checks"][name]
        assert c["value"] > c["limit"], (name, c)
    if fault == "answer":
        assert res["failed"] > 0
