"""The benchmark's yardstick on the CPU: its definition files, the work
the kernel is held to, the peaks table and the trace reduction.  None
of this touches a TPU."""
import json
from pathlib import Path

import pytest

from bench import accounting, cell, roofline, tracing
from bench.reference import Layer, conv_subarrays, layers_of, macs_per_frame

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: json.loads((ROOT / c["file"]).read_text())
           for c in BENCH["configs"]}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_config_macs_match_layer_shapes(name):
    cfg = CONFIGS[name]
    assert macs_per_frame(cfg) == cfg["macs_per_frame"]
    ops, _ = roofline.kernel_work(layers_of(cfg), frames=1, calls=1)
    assert ops == 2 * cfg["macs_per_frame"]


def test_stored_macs_per_frame():
    assert CONFIGS["resnet50-imagenet"]["macs_per_frame"] == 4_089_184_256
    assert CONFIGS["resnet18-cifar10"]["macs_per_frame"] == 555_422_720


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_program_model_is_the_config_file(name):
    from repro.configs.cnn import CNN_BENCHMARKS

    cell._check_program_config(CNN_BENCHMARKS[name](),
                               layers_of(CONFIGS[name]))


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_derived_counters_match_the_program(name):
    """A second witness for ``accounting.py``: the program's own
    per-frame accounting replay and planned initiation interval read
    the same on this configuration (the weights' values play no part,
    so zero-filled leaves stand in)."""
    import numpy as np

    from repro.configs.cnn import CNN_BENCHMARKS
    from repro.core.mapping import plan_network
    from repro.core.network import NetworkSimulator
    from repro.core.simulator import SimCounters
    from repro.core.transport import TrafficCounters

    cfg = CONFIGS[name]
    cnn = CNN_BENCHMARKS[name]()
    params = {l.name: np.zeros(l.weight_shape) for l in layers_of(cfg)}
    sim = NetworkSimulator(cnn, params, backend="trace", engine="exact",
                           dup_cap=cfg["dup_cap"], streaming=True)
    counters, traffic = SimCounters(), TrafficCounters()
    sim._account_frame(counters, traffic)
    got = accounting.flatten(vars(counters),
                             {k: dict(v) for k, v in vars(traffic).items()})
    assert got == accounting.frame_counters(cfg)
    plan = plan_network(cnn, dup_cap=cfg["dup_cap"])
    assert plan.initiation_interval == accounting.initiation_interval(cfg)


def test_derived_counters_by_hand():
    """resnet18-cifar10's stem by hand: 3x3 taps packed into one tile per
    filter row (C = 3), so 3 tiles in 3 groups; 32x32 fires, 34x34 raster."""
    cfg = dict(CONFIGS["resnet18-cifar10"])
    cfg["layers"] = cfg["layers"][:1]
    got = accounting.frame_counters(cfg)
    fires = 32 * 32
    assert got["sim.macs"] == fires * 9 * 3 * 64
    assert got["sim.cycles"] == 34 * 34 + 2 * 3
    assert got["sim.instr_fetches"] == 3 * 34 * 34
    assert got["sim.buf_push"] == got["sim.buf_pop"] == 2 * fires
    assert got["sim.act_ops"] == fires * 64
    assert got["traffic.packets.group"] == 2 * fires
    assert "traffic.packets.chain" not in got
    assert accounting.initiation_interval(CONFIGS["resnet18-cifar10"]) == 16
    assert accounting.initiation_interval(CONFIGS["resnet50-imagenet"]) == 98


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_subarrays_partition_each_contraction(name):
    """Every (row, tap, channel) of a conv lies in exactly one subarray
    of at most n_c rows."""
    cfg = CONFIGS[name]
    for layer in layers_of(cfg):
        if layer.kind != "conv":
            continue
        seen = set()
        for i, j0, taps, lo, hi in conv_subarrays(layer, cfg["n_c"]):
            assert taps * (hi - lo) <= cfg["n_c"]
            cells = {(i, j, c) for j in range(j0, j0 + taps)
                     for c in range(lo, hi)}
            assert not cells & seen
            seen |= cells
        assert len(seen) == layer.k * layer.k * layer.c


def test_kernel_work_counts_bytes_from_shapes():
    conv = Layer(name="l", kind="conv", h=8, w=8, c=4, m=16, k=3, s=1, p=1)
    ops, nbytes = roofline.kernel_work([conv], frames=2, calls=1)
    assert ops == 2 * 2 * 64 * 36 * 16
    assert nbytes == 2 * 64 * 36 + 36 * 16 + 4 * 2 * 64 * 16


def test_peaks_known_kind_and_unknown_kind_raises():
    pk = roofline.peaks("TPU v5 lite")
    assert pk["int8_ops_per_s"] == 393e12
    assert pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_roofline_names_its_bound():
    pk = {"int8_ops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert roofline.roofline(1000.0, 10.0, 20.0, pk) == (50.0, "compute")
    assert roofline.roofline(10.0, 100.0, 20.0, pk) == (50.0, "memory")


def _synthetic():
    # device ops at [10,20), [15,30), [50,60) in a window [0, 100);
    # host annotations: a call [0, 100) and a tail inside it [35, 55)
    tr = tracing.Trace(t0_ns=0, t1_ns=100)
    tr.devices["/device:TPU:0"] = [("fusion.1", 10, 10, ""),
                                   ("%cim_matmul_pallas.1 = custom-call", 15, 15,
                                    'custom_call_target="tpu_custom_call"'),
                                   ("fusion.1", 50, 10, "")]
    tr.host = [("call", 0, 100), ("tail_np", 35, 20)]
    return tr


def test_idle_share_on_synthetic_events():
    tr = _synthetic()
    assert tracing.busy_ns([(10, 20), (15, 30), (50, 60)], 0, 100) == 30
    assert tr.busy_s() == pytest.approx(30e-9)
    assert tr.window_s == pytest.approx(100e-9)
    assert tracing.idle_gaps([(10, 20), (15, 30), (50, 60)], 0, 100) == [
        (0, 10), (30, 50), (60, 100)]


def test_idle_gaps_go_to_the_innermost_annotation():
    idle = dict(_synthetic().idle_by_label())
    # gaps (0,10) (30,50) (60,100): the tail covers 35..50 of them
    assert idle["tail_np"] == pytest.approx(15e-9)
    assert idle["call"] == pytest.approx(55e-9)


def test_op_seconds_and_top_ops():
    tr = _synthetic()
    kernel = ("cim_matmul_pallas", "custom-call", "tpu_custom_call")
    assert tr.op_seconds(kernel) == pytest.approx(15e-9)
    assert tr.op_seconds(("cim_matmul_pallas", "fusion")) == 0.0
    assert tr.top_ops(1) == [["fusion.1", pytest.approx(20e-9)]]


def test_every_metric_has_a_reader_and_every_cell_its_files():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    for name in names:
        assert (ROOT / "bench" / "metrics" / f"{name}.py").is_file(), name
    for w in BENCH["workloads"]:
        spec = cell.load_spec(w["name"], ROOT)
        assert "setup_s" in spec.end_to_end and len(spec.end_to_end) >= 2
        assert spec.per_layer
        assert set(spec.limits) >= {"logit_gap", "counter_error"}
