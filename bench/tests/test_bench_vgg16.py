"""vgg16-imagenet's path through the program, on the CPU at a narrow size.

The narrow VGG-16 keeps the configuration's 2-2-3-3-3 plan of 3x3
convolutions with five 2x2/s2 max-pools, the flatten head and three FC
layers with ReLU between them; its widths are the published ones over
16, and its frames 128x128.  The padded width, 130, exceeds the
128-entry Rofm table, so the first two layers run as width strips and
the second one's strip cut meets its pool.  fc0 takes 512 inputs, two
rows of the FC grid.  The Pallas kernel runs in interpret mode here.
None of this touches a TPU."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import cell, entries
from bench.correct import _gap
from bench.inputs import frames, make_weights, rngs
from bench.reference import Reference, layers_of
from repro.configs.cnn import FCLayer, _vgg

ROOT = Path(__file__).resolve().parents[2]
VGG16 = json.loads((ROOT / "bench/configs/vgg16-imagenet.json").read_text())

#: the published VGG-16 plan at a sixteenth of its widths
NARROW_PLAN = [4, 4, "M", 8, 8, "M", 16, 16, 16, "M",
               32, 32, 32, "M", 32, 32, 32, "M"]
HW = 128
FRAMES = 2


def _narrow():
    return _vgg("vgg16-narrow", NARROW_PLAN, "imagenet", HW, (256, 256, 10))


def _config(cnn) -> dict:
    """The narrow net as a configuration file would state it, with the
    numerics of vgg16-imagenet's own file."""
    rows = []
    for layer in cnn.layers:
        if isinstance(layer, FCLayer):
            rows.append({"name": layer.name, "kind": "fc",
                         "c_in": layer.c_in, "c_out": layer.c_out})
        else:
            rows.append({"name": layer.name, "kind": "conv", "h": layer.h,
                         "w": layer.w, "c": layer.c, "m": layer.m,
                         "k": layer.k, "s": layer.s, "p": layer.p,
                         "pool_k": layer.pool_k, "pool_s": layer.pool_s})
    keys = ("head", "dup_cap", "n_c", "n_m", "w_bits", "a_bits", "adc_bits",
            "clip_percentile")
    return {"name": cnn.name, "input_hw": HW, "layers": rows,
            **{k: VGG16[k] for k in keys}}


@pytest.fixture(scope="module")
def narrow():
    """(cnn, config, layers, int8 weights, calibration frames, frames)
    from one seed, as ``bench/cell.py`` draws them."""
    cnn = _narrow()
    cfg = _config(cnn)
    layers = layers_of(cfg)
    rng, key_seed = rngs(5)
    params = make_weights(layers, key_seed, "int8")
    calib = frames(rng, 2, HW)
    x = frames(rng, FRAMES, HW)
    return cnn, cfg, layers, params, calib, x


def _serve(narrow, low_bits=None):
    from repro.runtime.serve_loop import build_stream_sim, serve_stream

    cnn, cfg, layers, params, calib, x = narrow
    sim = build_stream_sim(
        cnn, params, engine=entries._engine("pallas", low_bits, layers),
        trace_jit=True, dup_cap=cfg["dup_cap"], calib_images=calib)
    return sim, serve_stream(sim, x, batch_window=FRAMES)


def test_narrow_net_keeps_what_vgg16_works():
    """Width strips on the first two layers, one of them pooled; five
    pools; a flatten head into a two-row FC grid."""
    from repro.core.mapping import plan_network
    from repro.core.network import NetworkSimulator

    cnn = _narrow()
    assert [layer.pool_s for layer in cnn.conv_layers].count(2) == 5
    assert [layer.c_in for layer in cnn.layers[-3:]] == [512, 256, 256]
    params = {layer.name: np.zeros(layer.weight_shape)
              for layer in layers_of(_config(cnn))}
    sim = NetworkSimulator(cnn, params, backend="trace", streaming=True,
                           dup_cap=VGG16["dup_cap"])
    assert sorted(sim._strips) == [0, 1]
    assert cnn.layers[1].pool_s == 2
    assert all((s.hi - s.lo - 2) % 2 == 0 for s in sim._strips[1])
    fc0 = plan_network(cnn).layers[len(cnn.conv_layers)]
    assert fc0.kind == "fc" and fc0.c_splits == 2


@pytest.fixture(scope="module")
def want(narrow):
    """The plain reference's logits of the narrow net's frames."""
    cnn, cfg, layers, params, calib, x = narrow
    logits = Reference(cfg, params, calib).logits(x)
    assert logits.shape == (FRAMES, 10)
    return logits


def test_serving_path_matches_the_reference(narrow, want):
    """``serve_stream`` on the Pallas jit path reads the plain
    reference's logits exactly."""
    _, rep = _serve(narrow)
    assert _gap(rep.logits, want).max() == 0.0


def test_w4a4_control_misses_the_reference(narrow, want):
    """The w4a4 control, the nearest precision below the
    configuration's, misses the reference by more than the cell's
    ``logit_gap`` limit on every frame."""
    limit = json.loads(
        (ROOT / "bench/limits/vgg16.stream-b16.json").read_text())
    _, low = _serve(narrow, low_bits=4)
    gaps = _gap(low.logits, want)
    assert (gaps > limit["logit_gap"]).all(), gaps


def test_exact_path_matches_cnn_forward():
    """The exact engine on the narrow net equals ``models/cnn.py``'s
    forward bitwise.  Sparse small-integer weights keep every sum an
    integer far below 2**53, so float64 is exact in any order."""
    import jax
    import jax.numpy as jnp

    from repro.core.network import NetworkSimulator
    from repro.models.cnn import cnn_forward

    cnn = _narrow()
    rng = np.random.default_rng(0)
    params = {}
    for layer in layers_of(_config(cnn)):
        w = rng.integers(-1, 2, layer.weight_shape)
        w[rng.random(layer.weight_shape) < 0.7] = 0
        params[layer.name] = w.astype(np.float64)
    x = rng.integers(0, 2, (FRAMES, HW, HW, 3)).astype(np.float64)
    got = NetworkSimulator(cnn, params, backend="trace").run(x).logits
    with jax.enable_x64(True):
        p64 = {k: jnp.asarray(v, jnp.float64) for k, v in params.items()}
        want = np.asarray(cnn_forward(p64, jnp.asarray(x, jnp.float64), cnn))
    assert np.abs(want).max() > 0
    assert np.abs(want).max() < 2.0 ** 53
    np.testing.assert_array_equal(got, want)


def test_net_fc_span_per_fc_layer_and_batch():
    """``net.fc`` closes once per FC layer per numerics batch, and
    ``net.fc.ms_per_frame`` reads its total over the window's frames."""
    from repro.runtime.serve_loop import build_stream_sim, serve_stream
    from repro.telemetry.spans import Profiler

    cnn = _narrow()
    layers = layers_of(_config(cnn))
    rng, key_seed = rngs(11)
    sim = build_stream_sim(cnn, make_weights(layers, key_seed, "int8"),
                           engine="cim", dup_cap=VGG16["dup_cap"],
                           calib_images=frames(rng, 2, HW))
    x = frames(rng, 3, HW)
    with Profiler() as prof:
        serve_stream(sim, x, batch_window=2)
    opened = [ev["args"]["layer"] for ev in prof.events
              if ev["name"] == "net.fc" and ev["ph"] == "B"]
    assert opened == ["fc0", "fc1", "fc2"] * 2
    spans = cell._span_durations(prof.events)
    assert len(spans["net.fc"]) == 6

    spec = cell.load_spec("vgg16.stream-b16", ROOT)
    assert "net.fc.ms_per_frame" in spec.per_layer
    ctx = cell.Context(spec=spec, frames=3, spans=spans)
    unit, value = cell.read_metric("net.fc.ms_per_frame", ctx)
    assert unit == "ms"
    assert value == pytest.approx(sum(spans["net.fc"]) / 3 * 1e3)
    assert value > 0
    ctx = cell.Context(spec=spec, frames=3,
                       spans={"net.residual": [0.5]})
    assert cell.read_metric("net.fc.ms_per_frame", ctx)[1] is None
