"""Pluggable PE numerics engines (``core/engine.py``): the exact engine
must be bit-for-bit the pre-engine behavior, the CIM engine's w8a8+ADC
pipeline must be bitwise-identical across interp / trace / streaming
(ADC codes are integers — association order cannot matter), the Pallas
engine must be ADC-code-exact against the CIM engine, the lossless-spec
invariant must hold on every benchmark conv geometry, and the serving
routes must consume quantized ``{"q","s"}`` params both directly (CIM
engine) and via explicit dequantization."""
import numpy as np
import pytest
from conftest import int_params as _int_params

from repro.configs.cnn import CNN_BENCHMARKS, ConvLayer
from repro.core.cim import CIMSpec, lossless_spec
from repro.core.engine import (
    CIMEngine,
    ExactEngine,
    PallasEngine,
    conv_tile_slices,
    make_engine,
    quantize_weight,
)
from repro.core.mapping import plan_network
from repro.core.network import NetworkSimulator
from repro.core.schedule import compile_conv_block
from repro.core.simulator import BlockSimulator, simulate_fc
from repro.core.trace import TraceExecutor

LOSSY = CIMSpec(n_c=256, adc_bits=8, gain=64.0)


def _float_data(seed, shape, scale=1.0):
    return np.random.default_rng(seed).standard_normal(shape) * scale


def _block(seed, h=8, w=9, c=4, m=6, k=3, stride=1, pad=1, **kw):
    ifm = _float_data(seed, (2, h, w, c))
    wts = _float_data(seed + 1, (k, k, c, m))
    sched = compile_conv_block(f"blk{seed}", h, w, c, m, k, stride, pad, **kw)
    return sched, wts, ifm


def _cal(engine, name, ifm):
    """Minimal per-layer calibration for standalone block tests."""
    return engine.set_layer(name, a_scale=float(np.abs(ifm).max()) / 127)


# ---------------------------------------------------------------------------
# Exact engine: the default, bit-for-bit the pre-engine path
# ---------------------------------------------------------------------------


def test_exact_engine_is_default():
    sched, wts, ifm = _block(0)
    default = BlockSimulator(sched, wts)
    assert default.engine.name == "exact"
    explicit = BlockSimulator(sched, wts, engine=ExactEngine())
    assert default.run(ifm).tobytes() == explicit.run(ifm).tobytes()
    tr = TraceExecutor(sched, wts)
    assert tr.engine.name == "exact"
    assert default.run(ifm).tobytes() == tr.run(ifm).tobytes()


def test_make_engine_registry():
    assert make_engine("exact").name == "exact"
    assert make_engine("cim").name == "cim"
    assert make_engine("pallas").name == "pallas"
    spec = CIMSpec(adc_bits=6)
    assert make_engine("cim", spec).spec.adc_bits == 6
    eng = CIMEngine(spec)
    assert make_engine(eng) is eng
    with pytest.raises(ValueError):
        make_engine("nope")
    with pytest.raises(ValueError):
        make_engine(eng, spec)  # instance already carries its spec
    with pytest.raises(ValueError):
        make_engine("exact", spec)  # spec has no effect on exact


# ---------------------------------------------------------------------------
# CIM engine: quantized block bitwise across executors
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stride,pad,c,m", [(1, 1, 4, 6), (2, 1, 3, 5),
                                            (1, 0, 8, 4)])
def test_cim_block_interp_equals_trace(stride, pad, c, m):
    sched, wts, ifm = _block(11, c=c, m=m, stride=stride, pad=pad)
    eng = _cal(CIMEngine(LOSSY), sched.layer_name, ifm)
    out_i = BlockSimulator(sched, wts, engine=eng).run(ifm)
    out_t = TraceExecutor(sched, wts, engine=eng).run(ifm)
    assert out_i.tobytes() == out_t.tobytes()
    # quantization really engaged: lossy ADC differs from the exact path
    exact = TraceExecutor(sched, wts).run(ifm)
    assert not np.array_equal(out_t, exact)
    # ... but the numerics stay faithful (calibration keeps fidelity)
    denom = np.linalg.norm(exact)
    assert np.linalg.norm(out_t - exact) / denom < 0.2


def test_cim_block_batch_invariance():
    """Integer codes are exact in f64: a frame's quantized bits cannot
    depend on its batch neighbours."""
    sched, wts, ifm = _block(21)
    eng = _cal(CIMEngine(LOSSY), sched.layer_name, ifm)
    tr = TraceExecutor(sched, wts, engine=eng)
    full = tr.run(ifm)
    one = tr.run(ifm[0])
    assert np.array_equal(one, full[0])


def test_cim_counters_match_exact_engine():
    """Engines change numerics, never the event accounting."""
    import dataclasses

    sched, wts, ifm = _block(31)
    ex_i = BlockSimulator(sched, wts)
    ex_i.run(ifm)
    eng = _cal(CIMEngine(LOSSY), sched.layer_name, ifm)
    ci_i = BlockSimulator(sched, wts, engine=eng)
    ci_i.run(ifm)
    assert dataclasses.asdict(ex_i.counters) == dataclasses.asdict(ci_i.counters)


# ---------------------------------------------------------------------------
# Pallas engine: ADC-code-exact against the CIM engine
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("c,m,c_splits", [(4, 6, 1), (6, 4, 2)])
def test_pallas_block_codes_equal_cim(c, m, c_splits):
    kw = dict(c_splits=c_splits) if c_splits > 1 else {}
    sched, wts, ifm = _block(41, c=c, m=m, **kw)
    a_scale = float(np.abs(ifm).max()) / 127
    cim = CIMEngine(LOSSY).set_layer(sched.layer_name, a_scale=a_scale)
    pal = PallasEngine(LOSSY).set_layer(sched.layer_name, a_scale=a_scale)
    out_c = TraceExecutor(sched, wts, engine=cim).run(ifm)
    out_p = TraceExecutor(sched, wts, engine=pal).run(ifm)
    assert out_c.tobytes() == out_p.tobytes()
    # and through the per-cycle interpreter too
    out_pi = BlockSimulator(sched, wts, engine=pal).run(ifm)
    assert out_pi.tobytes() == out_c.tobytes()


def test_pallas_fc_codes_equal_cim():
    rng = np.random.default_rng(5)
    x = rng.standard_normal((3, 300))
    w = rng.standard_normal((300, 20))
    a_scale = float(np.abs(x).max()) / 127
    cim = CIMEngine(LOSSY).set_layer("fc", a_scale=a_scale)
    pal = PallasEngine(LOSSY).set_layer("fc", a_scale=a_scale)
    out_c = simulate_fc(x, w, 256, 256, engine=cim)
    out_p = simulate_fc(x, w, 256, 256, engine=pal)
    assert out_c.tobytes() == out_p.tobytes()
    # B=1 lane equality holds under quantization as well
    one = simulate_fc(x[:1], w, 256, 256, engine=cim)
    assert np.array_equal(one, out_c[:1])


def test_fc_subarray_split_when_spec_narrower_than_grid():
    """An FC grid tile holding more weight rows than the spec's subarray
    must convert per ``spec.n_c`` rows — one ADC each, codes accumulated
    digitally — exactly like the Pallas kernel's K steps.  (Regression:
    this used to be one oversized conversion, silently diverging from
    the Pallas engine.)"""
    rng = np.random.default_rng(6)
    x = rng.standard_normal((3, 512))
    w = rng.standard_normal((512, 64))
    spec = CIMSpec(n_c=128, adc_bits=8, gain=64.0)
    a_scale = float(np.abs(x).max()) / 127
    cim = CIMEngine(spec).set_layer("fc", a_scale=a_scale)
    pal = PallasEngine(spec).set_layer("fc", a_scale=a_scale)
    out_c = simulate_fc(x, w, 256, 256, engine=cim)  # grid n_c 256 > 128
    out_p = simulate_fc(x, w, 256, 256, engine=pal)
    assert out_c.tobytes() == out_p.tobytes()
    # and the split really bites: a one-conversion-per-tile spec differs
    wide = CIMEngine(CIMSpec(n_c=256, adc_bits=8, gain=64.0)).set_layer(
        "fc", a_scale=a_scale)
    assert not np.array_equal(out_c, simulate_fc(x, w, 256, 256,
                                                 engine=wide))


# ---------------------------------------------------------------------------
# calibrate_gain + lossless-spec invariant on every benchmark geometry
# ---------------------------------------------------------------------------


def _proxy_geometries():
    """One shrunk proxy per distinct conv shape (k, stride, pad, pack,
    c_splits) in any benchmark plan — same sweep as tests/test_trace.py."""
    seen = {}
    for name, fn in CNN_BENCHMARKS.items():
        cnn = fn()
        plan = plan_network(cnn)
        for layer, lp in zip(cnn.layers, plan.layers):
            if not isinstance(layer, ConvLayer):
                continue
            sig = (layer.k, layer.s, layer.p, lp.pack, lp.c_splits)
            seen.setdefault(sig, name)
    return sorted((sig, name) for sig, name in seen.items())


def _w8a8_reference(ifm, wts, sched, handle):
    """Plain w8a8 (no ADC loss): im2col exact int matmul through the
    engine's own quantization and dequantization."""
    import jax.numpy as jnp
    from jax import lax

    k, stride, pad = sched.k, sched.stride, sched.pad
    patches = np.asarray(lax.conv_general_dilated_patches(
        jnp.asarray(ifm, jnp.float32), (k, k), (stride, stride),
        padding=[(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC")), np.float64)
    b, e, f, _ = patches.shape
    xq = np.clip(np.round(patches.reshape(b * e * f, -1) / handle.a_scale),
                 -128, 127)
    qw, _ = quantize_weight(wts)
    # patches emit (C, K, K)-ordered features; engine weights are (K, K, C)
    wq = qw.transpose(2, 0, 1, 3).reshape(-1, wts.shape[-1]).astype(np.float64)
    exact = xq @ wq  # exact ints: association-order-free
    out = exact.reshape(b, e, f, -1) * handle.deq
    return np.maximum(out, 0.0)  # the compiled block's relu tail


@pytest.mark.parametrize("sig,config", _proxy_geometries())
def test_lossless_spec_equals_w8a8_exact(sig, config):
    """Satellite invariant: with ``CIMSpec.lossless`` (ADC step <= 1 —
    here exactly 1), the quantized pipeline must equal the plain w8a8
    int path bit-for-bit on every benchmark conv geometry, on both
    backends."""
    k, stride, pad, pack, c_splits = sig
    c_in = max(2 * c_splits, pack)
    c_out, h = 3, 8
    w = h + 1
    ifm = _float_data(k + stride, (2, h, w, c_in))
    wts = _float_data(2 * k, (k, k, c_in, c_out))
    sched = compile_conv_block(f"ll-{config}", h, w, c_in, c_out, k,
                               stride, pad, pack=pack, c_splits=c_splits)
    spec = lossless_spec(256)
    assert spec.lossless
    eng = _cal(CIMEngine(spec), sched.layer_name, ifm)
    handle = eng.conv_handle(sched.layer_name, wts, conv_tile_slices(sched))
    ref = _w8a8_reference(ifm, wts, sched, handle)
    out_t = TraceExecutor(sched, wts, engine=eng).run(ifm)
    assert out_t.tobytes() == ref.tobytes(), "trace != w8a8 exact"
    out_i = BlockSimulator(sched, wts, engine=eng).run(ifm)
    assert out_i.tobytes() == ref.tobytes(), "interp != w8a8 exact"


def test_lossy_spec_breaks_w8a8_equality():
    """The lossless test has teeth: a default 8-bit ADC does NOT equal
    the plain int path on the same data."""
    sched, wts, ifm = _block(51)
    eng = _cal(CIMEngine(LOSSY), sched.layer_name, ifm)
    handle = eng.conv_handle(sched.layer_name, wts, conv_tile_slices(sched))
    ref = _w8a8_reference(ifm, wts, sched, handle)
    out = TraceExecutor(sched, wts, engine=eng).run(ifm)
    assert not np.array_equal(out, ref)


def test_calibrate_gain_fills_adc_range():
    """Calibration picks a gain >= 1 that keeps fidelity: the calibrated
    engine must beat an uncalibrated unit-gain spec on the same data."""
    from repro.core.cim import calibrate_gain
    import jax.numpy as jnp

    rng = np.random.default_rng(8)
    x = rng.standard_normal((64, 512)).astype(np.float32)
    w = (rng.standard_normal((512, 128)) / 512 ** 0.5).astype(np.float32)
    spec = CIMSpec(n_c=256, adc_bits=8, gain=1.0)
    g = calibrate_gain(jnp.asarray(x), jnp.asarray(w), spec)
    assert g >= 1.0

    def err(gain):
        eng = CIMEngine(CIMSpec(n_c=256, adc_bits=8, gain=gain)).set_layer(
            "fc", a_scale=float(np.abs(x).max()) / 127)
        got = simulate_fc(x.astype(np.float64), w.astype(np.float64),
                          256, 256, engine=eng)
        want = x.astype(np.float64) @ w.astype(np.float64)
        return np.linalg.norm(got - want) / np.linalg.norm(want)

    assert err(g) < 0.5 * err(1.0)  # unit gain starves the converter
    assert err(g) < 0.05


def test_network_calibration_covers_every_layer():
    rng = np.random.default_rng(2)
    cnn = CNN_BENCHMARKS["vgg11-cifar10"]()
    params = _int_params(cnn, rng)
    sim = NetworkSimulator(cnn, params, backend="trace", engine="cim")
    eng = sim.pe_engine
    for layer in cnn.layers:
        assert layer.name in eng.calib, layer.name
        cal = eng.calib[layer.name]
        assert cal.a_scale > 0 and cal.gain >= 1.0


def test_resnet50_deep_integer_build_is_warning_clean():
    """{-1, 0, 1} weights grow activations ~1e56 through resnet50-imagenet,
    past float32's range; inputs scaled by 2^-64 (exact in f32 and f64)
    keep the engine's float32 calibration forward inside it.  Build and
    run then raise no RuntimeWarning — an overflowed calibration once
    surfaced as inf activation scales and invalid-cast warnings at the
    int8 quantization — and every activation scale is finite."""
    import warnings

    rng = np.random.default_rng(0)
    cnn = CNN_BENCHMARKS["resnet50-imagenet"]()
    params = _int_params(cnn, rng)
    frames = rng.random((1, cnn.input_hw, cnn.input_hw, 3)) * 2.0 ** -64
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        sim = NetworkSimulator(cnn, params, backend="trace", engine="cim",
                               calib_images=frames, dup_cap=128)
        sim.run(frames)
    scales = [cal.a_scale for cal in sim.pe_engine.calib.values()]
    assert len(scales) == len(cnn.layers)
    assert all(np.isfinite(s) and s > 0 for s in scales)


# ---------------------------------------------------------------------------
# Whole-network quantized execution (the acceptance criteria)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def vgg11_cim():
    rng = np.random.default_rng(7)
    cnn = CNN_BENCHMARKS["vgg11-cifar10"]()
    params = _int_params(cnn, rng)
    x = rng.integers(0, 2, (2, 32, 32, 3)).astype(np.float64)
    engine = CIMEngine(LOSSY)  # shared: calibrates once
    trace = NetworkSimulator(cnn, params, backend="trace", engine=engine)
    return cnn, params, x, engine, trace


def test_network_cim_interp_equals_trace(vgg11_cim):
    cnn, params, x, engine, trace = vgg11_cim
    res_t = trace.run(x)
    res_i = NetworkSimulator(cnn, params, backend="interp",
                             engine=engine).run(x)
    assert res_t.logits.tobytes() == res_i.logits.tobytes()
    assert res_t.counters == res_i.counters
    assert res_t.traffic.byte_hops == res_i.traffic.byte_hops


def test_network_cim_tracks_float_forward(vgg11_cim):
    import jax.numpy as jnp

    from repro.models.cnn import cnn_forward

    cnn, params, x, engine, trace = vgg11_cim
    res = trace.run(x)
    ref = np.asarray(cnn_forward(
        {k: jnp.asarray(v, jnp.float32) for k, v in params.items()},
        jnp.asarray(x, jnp.float32), cnn))
    assert (res.logits.argmax(-1) == ref.argmax(-1)).all()
    corr = np.corrcoef(res.logits.ravel(), ref.ravel())[0, 1]
    assert corr > 0.98, corr


def test_network_cim_streaming_matches_sequential(vgg11_cim):
    cnn, params, x, engine, trace = vgg11_cim
    rng = np.random.default_rng(9)
    frames = rng.integers(0, 2, (3, 32, 32, 3)).astype(np.float64)
    sim = NetworkSimulator(cnn, params, backend="trace", streaming=True,
                           engine=engine)
    sres = sim.run_stream(frames)
    seq = sim.run(frames)
    assert sres.logits.tobytes() == seq.logits.tobytes()
    assert sres.measured_ii == sres.analytic_ii


def test_network_pallas_equals_cim(vgg11_cim):
    cnn, params, x, engine, trace = vgg11_cim
    pal = PallasEngine(LOSSY)
    pal.calib = dict(engine.calib)  # same calibration -> same codes
    res_p = NetworkSimulator(cnn, params, backend="trace",
                             engine=pal).run(x)
    assert res_p.logits.tobytes() == trace.run(x).logits.tobytes()


@pytest.mark.slow
def test_network_cim_resnet18_interp_equals_trace():
    rng = np.random.default_rng(7)
    cnn = CNN_BENCHMARKS["resnet18-cifar10"]()
    params = _int_params(cnn, rng)
    x = rng.integers(0, 2, (2, 32, 32, 3)).astype(np.float64)
    engine = CIMEngine(LOSSY)
    res_t = NetworkSimulator(cnn, params, backend="trace",
                             engine=engine).run(x)
    res_i = NetworkSimulator(cnn, params, backend="interp",
                             engine=engine).run(x)
    assert res_t.logits.tobytes() == res_i.logits.tobytes()
    # streaming under quantized residual FIFOs stays bitwise too
    frames = rng.integers(0, 2, (3, 32, 32, 3)).astype(np.float64)
    sim = NetworkSimulator(cnn, params, backend="trace", streaming=True,
                           engine=engine)
    assert sim.run_stream(frames).logits.tobytes() == \
        sim.run(frames).logits.tobytes()


def test_network_engine_flag_validation():
    rng = np.random.default_rng(1)
    cnn = CNN_BENCHMARKS["vgg11-cifar10"]()
    params = _int_params(cnn, rng)
    for streaming in (False, True):  # the exact engine has no jit flavor
        with pytest.raises(ValueError, match="quantized engine"):
            NetworkSimulator(cnn, params, backend="trace", trace_jit=True,
                             streaming=streaming)
    with pytest.raises(ValueError):  # calib images are a quantized knob
        NetworkSimulator(cnn, params, calib_images=np.zeros((1, 32, 32, 3)))
    with pytest.raises(ValueError):
        NetworkSimulator(cnn, params, engine="bogus")
    sched, wts, ifm = _block(3)
    with pytest.raises(ValueError, match="no jit form"):  # exact engine
        TraceExecutor(sched, wts, use_jax=True)
    with pytest.raises(ValueError):  # quantized jit has no per-tile form
        TraceExecutor(sched, wts, use_jax=True, fused=False,
                      engine=_cal(CIMEngine(LOSSY), sched.layer_name, ifm))


def test_network_quantized_trace_jit_is_bitwise():
    """trace_jit on a quantized engine is the fused integer jit flavor —
    bitwise with the numpy trace, and therefore allowed to combine with
    streaming."""
    rng = np.random.default_rng(6)
    cnn = CNN_BENCHMARKS["vgg11-cifar10"]()
    params = {k: v * 0.1 for k, v in _int_params(cnn, rng).items()}
    frames = rng.random((3, 32, 32, 3))
    # one pre-shared engine instance: calibration runs once, all three
    # simulators run identical per-layer scales/gains
    kw = dict(backend="trace", engine=CIMEngine(LOSSY),
              calib_images=frames[:1])
    base = NetworkSimulator(cnn, params, **kw).run(frames)
    jit = NetworkSimulator(cnn, params, trace_jit=True, **kw).run(frames)
    stream_jit = NetworkSimulator(cnn, params, trace_jit=True,
                                  streaming=True, **kw).run(frames)
    assert jit.logits.tobytes() == base.logits.tobytes()
    assert stream_jit.logits.tobytes() == base.logits.tobytes()


# ---------------------------------------------------------------------------
# Serving routes for quantized {"q","s"} params (the serve_loop satellite)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def vgg11_quantized():
    from repro.runtime.serve_loop import quantize_cnn_params_for_serving

    rng = np.random.default_rng(3)
    cnn = CNN_BENCHMARKS["vgg11-cifar10"]()
    params = {k: v * 0.1 for k, v in _int_params(cnn, rng).items()}
    frames = rng.random((3, 32, 32, 3))
    return cnn, params, quantize_cnn_params_for_serving(params), frames


def test_serving_quantized_params_run_cim_engine(vgg11_quantized):
    from repro.runtime.serve_loop import build_stream_sim, serve_stream

    cnn, params, qparams, frames = vgg11_quantized
    sim = build_stream_sim(cnn, qparams)
    assert sim.pe_engine.name == "cim"
    rep = serve_stream(sim, frames)
    assert rep.measured_ii == rep.analytic_ii
    assert np.isfinite(rep.latency_cycles).all()
    # the resident int8 weights are exactly what the engine would build
    # from the float params itself — the two routes are bit-identical
    sim_f = NetworkSimulator(cnn, params, backend="trace", streaming=True,
                             engine="cim")
    assert sim.run(frames).logits.tobytes() == \
        sim_f.run(frames).logits.tobytes()


def test_serving_dequantize_route(vgg11_quantized):
    from repro.runtime.serve_loop import build_stream_sim, dequantize_params

    cnn, params, qparams, frames = vgg11_quantized
    deq = dequantize_params(qparams)
    sim = build_stream_sim(cnn, deq)
    assert sim.pe_engine.name == "exact"  # explicit float route
    res = sim.run(frames)
    assert res.logits.shape == (3, 10)
    # dequantized weights are the q*s roundtrip, close to the originals
    for name, w in params.items():
        err = np.abs(deq[name] - w).max() / max(np.abs(w).max(), 1e-9)
        assert err < 1 / 100, name


def test_exact_engine_rejects_quantized_params(vgg11_quantized):
    cnn, params, qparams, frames = vgg11_quantized
    with pytest.raises(ValueError, match="dequantize"):
        NetworkSimulator(cnn, qparams, backend="trace")
