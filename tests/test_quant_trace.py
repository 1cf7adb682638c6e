"""The fused integer-native quantized trace path (``core/trace.py``):
the batch-of-tiles lowering must reproduce the per-tile interpreter
fold's ADC codes bit-for-bit on ragged geometries — K % n_c != 0,
C > N_c split chains, FC grids whose tile spans several spec subarrays,
B == 1 — for both quantized engines and for the jit flavor, and the
vectorized conversion must equal per-tile conversion code-for-code."""
import dataclasses

import numpy as np
import pytest
from conftest import int_params as _int_params

from repro.configs.cnn import CNN_BENCHMARKS, ConvLayer, _vgg
from repro.core.cim import CIMSpec, adc_convert
from repro.core.engine import (
    EXACT_ENGINE,
    CIMEngine,
    PallasEngine,
    conv_tile_slices,
)
from repro.core.network import NetworkSimulator
from repro.core.schedule import compile_conv_block
from repro.core.simulator import BlockSimulator, simulate_fc
from repro.core.trace import TraceExecutor
from repro.core.variation import VariationModel
from repro.telemetry.spans import Profiler

LOSSY = CIMSpec(n_c=256, adc_bits=8, gain=64.0)
#: small subarray so conv tiles are K-ragged (kc < n_c) *and* FC grid
#: tiles span several spec subarrays (grid n_c 256 > spec n_c)
NARROW = CIMSpec(n_c=64, adc_bits=8, gain=48.0)

ENGINES = {"cim": CIMEngine, "pallas": PallasEngine}

#: ragged conv geometries: K % n_c != 0 (every tile's pack*Cs < n_c),
#: C > N_c split chains (c_splits), odd widths, stride, 1x1, pooling
GEOMS = [
    dict(h=8, w=9, c=5, m=6, k=3, stride=1, pad=1),
    dict(h=8, w=8, c=9, m=6, k=3, stride=1, pad=1, c_splits=3),
    dict(h=9, w=7, c=4, m=5, k=3, stride=2, pad=1),
    dict(h=6, w=6, c=7, m=4, k=1, stride=1, pad=0),
    dict(h=8, w=8, c=4, m=6, k=3, stride=1, pad=1, pool_k=2, pool_s=2),
]


def _block(seed, spec, engine_cls, batch, **kw):
    r = np.random.default_rng(seed)
    ifm = r.standard_normal((batch, kw["h"], kw["w"], kw["c"]))
    wts = r.standard_normal((kw["k"], kw["k"], kw["c"], kw["m"]))
    sched = compile_conv_block(
        f"rag{seed}", kw["h"], kw["w"], kw["c"], kw["m"], kw["k"],
        kw["stride"], kw["pad"],
        **{k: v for k, v in kw.items()
           if k in ("c_splits", "pool_k", "pool_s")})
    eng = engine_cls(spec).set_layer(
        sched.layer_name, a_scale=float(np.abs(ifm).max()) / 127)
    return sched, wts, ifm, eng


@pytest.mark.parametrize("engine", list(ENGINES))
@pytest.mark.parametrize("gi", range(len(GEOMS)))
@pytest.mark.parametrize("batch", [1, 2])
def test_fused_equals_pertile_equals_interp(engine, gi, batch):
    """interp == fused trace == per-tile trace == jit flavor, bitwise,
    on every ragged geometry, including unbatched B == 1 runs."""
    sched, wts, ifm, eng = _block(
        10 + gi, NARROW, ENGINES[engine], batch, **GEOMS[gi])
    interp = BlockSimulator(sched, wts, engine=eng).run(ifm)
    fused = TraceExecutor(sched, wts, engine=eng).run(ifm)
    pertile = TraceExecutor(sched, wts, engine=eng, fused=False).run(ifm)
    jit = TraceExecutor(sched, wts, engine=eng, use_jax=True).run(ifm)
    assert interp.tobytes() == fused.tobytes()
    assert interp.tobytes() == pertile.tobytes()
    assert interp.tobytes() == jit.tobytes()


@pytest.mark.parametrize("engine", list(ENGINES))
def test_batched_conversion_equals_pertile_conversion(engine):
    """The one-shot (tiles, rows, pixels) conversion is code-for-code
    the per-tile conversion: tiles_mac == the tile_mac chain fold."""
    sched, wts, ifm, eng = _block(3, NARROW, ENGINES[engine], 2, **GEOMS[0])
    h = eng.conv_handle(sched.layer_name, wts, conv_tile_slices(sched))
    rng = np.random.default_rng(0)
    t, kcm = len(h.kc), max(h.kc)
    patches = np.zeros((t, 6, kcm))
    for i, kc in enumerate(h.kc):
        patches[i, :, :kc] = rng.integers(-128, 128, (6, kc))
    fused = eng.tiles_mac(h, patches)
    ref = np.zeros_like(fused)
    for i, kc in enumerate(h.kc):  # per-tile dots + per-tile conversions
        d = patches[i, :, :kc] @ h.tile_w[i].reshape(kc, -1)
        ref += adc_convert(d, h.inv_step32, h.code_lo, h.code_hi)
    assert fused.tobytes() == ref.tobytes()


@pytest.mark.parametrize("engine", list(ENGINES))
@pytest.mark.parametrize("batch", [1, 3])
def test_fc_grid_spanning_subarrays_bitwise(engine, batch):
    """FC grid n_c (256) > spec n_c (64): each grid tile spans four
    spec subarrays — the vectorized multi-subarray conversion must
    match an explicit per-subarray reference loop bit-for-bit."""
    rng = np.random.default_rng(5)
    x = rng.standard_normal((batch, 200))   # K % n_c != 0 tail tile too
    w = rng.standard_normal((200, 30))
    eng = ENGINES[engine](NARROW).set_layer(
        "fc", a_scale=float(np.abs(x).max()) / 127)
    got = simulate_fc(x, w, 256, 256, engine=eng)

    h = eng.fc_handle("fc", w)
    xq = np.clip(np.round(x / h.a_scale), -128, 127)
    codes = np.zeros((batch, 30))
    for s0 in range(0, 200, NARROW.n_c):    # reference: one ADC per chunk
        d = xq[:, s0:s0 + NARROW.n_c] @ h.w[s0:s0 + NARROW.n_c]
        codes += adc_convert(d, h.inv_step32, h.code_lo, h.code_hi)
    ref = codes * h.deq
    assert got.tobytes() == ref.tobytes()


#: all injection mechanisms at once: conductance noise, stuck-at cells,
#: per-subarray ADC offset and gain error
VARIED = VariationModel(seed=7, conductance_sigma=0.02, stuck_zero=0.01,
                        stuck_one=0.004, adc_offset_sigma=0.4,
                        adc_gain_sigma=0.02)
ZERO = VariationModel(seed=7)


@pytest.mark.parametrize("gi", range(len(GEOMS)))
@pytest.mark.parametrize("batch", [1, 2])
def test_variation_lowerings_and_engines_bitwise(gi, batch):
    """Same seed => same physics, bitwise: under a full variation model
    the perturbed codes agree across interp == fused == per-tile == jit
    lowerings AND across CIMEngine vs PallasEngine, on every ragged
    geometry.  Variation perturbs the resident weights / ADC transfer
    once at handle build, so the lowering invariants survive intact."""
    outs = {}
    for engine in ENGINES:
        sched, wts, ifm, eng = _block(
            20 + gi, NARROW, ENGINES[engine], batch, **GEOMS[gi])
        eng.variation = VARIED
        interp = BlockSimulator(sched, wts, engine=eng).run(ifm)
        fused = TraceExecutor(sched, wts, engine=eng).run(ifm)
        pertile = TraceExecutor(sched, wts, engine=eng, fused=False).run(ifm)
        jit = TraceExecutor(sched, wts, engine=eng, use_jax=True).run(ifm)
        assert interp.tobytes() == fused.tobytes()
        assert interp.tobytes() == pertile.tobytes()
        assert interp.tobytes() == jit.tobytes()
        outs[engine] = interp
    assert outs["cim"].tobytes() == outs["pallas"].tobytes()


@pytest.mark.parametrize("engine", list(ENGINES))
@pytest.mark.parametrize("gi", range(len(GEOMS)))
def test_zero_magnitude_variation_is_bitwise_nominal(engine, gi):
    """A zero-magnitude VariationModel must be invisible: all sigmas /
    fractions at 0.0 skips injection entirely, so codes are bitwise
    equal to an engine with no variation model at all."""
    sched, wts, ifm, eng = _block(30 + gi, NARROW, ENGINES[engine], 2,
                                  **GEOMS[gi])
    nominal = TraceExecutor(sched, wts, engine=eng).run(ifm)
    _, _, _, eng_z = _block(30 + gi, NARROW, ENGINES[engine], 2,
                            **GEOMS[gi])
    eng_z.variation = ZERO
    varied = TraceExecutor(sched, wts, engine=eng_z).run(ifm)
    assert nominal.tobytes() == varied.tobytes()


def test_variation_changes_codes():
    """Sanity: the full variation model actually perturbs something on
    a geometry with enough cells (else the bitwise tests above could
    pass vacuously through a no-op injection path)."""
    sched, wts, ifm, eng = _block(40, NARROW, CIMEngine, 2, **GEOMS[1])
    nominal = TraceExecutor(sched, wts, engine=eng).run(ifm)
    _, _, _, eng_v = _block(40, NARROW, CIMEngine, 2, **GEOMS[1])
    eng_v.variation = VARIED
    varied = TraceExecutor(sched, wts, engine=eng_v).run(ifm)
    assert nominal.tobytes() != varied.tobytes()


@pytest.mark.parametrize("engine", list(ENGINES))
def test_network_ragged_interp_trace_stream_bitwise(engine):
    """Whole-network interp == trace == streaming == trace_jit on
    vgg11, where every conv tile is K-ragged (pack * Cs < n_c) and the
    512-channel layers split chains (C > N_c)."""
    rng = np.random.default_rng(9)
    cnn = CNN_BENCHMARKS["vgg11-cifar10"]()
    params = {k: v * 0.1 for k, v in _int_params(cnn, rng).items()}
    frames = rng.random((2, 32, 32, 3))
    eng = ENGINES[engine](LOSSY)  # shared: calibrate once, compare runs
    kw = dict(engine=eng, calib_images=frames[:1])
    interp = NetworkSimulator(cnn, params, backend="interp", **kw).run(frames)
    trace = NetworkSimulator(cnn, params, backend="trace", **kw).run(frames)
    stream = NetworkSimulator(cnn, params, backend="trace", streaming=True,
                              **kw).run(frames)
    jit = NetworkSimulator(cnn, params, backend="trace", trace_jit=True,
                           **kw).run(frames)
    assert interp.logits.tobytes() == trace.logits.tobytes()
    assert interp.logits.tobytes() == stream.logits.tobytes()
    assert interp.logits.tobytes() == jit.logits.tobytes()


# -- staging: the layer input quantized straight into the int8 raster ------

#: every distinct (H, W, C, pad) conv input of ``CNN_BENCHMARKS`` — pads 0,
#: 1 and 3 (the resnet50 stem), CIFAR to ImageNet widths
STAGE_GEOMS = sorted({(l.h, l.w, l.c, l.p)
                      for fn in CNN_BENCHMARKS.values()
                      for l in fn().layers if isinstance(l, ConvLayer)})


def _qhandle(a_scale):
    """A quantized engine handle carrying ``a_scale`` (what
    ``quant_stream`` reads: the scale and the code saturation)."""
    eng = CIMEngine(LOSSY).set_layer("stage", a_scale=a_scale)
    return eng, eng.fc_handle("stage", np.ones((4, 4)))


def _staged(eng, h, x, p):
    """The executor's staging of ``x`` into a fresh zero int8 raster."""
    b, hh, ww, c = x.shape
    raster = np.zeros((b, hh + 2 * p, ww + 2 * p, c), np.int8)
    eng.quant_stream(h, x, out=raster[:, p:p + hh, p:p + ww])
    return raster


def _reference(eng, h, x, p):
    """What the executor staged before: quantize the float64 padded copy,
    then cast to int8."""
    b, hh, ww, c = x.shape
    padded = np.zeros((b, hh + 2 * p, ww + 2 * p, c), np.float64)
    padded[:, p:p + hh, p:p + ww] = x
    return eng.quant_stream(h, padded).astype(np.int8)


@pytest.mark.parametrize("geom", STAGE_GEOMS, ids=lambda g: "x".join(
    map(str, g[:3])) + f"p{g[3]}")
def test_stage_quant_bitwise_on_benchmark_geometries(geom):
    """The blocked pass into the raster interior equals the float64
    padded quantization cast to int8, border included, on every
    benchmark conv input (the scale saturates the tails at both clip
    edges)."""
    hh, ww, c, p = geom
    x = np.random.default_rng(hh * ww + c + p).standard_normal(
        (2, hh, ww, c))
    eng, h = _qhandle(float(np.abs(x).max()) / 200)
    got = _staged(eng, h, x, p)
    assert got.tobytes() == _reference(eng, h, x, p).tobytes()
    assert got.min() == -128 and got.max() == 127


def test_stage_quant_float32_divides_in_float64():
    """A float32 input is divided in float64, as its float64 padded copy
    was: on inputs next to the .5 ties, where a float32 division would
    round the other way, the staged codes equal the float64 reference."""
    a = 0.0137
    ties = (np.arange(-130, 130) + 0.5) * a
    x32 = np.concatenate([
        np.nextafter(np.float32(ties), np.float32(d))
        for d in (-np.inf, 0, np.inf)]).astype(np.float32)
    x32 = np.concatenate([np.float32(ties), x32])
    # the data discriminate: a float32 division rounds some differently
    f64 = np.rint(x32.astype(np.float64) / a)
    assert (np.rint(x32 / np.float32(a)) != f64).any()
    x = np.resize(x32, (2, 5, 7, 16))
    eng, h = _qhandle(a)
    got = _staged(eng, h, x, 1)
    assert got.tobytes() == _reference(eng, h, x, 1).tobytes()


def test_stage_quant_strided_width_strip_view():
    """A non-contiguous width strip of a padded input (what
    ``NetworkSimulator._run_layer`` hands each strip executor) stages to
    the same codes as its contiguous copy."""
    r = np.random.default_rng(5)
    padded = r.standard_normal((3, 20, 230, 6))
    eng, h = _qhandle(0.01)
    for lo, hi in ((0, 100), (96, 230), (57, 58)):
        view = padded[:, :, lo:hi]
        assert not view.flags.c_contiguous
        got = _staged(eng, h, view, 0)
        assert got.tobytes() == _reference(
            eng, h, np.ascontiguousarray(view), 0).tobytes()


@pytest.mark.parametrize("a_scale", [0.25, 0.0137])
def test_stage_quant_ties_and_clip_edges(a_scale):
    """Exact .5 ties of ``x / a_scale`` round half to even and the codes
    saturate at -128 and 127, as ``np.clip(np.round(...))`` does."""
    k = np.arange(-140, 141, dtype=np.float64)
    vals = np.concatenate([
        (k + 0.5) * a_scale, k * a_scale,
        np.array([-128.5, -128.49, -129.0, 127.5, 127.49, 128.0,
                  1e9, -1e9, 0.0, -0.0, 1e-300, -1e-300]) * a_scale])
    x = np.resize(vals, (2, 3, 4, vals.size // 24 + 1))
    eng, h = _qhandle(a_scale)
    got = _staged(eng, h, x, 2)
    assert got.tobytes() == _reference(eng, h, x, 2).tobytes()
    if a_scale == 0.25:  # power of two: the quotients are exact ties
        codes = got[:, 2:-2, 2:-2].ravel()[:k.size]
        want = np.clip(np.round(k + 0.5), -128, 127)
        assert (codes == want).all()
        assert {-128, 127} <= set(codes.tolist())


@pytest.mark.parametrize("engine", list(ENGINES))
@pytest.mark.parametrize("use_jax", [False, True])
def test_reused_raster_back_to_back_runs(engine, use_jax):
    """One executor on two inputs back to back: the second output equals
    a fresh executor's, and both runs staged into the same int8 raster —
    its border stays zero and nothing of the first input, on the host
    or the device, reaches the second."""
    sched, wts, ifm, eng = _block(50, NARROW, ENGINES[engine], 2,
                                  **GEOMS[0])
    other = np.random.default_rng(51).standard_normal(ifm.shape) * 3
    ex = TraceExecutor(sched, wts, engine=eng, use_jax=use_jax)
    ex.run(other)
    raster = ex._scratch["raster8"]
    second = ex.run(ifm)
    assert ex._scratch["raster8"] is raster
    fresh = TraceExecutor(sched, wts, engine=eng, use_jax=use_jax).run(ifm)
    assert second.tobytes() == fresh.tobytes()
    p = sched.pad
    assert raster.tobytes() == _reference(eng, ex.handle, ifm, p).tobytes()


# -- the block tail: one blocked pass over the code sums -------------------

#: every distinct conv output (E, F, M) of ``CNN_BENCHMARKS`` before any
#: pool, each with and without a 2x2/s2 pool where the pool tiles it
TAIL_GEOMS = sorted({((l.h + 2 * l.p - l.k + l.s) // l.s,
                      (l.w + 2 * l.p - l.k + l.s) // l.s, l.m)
                     for fn in CNN_BENCHMARKS.values()
                     for l in fn().layers if isinstance(l, ConvLayer)})
TAIL_CASES = [(e, f, m, ps) for e, f, m in TAIL_GEOMS for ps in (0, 2)
              if e % 2 == 0 and f % 2 == 0 or not ps]

#: how the code sums reach the tail: the jit path's int32 (CIM) or
#: float32 (Pallas) sums, the host paths' float64, and an int32 width
#: strip of a wider array (non-contiguous)
TAIL_LAYOUTS = ("int32", "float32", "float64", "int32-strip")


def _tail_executor(m, pool_s, engine=None, bias=None):
    """An executor whose block tail has ``m`` output channels, ReLU and a
    ``pool_s`` pool; the tail takes its E and F from the code sums."""
    hw = max(pool_s, 1)
    sched = compile_conv_block(f"tail{m}p{pool_s}", hw, hw, 4, m, 1, 1, 0,
                               pool_k=pool_s, pool_s=pool_s)
    wts = np.random.default_rng(m).standard_normal((1, 1, 4, m))
    if engine is None:
        engine = CIMEngine(LOSSY).set_layer(sched.layer_name, a_scale=0.01)
    return TraceExecutor(sched, wts, engine=engine, bias=bias)


def _code_sums(shape, h, seed):
    """Exact integer code sums of three tiles' codes, a quarter exact
    zeros, with the single-tile and three-tile saturation edges."""
    r = np.random.default_rng(seed)
    lo, hi = 3 * h.code_lo, 3 * h.code_hi
    codes = r.integers(lo, hi + 1, shape).astype(np.float64)
    pick = r.random(shape)
    codes[pick < 0.25] = 0.0
    for v, (a, b) in zip((lo, hi, h.code_lo, h.code_hi),
                         ((0.25, 0.27), (0.27, 0.29), (0.29, 0.31),
                          (0.31, 0.33))):
        codes[(pick >= a) & (pick < b)] = v
    return codes


def _layout(codes, layout):
    if layout == "int32-strip":
        b, e, f, m = codes.shape
        wide = np.zeros((b, e, f + 5, m), np.int32)
        view = wide[:, :, 3:3 + f]
        view[...] = codes
        assert not view.flags.c_contiguous
        return view
    return codes.astype(layout)


def _stepwise_tail(acc, deq, bias, pool_s):
    """The tail as separate full-size steps: widen, times ``deq``, bias,
    ReLU, then the Fig. 9 fold — the running max over each window row's
    pixels, then over the window rows."""
    out = np.asarray(acc, np.float64)
    if deq is not None:
        out = out * deq
    if bias is not None:
        out = out + bias
    out = np.maximum(out, 0.0)
    if pool_s:
        b, e, f, m = out.shape
        win = out.reshape(b, e // pool_s, pool_s, f // pool_s, pool_s, m)
        row = win[:, :, :, :, 0]
        for x in range(1, pool_s):
            row = np.maximum(row, win[:, :, :, :, x])
        out = row[:, :, 0]
        for y in range(1, pool_s):
            out = np.maximum(out, row[:, :, y])
    return out


def _tail_pool_first(ex, acc):
    """The tail's output and its ``te.tail_pool_first`` count."""
    with Profiler() as prof:
        got = ex._tail_np(acc)
    return got, prof.counts.get("te.tail_pool_first", 0)


@pytest.mark.parametrize("layout", TAIL_LAYOUTS)
@pytest.mark.parametrize("case", TAIL_CASES, ids=lambda c: "x".join(
    map(str, c[:3])) + f"p{c[3]}")
def test_tail_bitwise_on_benchmark_geometries(case, layout):
    """The blocked tail equals the stepwise tail byte for byte on every
    benchmark conv output, pooled before dequantization where it pools,
    whatever dtype and strides the code sums come in."""
    e, f, m, ps = case
    ex = _tail_executor(m, ps)
    codes = _code_sums((2, e, f, m), ex.handle, e * f + m + ps)
    acc = _layout(codes, layout)
    got, pooled = _tail_pool_first(ex, acc)
    want = _stepwise_tail(codes, ex.handle.deq, None, ps)
    assert got.dtype == np.float64 and got.flags.c_contiguous
    assert got.tobytes() == want.tobytes()
    assert pooled == (codes.size if ps else 0)
    assert (got == 0).any() and (got > 0).any()
    again = ex._tail_np(acc)
    assert again is not got and again.tobytes() == got.tobytes()


@pytest.mark.parametrize("bad", [0.0, -1e-3])
def test_tail_fallback_order_without_positive_deq(bad):
    """A handle with one channel's ``deq`` not positive keeps the
    dequantize-then-pool order and still matches the stepwise tail
    bitwise; the pool-first counter skips it, and it is decided anew
    when the executor's handle is replaced."""
    ex = _tail_executor(16, 2)
    codes = _code_sums((2, 8, 6, 16), ex.handle, 3)
    _, pooled = _tail_pool_first(ex, codes.astype(np.int32))
    assert pooled == codes.size
    deq = ex.handle.deq.copy()
    deq[5] = bad
    ex.handle = dataclasses.replace(ex.handle, deq=deq)
    for dt in (np.int32, np.float32, np.float64):
        got, pooled = _tail_pool_first(ex, codes.astype(dt))
        assert got.tobytes() == _stepwise_tail(
            codes, deq, None, 2).tobytes()
        assert pooled == 0


def test_tail_exact_engine_with_bias_keeps_order():
    """The exact engine's float64 outputs, signed zeros among them, take
    bias, ReLU, then the pool, as the stepwise tail does; nothing is
    counted as pooled first."""
    r = np.random.default_rng(8)
    bias = r.standard_normal(12)
    ex = _tail_executor(12, 2, engine=EXACT_ENGINE, bias=bias)
    acc = r.standard_normal((3, 6, 10, 12))
    acc[r.random(acc.shape) < 0.2] = -0.0
    acc[..., 0] = -bias[0]
    got, pooled = _tail_pool_first(ex, acc)
    assert got.tobytes() == _stepwise_tail(acc, None, bias, 2).tobytes()
    assert pooled == 0


#: ``bench/tests/test_bench_vgg16.py``'s narrow VGG-16: the published
#: plan at a sixteenth of its widths, 128x128 frames (two strip layers,
#: one of them pooled; five 2x2 pools)
VGG16_NARROW = [4, 4, "M", 8, 8, "M", 16, 16, 16, "M",
                32, 32, 32, "M", 32, 32, 32, "M"]


def test_narrow_vgg16_jit_serving_pools_first_and_matches_pertile(
        monkeypatch):
    """On the narrow VGG-16, jit serving on the Pallas engine counts every
    pooled layer's pre-pool elements as pooled first, and its logits
    equal the per-tile ``fused=False`` path's bitwise."""
    from repro.runtime.serve_loop import build_stream_sim, serve_stream

    cnn = _vgg("vgg16-narrow", VGG16_NARROW, "imagenet", 128,
               (256, 256, 10))
    r = np.random.default_rng(16)
    params = _int_params(cnn, r)
    calib = r.standard_normal((2, 128, 128, 3))
    x = r.standard_normal((2, 128, 128, 3))

    def serve(engine, **kw):
        sim = build_stream_sim(cnn, params, engine=engine,
                               calib_images=calib, **kw)
        with Profiler() as prof:
            rep = serve_stream(sim, x, batch_window=2)
        return rep.logits, prof.counts.get("te.tail_pool_first", 0)

    jit, pooled = serve("pallas", trace_jit=True)
    pre_pool = sum(
        len(x) * (l.h + 2 * l.p - l.k + l.s) // l.s
        * ((l.w + 2 * l.p - l.k + l.s) // l.s) * l.m
        for l in cnn.conv_layers if l.pool_s)
    assert sum(1 for l in cnn.conv_layers if l.pool_s) == 5
    assert pooled == pre_pool

    init = TraceExecutor.__init__
    monkeypatch.setattr(
        TraceExecutor, "__init__",
        lambda self, *a, **kw: init(self, *a, fused=False, **kw))
    pertile, _ = serve("cim")
    assert np.abs(jit).max() > 0
    assert jit.tobytes() == pertile.tobytes()
