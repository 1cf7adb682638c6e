"""Trace-compiled fast path: the vectorized executor must be *bitwise*
equal to the per-cycle interpreter — OFM values, ``SimCounters``,
``TrafficCounters`` and per-link mesh traffic — for every conv geometry
appearing in any ``CNN_BENCHMARKS`` mapping plan (incl. pool strides and
C > N_c channel-split chains), batched and unbatched; and the
whole-network trace backend
reproduces the interpreter run and the jax reference exactly, now
including ResNet-18's residual wiring."""
import dataclasses

import numpy as np
import pytest
from conftest import int_params as _int_params

from repro.configs.cnn import CNN_BENCHMARKS, ConvLayer
from repro.core.mapping import plan_network
from repro.core.network import NetworkSimulator
from repro.core.schedule import compile_conv_block
from repro.core.simulator import BlockSimulator
from repro.core.trace import TraceExecutor, compile_trace
from repro.core.transport import RESIDUAL


def _int_data(seed, shape, lo=-4, hi=5):
    return np.random.default_rng(seed).integers(lo, hi, shape).astype(
        np.float64)


def _assert_block_equal(sched, wts, bias, ifm):
    """Run interpreter and trace on identical inputs; everything the
    simulator reports must agree bitwise."""
    interp = BlockSimulator(sched, wts, bias=bias)
    out_i = interp.run(ifm)
    trace = TraceExecutor(sched, wts, bias=bias)
    out_t = trace.run(ifm)
    assert out_i.tobytes() == out_t.tobytes(), "OFM not bitwise-equal"
    assert out_i.shape == out_t.shape
    assert dataclasses.asdict(interp.counters) == \
        dataclasses.asdict(trace.counters)
    assert interp.transport.counters.byte_hops == \
        trace.transport.counters.byte_hops
    assert interp.transport.counters.packets == \
        trace.transport.counters.packets
    assert interp.transport.counters.hops == trace.transport.counters.hops
    assert interp.transport.noc.link_traffic == \
        trace.transport.noc.link_traffic
    return out_t


# ---------------------------------------------------------------------------
# Block-level equivalence across every benchmark conv geometry
# ---------------------------------------------------------------------------


def _proxy_geometries():
    """One shrunk-but-geometry-faithful proxy per distinct conv shape
    (k, stride, pad, pack, c_splits) appearing in any benchmark plan."""
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


@pytest.mark.parametrize("sig,config", _proxy_geometries())
def test_trace_bitwise_equals_interp_all_configs(sig, config):
    k, stride, pad, pack, c_splits = sig
    c_in = max(2 * c_splits, pack)  # keep every split tile non-empty
    c_out, h = 3, 8
    w = h + 1
    ifm = _int_data(k + stride, (h, w, c_in))
    wts = _int_data(2 * k, (k, k, c_in, c_out))
    bias = _int_data(3 * k, (c_out,))
    sched = compile_conv_block(f"proxy-{config}", h, w, c_in, c_out, k,
                               stride, pad, pack=pack, c_splits=c_splits)
    _assert_block_equal(sched, wts, bias, ifm)


@pytest.mark.parametrize("pool,hw", [(2, 8), (3, 9), (4, 8)])
def test_trace_pool_stride_bitwise(pool, hw):
    h = w = hw
    c, m, k = 2, 3, 3
    ifm = _int_data(7 + pool, (h, w, c))
    wts = _int_data(8 + pool, (k, k, c, m))
    sched = compile_conv_block("p", h, w, c, m, k, 1, 1,
                               pool_k=pool, pool_s=pool)
    _assert_block_equal(sched, wts, np.zeros(m), ifm)


def test_trace_channel_split_chain_bitwise():
    """C > N_c: the group extends east with split tiles, each MACing its
    own channel slice — the segment fold must still match exactly."""
    h = w = 8
    c, m, k, c_splits = 12, 4, 3, 4
    ifm = _int_data(21, (h, w, c))
    wts = _int_data(22, (k, k, c, m))
    sched = compile_conv_block("csplit", h, w, c, m, k, 1, 1,
                               pack=1, c_splits=c_splits)
    assert sched.group_size == k * c_splits  # pack=1: k tap tiles x splits
    _assert_block_equal(sched, wts, np.zeros(m), ifm)


def test_trace_batched_bitwise_and_counters_per_inference():
    h = w = 8
    c, m, k = 3, 4, 3
    wts = _int_data(11, (k, k, c, m))
    bias = _int_data(12, (m,))
    ifms = _int_data(13, (8, h, w, c))
    sched = compile_conv_block("b8", h, w, c, m, k, 1, 1, pool_k=2, pool_s=2)
    out_b = _assert_block_equal(sched, wts, bias, ifms)
    for i in range(8):
        one = TraceExecutor(sched, wts, bias=bias).run(ifms[i])
        np.testing.assert_array_equal(out_b[i], one)
    # counters don't scale with B (one routed packet carries the batch)
    t1 = TraceExecutor(sched, wts, bias=bias)
    t1.run(ifms[:1])
    t8 = TraceExecutor(sched, wts, bias=bias)
    t8.run(ifms)
    assert t1.counters == t8.counters
    assert t1.transport.counters.byte_hops == t8.transport.counters.byte_hops


def test_trace_float_data_still_bitwise():
    """Bitwise equality is an association-order property, not an
    exact-integer one: it must hold for arbitrary float inputs too."""
    rng = np.random.default_rng(42)
    h = w = 9
    c, m, k = 5, 4, 3
    ifm = rng.standard_normal((2, h, w, c))
    wts = rng.standard_normal((k, k, c, m))
    sched = compile_conv_block("float", h, w, c, m, k, 1, 1, pack=3)
    _assert_block_equal(sched, wts, rng.standard_normal(m), ifm)


def test_trace_plan_shapes():
    sched = compile_conv_block("plan", 8, 8, 4, 3, 3, 1, 1, pack=2)
    plan = compile_trace(sched)
    assert plan.fires == sched.e * sched.f
    assert len(plan.tiles) == sched.chain_len
    assert len(plan.segments) == sched.k
    for tt in plan.tiles:
        assert tt.gather.shape == (tt.pack, plan.fires)
        assert tt.row_mask.sum() == sched.e
        assert tt.phase_mask.sum() == sched.f
        # every gathered index addresses the padded raster stream
        assert tt.gather.min() >= 0
        assert tt.gather.max() < plan.n_pix


# ---------------------------------------------------------------------------
# Whole-network: backend switch + residual wiring
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def vgg11_both_backends():
    rng = np.random.default_rng(0)
    cnn = CNN_BENCHMARKS["vgg11-cifar10"]()
    params = _int_params(cnn, rng)
    x = rng.integers(0, 2, (2, 32, 32, 3)).astype(np.float64)
    res_i = NetworkSimulator(cnn, params).run(x)
    sim_t = NetworkSimulator(cnn, params, backend="trace")
    res_t = sim_t.run(x)
    return res_i, res_t, sim_t


def test_network_trace_backend_bitwise_equals_interp(vgg11_both_backends):
    res_i, res_t, _ = vgg11_both_backends
    assert res_i.logits.tobytes() == res_t.logits.tobytes()
    assert res_i.counters == res_t.counters
    assert res_i.traffic.byte_hops == res_t.traffic.byte_hops
    assert res_i.traffic.packets == res_t.traffic.packets
    assert res_i.traffic.hops == res_t.traffic.hops


def test_network_trace_rerun_is_stable(vgg11_both_backends):
    """Executors are cached across runs; a second run must reproduce the
    first (fresh counters, same logits)."""
    res_i, res_t, sim_t = vgg11_both_backends
    rng = np.random.default_rng(0)
    cnn = CNN_BENCHMARKS["vgg11-cifar10"]()
    _int_params(cnn, rng)  # advance rng to the image draw
    x = rng.integers(0, 2, (2, 32, 32, 3)).astype(np.float64)
    again = sim_t.run(x)
    assert again.logits.tobytes() == res_t.logits.tobytes()
    assert again.counters == res_t.counters


def test_network_invalid_backend_rejected():
    cnn = CNN_BENCHMARKS["vgg11-cifar10"]()
    rng = np.random.default_rng(3)
    with pytest.raises(ValueError):
        NetworkSimulator(cnn, _int_params(cnn, rng), backend="warp")


def test_network_nonconforming_residual_rejected():
    """Only the jax reference's `*_a`/`residual_from`/`*_sc` convention
    is wired; a shortcut pointing anywhere else must fail loudly rather
    than silently reuse a stale saved input."""
    from repro.configs.cnn import CNNConfig

    layers = (
        ConvLayer("c0", 8, 8, 3, 4),
        ConvLayer("c1", 8, 8, 4, 4, residual_from="c0"),  # c0 is not *_a
    )
    bad = CNNConfig("badres", "cifar10", 8, layers)
    rng = np.random.default_rng(9)
    with pytest.raises(NotImplementedError):
        NetworkSimulator(bad, _int_params(bad, rng))


def _jax_reference(cnn, params, x):
    import jax
    import jax.numpy as jnp

    from repro.models.cnn import cnn_forward

    with jax.enable_x64(True):
        p64 = {k: jnp.asarray(v, jnp.float64) for k, v in params.items()}
        return np.asarray(cnn_forward(p64, jnp.asarray(x, jnp.float64), cnn))


def test_resnet18_trace_runs_end_to_end_matching_jax():
    """Residual wiring: identity and projection (``*_sc``) shortcuts,
    post-add ReLU, global average pool — trace backend vs the jax
    forward.  Early layers are exact (small integers); by mid-network
    the 17-conv stack exceeds float64's exact-integer range, so the
    network-level check is tight-allclose while the bitwise claim is
    covered trace-vs-interp below."""
    cnn = CNN_BENCHMARKS["resnet18-cifar10"]()
    rng = np.random.default_rng(1)
    params = _int_params(cnn, rng)
    x = rng.integers(0, 2, (2, 32, 32, 3)).astype(np.float64)
    res = NetworkSimulator(cnn, params, backend="trace").run(x)
    ref = _jax_reference(cnn, params, x)
    assert res.logits.shape == ref.shape == (2, 10)
    np.testing.assert_allclose(res.logits, ref, rtol=1e-9)
    # the shortcut streams are routed traffic now
    assert res.traffic.byte_hops[RESIDUAL] > 0
    assert res.traffic.packets[RESIDUAL] > 0


def test_resnet18_small_slice_exact_vs_jax():
    """On a shallow residual slice every value stays exactly
    representable, so the trace backend matches jax bitwise — identity
    shortcut, projection shortcut and GAP+FC all covered."""
    from repro.configs.cnn import CNNConfig, FCLayer, _res_block

    layers = []
    h, w, c = _res_block(layers, "s0b0", 8, 8, 4, 4, 1, False)  # identity
    h, w, c = _res_block(layers, "s1b0", h, w, c, 6, 2, False)  # projection
    layers.append(FCLayer("fc", c, 5))
    mini = CNNConfig("resnet-mini", "cifar10", 8, tuple(layers))
    rng = np.random.default_rng(7)
    params = _int_params(mini, rng)
    x = rng.integers(0, 2, (2, 8, 8, 4)).astype(np.float64)
    res_t = NetworkSimulator(mini, params, backend="trace").run(x)
    res_i = NetworkSimulator(mini, params).run(x)
    ref = _jax_reference(mini, params, x)
    np.testing.assert_array_equal(res_t.logits, ref)
    assert res_t.logits.tobytes() == res_i.logits.tobytes()
    assert res_t.counters == res_i.counters
    assert res_t.traffic.byte_hops == res_i.traffic.byte_hops


@pytest.mark.slow
def test_resnet18_trace_bitwise_equals_interp():
    """The full ResNet-18 run: trace == interp bitwise even where the
    arithmetic is inexact (association orders match by construction;
    ``gemm_rows`` makes this batch-size independent — the B=1 flavor is
    covered in tests/test_streaming.py)."""
    cnn = CNN_BENCHMARKS["resnet18-cifar10"]()
    rng = np.random.default_rng(1)
    params = _int_params(cnn, rng)
    x = rng.integers(0, 2, (2, 32, 32, 3)).astype(np.float64)
    res_i = NetworkSimulator(cnn, params).run(x)
    res_t = NetworkSimulator(cnn, params, backend="trace").run(x)
    assert res_i.logits.tobytes() == res_t.logits.tobytes()
    assert res_i.counters == res_t.counters
    assert res_i.traffic.byte_hops == res_t.traffic.byte_hops
