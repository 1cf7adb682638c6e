"""Whole-network simulation: a full CNN_BENCHMARKS model executes
end-to-end from compiled instruction tables over the placed, routed NoC
and matches the jax reference forward pass exactly; routed CHAIN and
OFM traffic counters match the analytic NoC/energy counts exactly
(GROUP totals are per-copy placement-dependent — the functional sim
drives copy 0 while the energy model accounts all duplicated copies;
per-chain GROUP equality is covered in test_transport.py)."""
import numpy as np
import pytest

from repro.configs.cnn import CNN_BENCHMARKS, ConvLayer
from repro.core.network import NetworkSimulator
from repro.core.noc import inter_block_byte_hops
from repro.core.transport import CHAIN, OFM, PSUM_BYTES


def _int_params(cnn, rng):
    """Small integer weights keep every intermediate exactly representable
    in float64 through the whole network (sim vs jax bitwise-comparable)."""
    params = {}
    for l in cnn.layers:
        if isinstance(l, ConvLayer):
            params[l.name] = rng.integers(
                -1, 2, (l.k, l.k, l.c, l.m)).astype(np.float64)
        else:
            params[l.name] = rng.integers(
                -1, 2, (l.c_in, l.c_out)).astype(np.float64)
    return params


def _jax_reference(cnn, params, x):
    import jax
    import jax.numpy as jnp

    from repro.models.cnn import cnn_forward

    with jax.enable_x64(True):
        p64 = {k: jnp.asarray(v, jnp.float64) for k, v in params.items()}
        return np.asarray(cnn_forward(p64, jnp.asarray(x, jnp.float64), cnn))


@pytest.fixture(scope="module")
def vgg11_run():
    rng = np.random.default_rng(0)
    cnn = CNN_BENCHMARKS["vgg11-cifar10"]()
    params = _int_params(cnn, rng)
    x = rng.integers(0, 2, (2, 32, 32, 3)).astype(np.float64)
    sim = NetworkSimulator(cnn, params)
    res = sim.run(x)
    return cnn, params, x, sim, res


def test_vgg11_matches_jax_reference_exactly(vgg11_run):
    cnn, params, x, sim, res = vgg11_run
    ref = _jax_reference(cnn, params, x)
    assert res.logits.shape == ref.shape == (2, 10)
    np.testing.assert_array_equal(res.logits, ref)


def test_vgg11_ofm_traffic_matches_analytic(vgg11_run):
    """OFM tail->head streams are accounted through the same placement +
    route as noc.inter_block_byte_hops — equal by construction."""
    _, _, _, sim, res = vgg11_run
    assert res.traffic.byte_hops[OFM] == inter_block_byte_hops(sim.plan)


def test_vgg11_chain_traffic_matches_energy_model(vgg11_run):
    """Chain psum byte-hops summed over the network equal the energy
    model's per-layer counts (chain links are snake-adjacent: 1 hop)."""
    _, _, _, sim, res = vgg11_run
    expect = 0
    for lp in sim.plan.layers:
        if lp.kind != "conv":
            continue
        group_size = lp.chain_len // lp.k
        expect += (lp.out_pixels * lp.k * (group_size - 1)
                   * lp.c_out * PSUM_BYTES)
    assert res.traffic.byte_hops[CHAIN] == expect


def test_vgg11_batched_matches_single(vgg11_run):
    cnn, params, x, sim, res = vgg11_run
    for i in range(x.shape[0]):
        single = NetworkSimulator(cnn, params).run(x[i])
        np.testing.assert_array_equal(res.logits[i], single.logits)


def test_resnet_constructs_with_residual_wiring():
    """Residual shortcuts are wired now: the simulator builds, the
    residual-target and ``*_sc`` blocks compile with a bare tail (the
    ReLU fires after the shortcut add), and plain layers keep theirs.
    End-to-end ResNet runs live in tests/test_trace.py."""
    cnn = CNN_BENCHMARKS["resnet18-cifar10"]()
    rng = np.random.default_rng(1)
    sim = NetworkSimulator(cnn, _int_params(cnn, rng))
    for layer, sched in zip(cnn.layers, sim.schedules):
        if not isinstance(layer, ConvLayer):
            continue
        bare = layer.residual_from is not None or layer.name.endswith("_sc")
        assert sched.tail.activation == (None if bare else "relu"), layer.name


def test_imagenet_width_compiles_as_strips():
    """224-wide layers exceed the 128-entry schedule table (Tab. 3): a
    single schedule still refuses to compile, and the network simulator
    width-tiles such layers instead (per-strip tables, same chain)."""
    from repro.core.instructions import TABLE_CAPACITY
    from repro.core.schedule import compile_conv_block

    with pytest.raises(ValueError):
        compile_conv_block("too-wide", 224, 224, 3, 64, 3, 1, 1)
    cnn = CNN_BENCHMARKS["vgg16-imagenet"]()
    rng = np.random.default_rng(2)
    sim = NetworkSimulator(cnn, _int_params(cnn, rng))
    assert sim._strips  # every 224/112-wide layer compiled as strips
    for li, strips in sim._strips.items():
        layer = cnn.layers[li]
        assert layer.w + 2 * layer.p > TABLE_CAPACITY
        assert sim.schedules[li] is None
        assert all(s.sched.wp <= TABLE_CAPACITY for s in strips)
        # strips tile the output width exactly and in order
        f_total = (layer.w + 2 * layer.p - layer.k + layer.s) // layer.s
        assert strips[0].f0 == 0 and strips[-1].f1 == f_total
        for a, b in zip(strips, strips[1:]):
            assert a.f1 == b.f0


def test_width_striping_bitwise_equals_whole_block():
    """A block run as width strips (tiny capacity to force several
    strips) produces the whole block's exact OFM, pooling included."""
    from repro.core.schedule import compile_conv_block, compile_conv_strips
    from repro.core.simulator import BlockSimulator

    rng = np.random.default_rng(3)
    h, w, c, m, k, s, p = 9, 21, 2, 3, 3, 2, 1
    ifm = rng.integers(-4, 5, (2, h, w, c)).astype(np.float64)
    wts = rng.integers(-4, 5, (k, k, c, m)).astype(np.float64)

    whole = BlockSimulator(
        compile_conv_block("whole", h, w, c, m, k, s, p), wts).run(ifm)

    strips = compile_conv_strips("striped", h, w, c, m, k, s, p,
                                 capacity=9)
    assert len(strips) > 2
    padded = np.zeros((2, h + 2 * p, w + 2 * p, c))
    padded[:, p:p + h, p:p + w] = ifm
    parts = [BlockSimulator(st.sched, wts).run(padded[:, :, st.lo:st.hi])
             for st in strips]
    np.testing.assert_array_equal(np.concatenate(parts, axis=2), whole)


def test_width_striping_pooled_block():
    """Striping composes with the tail max-pool (strip cuts land on
    pool-stride boundaries)."""
    from repro.core.schedule import compile_conv_block, compile_conv_strips
    from repro.core.simulator import BlockSimulator

    rng = np.random.default_rng(4)
    h, w, c, m = 8, 16, 2, 3
    ifm = rng.integers(-4, 5, (h, w, c)).astype(np.float64)
    wts = rng.integers(-4, 5, (3, 3, c, m)).astype(np.float64)
    whole = BlockSimulator(
        compile_conv_block("w", h, w, c, m, 3, 1, 1, pool_k=2, pool_s=2),
        wts).run(ifm)
    strips = compile_conv_strips("s", h, w, c, m, 3, 1, 1,
                                 pool_k=2, pool_s=2, capacity=10)
    assert len(strips) > 1
    padded = np.zeros((h + 2, w + 2, c))
    padded[1:1 + h, 1:1 + w] = ifm
    parts = [BlockSimulator(st.sched, wts).run(padded[:, st.lo:st.hi])
             for st in strips]
    np.testing.assert_array_equal(np.concatenate(parts, axis=1), whole)


@pytest.mark.slow
def test_resnet50_end_to_end_matches_jax():
    """ResNet-50 (ImageNet, 224x224) through the whole pipeline: the
    width-striped stem, bottleneck residuals (identity + projection
    shortcuts), global average pooling and the FC head — matching the
    jax reference forward (allclose: activations overflow exact f64
    integer range through 53 layers; B=2 keeps gemm kernels uniform)."""
    rng = np.random.default_rng(5)
    cnn = CNN_BENCHMARKS["resnet50-imagenet"]()
    params = _int_params(cnn, rng)
    x = rng.integers(0, 2, (2, 224, 224, 3)).astype(np.float64)
    sim = NetworkSimulator(cnn, params, dup_cap=128, backend="trace")
    assert 0 in sim._strips and len(sim._strips) == 1  # the stem only
    res = sim.run(x)
    ref = _jax_reference(cnn, params, x)
    assert res.logits.shape == ref.shape == (2, 1000)
    np.testing.assert_allclose(res.logits, ref, rtol=1e-9)
    # bottleneck shortcut streams really moved over the mesh
    assert res.traffic.byte_hops["residual"] > 0
    assert res.traffic.byte_hops["ofm"] > 0