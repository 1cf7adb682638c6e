"""Hypothesis property tests (ISA roundtrip, random conv shapes, CIM
circuit equivalence).

Kept in their own module behind ``pytest.importorskip`` so a missing
``hypothesis`` package (it is an optional dev dependency, see
``requirements.txt``) skips these instead of hard-failing collection of
the deterministic suites in ``test_domino_core.py`` / ``test_kernels.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.cim import CIMSpec  # noqa: E402
from repro.core.instructions import Instruction, Opcode  # noqa: E402
from repro.core.schedule import compile_conv_block  # noqa: E402
from repro.core.simulator import BlockSimulator  # noqa: E402
from repro.kernels.ref import (  # noqa: E402
    cim_matmul_bitplane_ref,
    cim_matmul_ref,
    int8_matmul_exact_ref,
)


# ---------------------------------------------------------------------------
# ISA
# ---------------------------------------------------------------------------


@settings(max_examples=200, deadline=None)
@given(
    opc=st.sampled_from([Opcode.C, Opcode.M]),
    rx=st.integers(0, 31),
    func=st.integers(0, 63),
    tx=st.integers(0, 15),
)
def test_instruction_roundtrip(opc, rx, func, tx):
    ins = Instruction(opc, rx=rx, func=func, tx=tx)
    word = ins.encode()
    assert 0 <= word < 2 ** 16  # 16-bit ISA (Tab. 2)
    back = Instruction.decode(word)
    assert back == ins


# ---------------------------------------------------------------------------
# Conv on the move, random shapes
# ---------------------------------------------------------------------------


def _int_data(key, shape, lo=-4, hi=5):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(key), shape, lo, hi), np.float64
    )


def _conv_oracle(ifm, w, b, stride, pad, relu=True):
    out = jax.lax.conv_general_dilated(
        jnp.asarray(ifm, jnp.float64)[None],
        jnp.asarray(w, jnp.float64),
        window_strides=(stride, stride),
        padding=[(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )[0]
    out = out + jnp.asarray(b, jnp.float64)
    if relu:
        out = jnp.maximum(out, 0)
    return np.asarray(out)


@settings(max_examples=10, deadline=None)
@given(
    h=st.integers(6, 12),
    c=st.integers(1, 4),
    m=st.integers(1, 4),
    seed=st.integers(0, 1000),
)
def test_conv_property_random_shapes(h, c, m, seed):
    w, k, stride, pad = h + 2, 3, 1, 1
    ifm = _int_data(seed, (h, w, c))
    wts = _int_data(seed + 1, (k, k, c, m))
    b = _int_data(seed + 2, (m,))
    sched = compile_conv_block("r", h, w, c, m, k, stride, pad)
    got = BlockSimulator(sched, wts, bias=b).run(ifm)
    np.testing.assert_array_equal(got, _conv_oracle(ifm, wts, b, stride, pad))


# ---------------------------------------------------------------------------
# CIM circuit equivalence (paper §4.5 numerics)
# ---------------------------------------------------------------------------


def _rand_int8(key, shape):
    return jax.random.randint(key, shape, -128, 128, dtype=jnp.int8)


@settings(max_examples=25, deadline=None)
@given(
    m=st.integers(1, 8),
    n=st.integers(1, 8),
    subs=st.integers(1, 3),
    seed=st.integers(0, 2**31 - 1),
)
def test_bitplane_circuit_equivalence(m, n, subs, seed):
    spec = CIMSpec(n_c=32, adc_bits=8, gain=4.0)
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    k_dim = subs * spec.n_c
    xq = _rand_int8(k1, (m, k_dim))
    wq = _rand_int8(k2, (k_dim, n))
    a = cim_matmul_bitplane_ref(xq, wq, spec)
    b = cim_matmul_ref(xq, wq, spec)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_lossless_adc_recovers_exact_matmul(seed):
    """With adc_step <= 1 the pipeline must equal the exact int8 matmul."""
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    xq = _rand_int8(k1, (4, 64))
    wq = _rand_int8(k2, (64, 4))
    # n_c=64: full_scale = 64*127*127; make ADC wide enough to be lossless
    spec = CIMSpec(n_c=64, adc_bits=22, gain=1.0)
    assert spec.lossless
    got = cim_matmul_ref(xq, wq, spec)
    want = int8_matmul_exact_ref(xq, wq)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0.5)


@settings(max_examples=8, deadline=None)
@given(
    m=st.integers(1, 17),
    k=st.integers(1, 300),
    n=st.integers(1, 140),
    seed=st.integers(0, 2**31 - 1),
)
def test_pallas_jnp_codes_bitwise_property(m, k, n, seed):
    """Property (the ragged satellite): for *any* shape — K not a
    multiple of n_c, M/N off the block grid, B=1 — the Pallas kernel and
    the jnp fast path produce bitwise-identical step-scaled outputs
    (hence identical ADC codes: the scaling is one shared f32 multiply
    of an exactly-represented integer code sum)."""
    from repro.core.cim import cim_matmul
    from repro.kernels.cim_matmul import cim_matmul_pallas

    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    xq = _rand_int8(k1, (m, k))
    wq = _rand_int8(k2, (k, n))
    spec = CIMSpec(n_c=96, adc_bits=8, gain=7.0)
    out_jnp = np.asarray(cim_matmul(xq, wq, spec))
    out_pl = np.asarray(cim_matmul_pallas(xq, wq, spec))
    assert out_jnp.tobytes() == out_pl.tobytes()


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), gain=st.floats(1.0, 64.0))
def test_adc_codes_bounded(seed, gain):
    """Property: every accumulated output is bounded by n_sub * q_max * step."""
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    xq = _rand_int8(k1, (8, 512))
    wq = _rand_int8(k2, (512, 8))
    spec = CIMSpec(n_c=128, adc_bits=8, gain=gain)
    out = np.asarray(cim_matmul_ref(xq, wq, spec))
    n_sub = 512 // 128
    bound = n_sub * (spec.q_max + 1) * spec.adc_step
    assert np.all(np.abs(out) <= bound + 1e-3)


@settings(max_examples=15, deadline=None)
@given(
    h=st.integers(5, 9),
    w=st.integers(5, 9),
    c=st.integers(1, 9),
    m=st.integers(1, 8),
    k=st.sampled_from([1, 3]),
    n_c=st.sampled_from([32, 64, 96]),
    gain=st.floats(2.0, 64.0),
    seed=st.integers(0, 2**31 - 1),
)
def test_fused_trace_codes_bitwise_property(h, w, c, m, k, n_c, gain, seed):
    """Property (the fused-lowering satellite): for *any* conv geometry
    and subarray width the batch-of-tiles trace lowering reproduces the
    per-tile interpreter fold's ADC codes bit-for-bit — the codes are
    small integers exact in f64, so the fused association order cannot
    change a single bit."""
    from repro.core.engine import CIMEngine
    from repro.core.trace import TraceExecutor

    spec = CIMSpec(n_c=n_c, adc_bits=8, gain=gain)
    rng = np.random.default_rng(seed)
    ifm = rng.standard_normal((1, h, w, c))
    wts = rng.standard_normal((k, k, c, m))
    sched = compile_conv_block("prop", h, w, c, m, k, 1, k // 2)
    eng = CIMEngine(spec).set_layer(
        sched.layer_name, a_scale=float(np.abs(ifm).max()) / 127)
    interp = BlockSimulator(sched, wts, engine=eng).run(ifm)
    fused = TraceExecutor(sched, wts, engine=eng).run(ifm)
    pertile = TraceExecutor(sched, wts, engine=eng, fused=False).run(ifm)
    assert interp.tobytes() == fused.tobytes()
    assert interp.tobytes() == pertile.tobytes()
