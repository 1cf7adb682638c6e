"""Kernel vs oracle: shape sweeps + circuit-equivalence property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cim import (
    CIMSpec,
    calibrate_gain,
    cim_matmul,
    cim_linear_reference,
    quantize_symmetric,
)
from repro.kernels.cim_matmul import cim_matmul_pallas
from repro.kernels.ref import (
    cim_matmul_bitplane_ref,
    cim_matmul_ref,
    int8_matmul_exact_ref,
)

jax.config.update("jax_enable_x64", False)


def _rand_int8(key, shape):
    return jax.random.randint(key, shape, -128, 128, dtype=jnp.int8)


SHAPES = [
    (8, 256, 16),
    (16, 256, 128),
    (32, 512, 64),
    (128, 1024, 256),
    (1, 300, 7),      # ragged: K not a multiple of n_c, tiny N
    (65, 700, 130),   # everything ragged
    (256, 2048, 512),
]


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_pallas_matches_ref(m, k, n):
    key = jax.random.PRNGKey(m * 7 + k * 3 + n)
    k1, k2 = jax.random.split(key)
    xq = _rand_int8(k1, (m, k))
    wq = _rand_int8(k2, (k, n))
    spec = CIMSpec(n_c=256, adc_bits=8, gain=16.0)
    ref = cim_matmul_ref(xq, wq, spec)
    out = cim_matmul_pallas(xq, wq, spec)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=0, atol=0)


@pytest.mark.parametrize("block_m,block_n", [(8, 128), (64, 128), (256, 256), (512, 512)])
def test_pallas_block_shapes(block_m, block_n):
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    xq = _rand_int8(k1, (96, 768))
    wq = _rand_int8(k2, (768, 192))
    spec = CIMSpec()
    ref = cim_matmul_ref(xq, wq, spec)
    out = cim_matmul_pallas(xq, wq, spec, block_m=block_m, block_n=block_n)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("n_c", [64, 128, 256, 512])
@pytest.mark.parametrize("adc_bits", [6, 8, 12])
def test_pallas_spec_sweep(n_c, adc_bits):
    key = jax.random.PRNGKey(n_c + adc_bits)
    k1, k2 = jax.random.split(key)
    xq = _rand_int8(k1, (32, 2 * n_c + 17))
    wq = _rand_int8(k2, (2 * n_c + 17, 96))
    spec = CIMSpec(n_c=n_c, adc_bits=adc_bits, gain=8.0)
    ref = cim_matmul_ref(xq, wq, spec)
    out = cim_matmul_pallas(xq, wq, spec)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_jnp_fast_path_matches_ref():
    """core.cim.cim_matmul (the layer fast path) == kernel oracle."""
    key = jax.random.PRNGKey(3)
    k1, k2 = jax.random.split(key)
    xq = _rand_int8(k1, (24, 600))
    wq = _rand_int8(k2, (600, 48))
    spec = CIMSpec()
    np.testing.assert_array_equal(
        np.asarray(cim_matmul(xq, wq, spec)),
        np.asarray(cim_matmul_ref(xq, wq, spec)),
    )


# The padding edge cases the round-number SHAPES sweep never exercises:
# K not a multiple of n_c, M/N not multiples of the block size, B=1.  The
# jnp fast path and the Pallas kernel must emit *bitwise-identical ADC
# codes* on all of them (the engine layer accumulates these digitally —
# a single differing code would break cim-vs-pallas bitwise equality).
RAGGED = [
    (1, 300, 7),       # B=1, K % n_c != 0, tiny N
    (1, 129, 1),       # single row, single column, one ragged subarray
    (5, 257, 10),      # K just over one subarray
    (3, 511, 129),     # N just over the 128-lane block
    (9, 1000, 131),    # everything off-size
]


def _jnp_adc_codes(xq, wq, spec):
    """Raw digitally-accumulated ADC codes of the jnp reference path."""
    from repro.core.cim import adc_quantize

    k = wq.shape[0]
    pad = (-k) % spec.n_c
    if pad:
        xq = jnp.pad(xq, ((0, 0), (0, pad)))
        wq = jnp.pad(wq, ((0, pad), (0, 0)))
    n_sub = (k + pad) // spec.n_c
    xs = xq.reshape(xq.shape[0], n_sub, spec.n_c).astype(jnp.int32)
    ws = wq.reshape(n_sub, spec.n_c, -1).astype(jnp.int32)
    d = jnp.einsum("msk,skn->msn", xs, ws)
    return jnp.sum(adc_quantize(d, spec), axis=1)


@pytest.mark.parametrize("m,k,n", RAGGED)
def test_pallas_codes_bitwise_vs_jnp_ragged(m, k, n):
    key = jax.random.PRNGKey(m * 31 + k * 5 + n)
    k1, k2 = jax.random.split(key)
    xq = _rand_int8(k1, (m, k))
    wq = _rand_int8(k2, (k, n))
    spec = CIMSpec(n_c=128, adc_bits=6, gain=5.0)
    codes_ref = np.asarray(_jnp_adc_codes(xq, wq, spec), np.int32)
    codes_pl = np.asarray(
        cim_matmul_pallas(xq, wq, spec, emit_codes=True))
    assert np.all(codes_pl == np.round(codes_pl))  # integers in f32
    assert codes_pl.astype(np.int32).tobytes() == codes_ref.tobytes()
    # and the step-scaled outputs are bitwise-equal f32 too
    out_jnp = np.asarray(cim_matmul(xq, wq, spec))
    out_pl = np.asarray(cim_matmul_pallas(xq, wq, spec))
    assert out_jnp.tobytes() == out_pl.tobytes()


def test_cim_linear_accuracy():
    """End-to-end float linear through CIM keeps reasonable fidelity when
    the gain is calibrated (the paper's accuracy rows: ~1-2% drop)."""
    key = jax.random.PRNGKey(11)
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (64, 512), jnp.float32)
    w = jax.random.normal(k2, (512, 256), jnp.float32) / 512**0.5
    want = x @ w

    def rel_err(adc_bits):
        spec = CIMSpec(n_c=256, adc_bits=adc_bits)
        g = calibrate_gain(x, w, spec)
        spec = CIMSpec(n_c=256, adc_bits=adc_bits, gain=g)
        got = cim_linear_reference(x, w, spec)
        return float(
            np.linalg.norm(np.asarray(got - want)) / np.linalg.norm(np.asarray(want))
        )

    e8, e10, e12 = rel_err(8), rel_err(10), rel_err(12)
    # 8-bit SAR ADC (paper config): small but nonzero error — this is the
    # accuracy drop Tab. 4 reports (VGG-11 91.51% fp -> 89.85% on Domino)
    assert e8 < 0.03, f"8-bit relative error {e8:.4f} too high"
    # error falls with converter resolution toward the int8-quantization
    # floor (~1.2% on this data)
    assert e12 <= e10 <= e8, (e8, e10, e12)
    assert e12 < 0.015


def test_quantize_roundtrip():
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (128, 64)) * 3.0
    q, s = quantize_symmetric(x, 8)
    back = np.asarray(q, np.float32) * np.asarray(s)
    rel = np.abs(back - np.asarray(x)).max() / np.abs(np.asarray(x)).max()
    assert rel < 1 / 100  # 8-bit: ~1/254 max relative step


#: a subarray dot whose offset conversion sits on a half-code boundary:
#: f32(f32(d * inv) + off) is exactly -57.5 (-> -58, half to even), while
#: one fused multiply-add gives -57.4999973 (-> -57)
_TIE_DOT, _TIE_INV, _TIE_OFF = -31146, 0.0018416885, -0.13876641


@pytest.mark.parametrize("path", ["kernel", "jit"])
def test_variation_conversion_rounds_twice(path):
    """The variation conversion rounds the product and the offset sum
    separately, as the numpy ``adc_convert`` does, on the Pallas kernel
    and under jit — never as one fused multiply-add."""
    from repro.core.cim import adc_convert, adc_convert_jnp

    inv, off = np.float32(_TIE_INV), np.float32(_TIE_OFF)
    want = adc_convert(np.array([_TIE_DOT]), inv, -128.0, 127.0, off)[0]
    assert want == -58.0
    if path == "kernel":
        # 127*-128 + 127*-128 + 127*10 + 96*1 = -31146
        xq = jnp.zeros((1, 256), jnp.int8).at[0, :4].set(
            jnp.array([127, 127, 127, 96], jnp.int8))
        wq = jnp.zeros((256, 1), jnp.int8).at[:4, 0].set(
            jnp.array([-128, -128, 10, 1], jnp.int8))
        got = cim_matmul_pallas(xq, wq, CIMSpec(), emit_codes=True,
                                adc_var=jnp.array([[inv, off]]))[0, 0]
    else:
        convert = jax.jit(
            lambda d, i, o: adc_convert_jnp(d, i, -128.0, 127.0, o))
        got = convert(jnp.full((8, 128), _TIE_DOT, jnp.int32), inv, off)[0, 0]
    assert float(got) == want
