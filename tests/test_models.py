"""Smoke tests of the jax CNN reference (``models/cnn.py``) on the
paper's benchmark models: forward shapes, and the CIM-quantized forward
against the dense one."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow

from repro.configs.cnn import CNN_BENCHMARKS
from repro.models.cnn import cnn_forward, init_cnn


@pytest.mark.parametrize("name", ["vgg11-cifar10", "resnet18-cifar10"])
def test_cnn_forward_shapes(name):
    cnn = CNN_BENCHMARKS[name]()
    key = jax.random.PRNGKey(5)
    params = init_cnn(key, cnn)
    x = jax.random.normal(key, (2, cnn.input_hw, cnn.input_hw, 3))
    logits = cnn_forward(params, x, cnn)
    assert logits.shape == (2, 10)
    assert jnp.all(jnp.isfinite(logits))


def test_cnn_cim_mode_close_to_dense():
    """CIM-quantized CNN stays close to dense (the paper's accuracy gap)."""
    from repro.core.cim import CIMSpec
    cnn = CNN_BENCHMARKS["vgg11-cifar10"]()
    key = jax.random.PRNGKey(6)
    params = init_cnn(key, cnn)
    x = jax.random.normal(key, (1, 32, 32, 3))
    dense = cnn_forward(params, x, cnn)
    cim = cnn_forward(params, x, cnn, cim=CIMSpec(n_c=256, adc_bits=8, gain=64.0))
    # rankings should largely agree even at 8-bit
    corr = np.corrcoef(np.asarray(dense).ravel(), np.asarray(cim).ravel())[0, 1]
    assert corr > 0.95, corr
