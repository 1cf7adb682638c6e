"""jit'd public wrappers around the Pallas kernels.

``cim_linear`` is the layer-facing entry point: float activations in,
float out, with quantization, the CIM pipeline, dequantization and the
Domino "tail" ops (bias / activation — the things Rofm computes in the
last tile) fused behind one jit boundary.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.cim import CIMSpec, DEFAULT_SPEC, cim_matmul, quantize_symmetric
from repro.kernels.cim_matmul import cim_matmul_pallas


@functools.partial(jax.jit, static_argnames=("spec", "use_pallas", "activation"))
def cim_linear(x: jax.Array, wq: jax.Array, w_scale: jax.Array,
               bias: Optional[jax.Array] = None,
               spec: CIMSpec = DEFAULT_SPEC,
               use_pallas: bool = False,
               activation: Optional[str] = None) -> jax.Array:
    """x (..., K) float @ pre-quantized wq (K, N) int8 -> (..., N) float.

    use_pallas=True routes through the Pallas kernel (interpreted off
    TPU, which is slow for big shapes — the pure-jnp path has identical
    numerics, proven by tests, and is the default).
    """
    orig_dtype = x.dtype
    lead = x.shape[:-1]
    xq, x_scale = quantize_symmetric(x.astype(jnp.float32), spec.a_bits)
    if use_pallas:
        x2 = xq.reshape(-1, xq.shape[-1])
        acc = cim_matmul_pallas(x2, wq, spec)
        acc = acc.reshape(*lead, -1)
    else:
        acc = cim_matmul(xq, wq, spec)
    out = acc * x_scale * w_scale.reshape((1,) * len(lead) + (-1,))
    if bias is not None:
        out = out + bias
    if activation is not None:
        out = _ACTIVATIONS[activation](out)
    return out.astype(orig_dtype)


def quantize_weights(w: jax.Array, spec: CIMSpec = DEFAULT_SPEC):
    """Per-output-column symmetric int8 weight quantization (offline —
    Domino programs ReRAM cells once at initialization)."""
    return quantize_symmetric(w, spec.w_bits, axis=0)


_ACTIVATIONS: dict = {
    "relu": jax.nn.relu,
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "tanh": jnp.tanh,
}
