"""CNN inference in JAX for the paper's benchmark models (VGG / ResNet).

Two numerics modes:
* dense  — f32 ``lax.conv`` (the accuracy oracle);
* cim    — every conv/FC routed through the Domino PE pipeline
  (im2col -> ``cim_linear_reference``), i.e. 8-bit weights resident in
  crossbars + per-subarray ADC.  This is what produces the paper's
  ~1-2% accuracy drop (Tab. 4 accuracy rows).

BatchNorm is assumed folded into conv weights (standard for CIM
deployment; the paper stores only folded 8-bit weights).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.cnn import CNNConfig, ConvLayer, FCLayer
from repro.core.cim import CIMSpec, cim_linear_reference, quantize_symmetric


def init_cnn(key, cnn: CNNConfig, dtype=jnp.float32) -> Dict[str, jax.Array]:
    params = {}
    keys = jax.random.split(key, len(cnn.layers))
    for k, layer in zip(keys, cnn.layers):
        if isinstance(layer, ConvLayer):
            fan_in = layer.c * layer.k * layer.k
            params[layer.name] = (
                jax.random.normal(k, (layer.k, layer.k, layer.c, layer.m))
                / jnp.sqrt(fan_in)
            ).astype(dtype)
        else:
            params[layer.name] = (
                jax.random.normal(k, (layer.c_in, layer.c_out))
                / jnp.sqrt(layer.c_in)
            ).astype(dtype)
    return params


def _conv(x, w, layer: ConvLayer, cim: Optional[CIMSpec]):
    if cim is None:
        return lax.conv_general_dilated(
            x, w, window_strides=(layer.s, layer.s),
            padding=[(layer.p, layer.p), (layer.p, layer.p)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
    # im2col -> CIM matmul: each output pixel's receptive field becomes a
    # row; the (K*K*C, M) weight matrix lives in crossbars.
    b = x.shape[0]
    patches = lax.conv_general_dilated_patches(
        x, (layer.k, layer.k), (layer.s, layer.s),
        padding=[(layer.p, layer.p), (layer.p, layer.p)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )  # (B, E, F, K*K*C)
    e, f = patches.shape[1], patches.shape[2]
    cols = patches.reshape(b * e * f, -1)
    # conv_general_dilated_patches emits (C, K, K)-ordered features
    wmat = w.transpose(2, 0, 1, 3).reshape(-1, layer.m)
    out = cim_linear_reference(cols, wmat, cim)
    return out.reshape(b, e, f, layer.m)


def cnn_forward(params, images, cnn: CNNConfig,
                cim: Optional[CIMSpec] = None,
                capture: Optional[Dict[str, jax.Array]] = None) -> jax.Array:
    """images: (B, H, W, 3) -> logits (B, classes).

    ``capture`` (a dict, filled in place) records every layer's *input*
    activation — the tensor the Domino block would stream — keyed by
    layer name; the quantized PE engines calibrate their per-layer
    activation scale and ADC gain from it (``core/engine.py``).

    Convolutions and matmuls run at full f32 precision on every backend
    (a TPU's default f32 dot takes bf16 passes), so a chip calibrates
    the same ADC gains and gives the same reference as the host.
    """
    with jax.default_matmul_precision("highest"):
        return _forward(params, images, cnn, cim, capture)


def _forward(params, images, cnn: CNNConfig, cim: Optional[CIMSpec],
             capture: Optional[Dict[str, jax.Array]]) -> jax.Array:
    x = images
    saved: Dict[str, jax.Array] = {}
    layers: List = list(cnn.layers)
    i = 0
    while i < len(layers):
        layer = layers[i]
        if isinstance(layer, FCLayer):
            if x.ndim == 4:
                if cnn.name.startswith("resnet"):
                    x = jnp.mean(x, axis=(1, 2))  # global average pool
                else:
                    x = x.reshape(x.shape[0], -1)
            if capture is not None:
                capture[layer.name] = x
            if cim is None:
                x = x @ params[layer.name]
            else:
                x = cim_linear_reference(x, params[layer.name], cim)
            if i < len(layers) - 1:
                x = jax.nn.relu(x)
            i += 1
            continue

        if layer.name.endswith("_a"):
            saved["block_in"] = x
        if capture is not None:
            capture[layer.name] = x
        y = _conv(x, params[layer.name], layer, cim)
        if layer.residual_from is not None:
            nxt = layers[i + 1] if i + 1 < len(layers) else None
            if isinstance(nxt, ConvLayer) and nxt.name.endswith("_sc"):
                if capture is not None:
                    capture[nxt.name] = saved["block_in"]
                shortcut = _conv(saved["block_in"], params[nxt.name], nxt, cim)
                i += 1  # consume the shortcut layer
            else:
                shortcut = saved["block_in"]
            y = y + shortcut
        x = jax.nn.relu(y)
        if layer.pool_s:
            x = lax.reduce_window(
                x, -jnp.inf, lax.max,
                (1, layer.pool_k, layer.pool_k, 1),
                (1, layer.pool_s, layer.pool_s, 1), "VALID")
        i += 1
    return x


def collect_layer_inputs(params, images, cnn: CNNConfig
                         ) -> Dict[str, jax.Array]:
    """Float forward pass capturing each layer's input activation — the
    calibration hook for the quantized PE engines."""
    capture: Dict[str, jax.Array] = {}
    cnn_forward(params, images, cnn, capture=capture)
    return capture
