"""Inputs of a cell, all drawn from ``--seed``: int8 weights with
per-column scales (made on the device in one jitted call), frames and
calibration frames (numpy), Monte-Carlo trial seeds."""
from __future__ import annotations

from functools import partial
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import Layer


def rngs(seed: int) -> Tuple[np.random.Generator, int]:
    """A host generator and a JAX key seed, both from ``seed`` (any
    non-negative integer)."""
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
    return rng, int(rng.integers(0, 2 ** 31 - 1))


@partial(jax.jit, static_argnames=("shapes", "quantize"))
def _weights(key, *, shapes: Tuple[Tuple[int, ...], ...], quantize: bool):
    """He-normal float32 weights, or their int8 quantization per output
    column over the flattened contraction: ``(q int8, s f32 (M,))``."""
    out = []
    for k, shape in zip(jax.random.split(key, len(shapes)), shapes):
        fan_in = int(np.prod(shape[:-1]))
        w = jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)
        if not quantize:
            out.append(w)
            continue
        w2 = w.reshape(-1, shape[-1])
        s = jnp.maximum(jnp.max(jnp.abs(w2), axis=0), 1e-8) / 127
        q = jnp.clip(jnp.round(w2 / s), -128, 127).astype(jnp.int8)
        out.append((q.reshape(shape), s))
    return out


def make_weights(layers: Sequence[Layer], key_seed: int, served: str
                 ) -> Dict[str, object]:
    """The program's weights per layer name, on the host, in the type
    they are served in: ``"int8"`` (``{"q", "s"}`` serving leaves) or
    ``"float32"`` (float weights the program quantizes itself)."""
    shapes = tuple(layer.weight_shape for layer in layers)
    got = jax.device_get(_weights(jax.random.PRNGKey(key_seed),
                                  shapes=shapes, quantize=served == "int8"))
    if served == "int8":
        return {layer.name: {"q": np.asarray(q), "s": np.asarray(s)}
                for layer, (q, s) in zip(layers, got)}
    return {layer.name: np.asarray(w, np.float64)
            for layer, w in zip(layers, got)}


def frames(rng: np.random.Generator, n: int, hw: int) -> np.ndarray:
    """``n`` frames (n, hw, hw, 3) float64 uniform in [0, 1)."""
    return rng.random((n, hw, hw, 3))
