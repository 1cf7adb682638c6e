"""CIM array numerics — the Domino PE modeled in the integer domain.

The Domino PE (paper §4.5) stores an 8-bit weight as eight single-level
1T1R cells across bit lines.  Four current mirrors per 4-bit group apply
per-bit-line significances (k/8, k/4, k/2, k); the two 4-bit groups are
joined by a 16:1 charge redistribution between two integrators; input-bit
significance is realized by charge averaging over the 8 bit-serial input
cycles; one SAR ADC per column digitizes the result.

On a TPU none of the analog machinery exists, so we reproduce its
*numerics* exactly:

* bit-plane decomposition + mirror significances + 16:1 group join is
  mathematically identical to an exact int8 dot product (proved by
  :func:`repro.kernels.ref.cim_matmul_bitplane_ref` and property tests);
* the only true nonideality is the ADC: a per-subarray (N_c rows)
  quantize-and-saturate step.  We model it as
  ``q = clip(round(d * gain * Q / FS), -Q-1, Q)`` with ``FS`` the
  subarray's full-scale dot value and ``gain`` the paper's integration
  gain ``k`` (calibrated per layer);
* ADC outputs are *digitally* accumulated across subarrays — this is the
  partial-sum that Domino's Rofm adds "on the move".

Everything here is pure jnp; the Pallas kernel in
``repro/kernels/cim_matmul.py`` implements the same pipeline with
explicit VMEM tiling.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class CIMSpec:
    """Static description of one CIM crossbar (Domino Tab. 3 defaults)."""

    n_c: int = 256  # rows per subarray = ADC accumulation granularity
    n_m: int = 256  # columns (8-bit weights) per array
    w_bits: int = 8
    a_bits: int = 8
    adc_bits: int = 8
    # integration gain k (paper §4.5): scales the ADC input so the useful
    # dot-product range fills the converter.  gain=FS/target_range.
    gain: float = 16.0

    @property
    def q_max(self) -> int:
        return 2 ** (self.adc_bits - 1) - 1

    @property
    def w_max(self) -> int:
        return 2 ** (self.w_bits - 1) - 1

    @property
    def a_max(self) -> int:
        return 2 ** (self.a_bits - 1) - 1

    @property
    def full_scale(self) -> float:
        """Max |dot| one subarray can produce (drives the ADC range)."""
        return float(self.n_c * self.w_max * self.a_max)

    @property
    def adc_inv_step(self) -> float:
        """Multiplier taking an exact int32 subarray dot to ADC codes."""
        return self.gain * self.q_max / self.full_scale

    @property
    def adc_step(self) -> float:
        return 1.0 / self.adc_inv_step

    @property
    def lossless(self) -> bool:
        """True if the ADC step <= 1 (no information lost)."""
        return self.adc_step <= 1.0


DEFAULT_SPEC = CIMSpec()


def lossless_spec(n_c: int = 256, w_bits: int = 8, a_bits: int = 8) -> CIMSpec:
    """A spec whose ADC step is exactly 1 code per dot unit: the converter
    is wide enough that ``q_max >= full_scale`` (no saturation) and the
    gain makes the float32 inverse step round to exactly 1.0 — so ADC
    codes *are* the exact subarray dots and the quantized pipeline
    degenerates to plain w8a8 (the invariant ``tests/test_engine.py``
    locks down on every benchmark conv geometry)."""
    import math

    w_max = 2 ** (w_bits - 1) - 1
    a_max = 2 ** (a_bits - 1) - 1
    fs = n_c * w_max * a_max
    adc_bits = math.ceil(math.log2(fs + 1)) + 1  # q_max = 2^(b-1)-1 >= fs
    q_max = 2 ** (adc_bits - 1) - 1
    spec = CIMSpec(n_c=n_c, w_bits=w_bits, a_bits=a_bits,
                   adc_bits=adc_bits, gain=fs / q_max)
    assert spec.lossless and np.float32(spec.adc_inv_step) == np.float32(1.0)
    return spec


# ---------------------------------------------------------------------------
# Quantization helpers
# ---------------------------------------------------------------------------


def quantize_symmetric(x: jax.Array, bits: int = 8,
                       axis: Optional[int] = None) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor (or per-axis) int quantization.

    Returns (q, scale) with x ~= q * scale, q in int8.
    """
    qmax = 2 ** (bits - 1) - 1
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-8) / qmax
    q = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def adc_quantize(d: jax.Array, spec: CIMSpec) -> jax.Array:
    """The SAR-ADC model: round-and-saturate an exact subarray dot.

    ``d`` is int32 (exact dot over <=n_c rows).  Output is int32 ADC codes
    in [-q_max-1, q_max].
    """
    return adc_convert_jnp(d, spec.adc_inv_step, -spec.q_max - 1,
                           spec.q_max)


def adc_convert_jnp(d: jax.Array, inv_step32, code_lo, code_hi,
                    offset=None) -> jax.Array:
    """Traceable twin of :func:`adc_convert` (the jitted trace path and
    the Pallas kernel body): the same f32 multiply(+add), round
    half-to-even and saturate, as int32 codes — so a zero code is +0.0
    once cast back to float.

    Compilers fuse a multiply that feeds an add into one fused
    multiply-add (XLA's CPU backend does), which rounds once where the
    numpy conversion rounds twice and moves a code whenever the exact
    value lies within an f32 ulp of a half-code boundary.  So with an
    offset the product first passes through a max with the lowest finite
    f32: the identity on these finite products, and not an operand any
    compiler can fuse into the add.
    """
    acc = d.astype(jnp.float32) * inv_step32
    if offset is not None:
        acc = jnp.maximum(acc, jnp.finfo(jnp.float32).min) + offset
    return jnp.clip(jnp.round(acc), code_lo, code_hi).astype(jnp.int32)


def adc_dequantize(codes: jax.Array, spec: CIMSpec) -> jax.Array:
    return codes.astype(jnp.float32) * spec.adc_step


def adc_convert(d: np.ndarray, inv_step32, code_lo: float, code_hi: float,
                offset=None) -> np.ndarray:
    """The SAR conversion on exact integer dots, **shared verbatim** by
    every executor flavor (per-tile numpy, the fused batch-of-tiles trace
    path, the FC grid) and bit-for-bit the jnp / Pallas-kernel arithmetic:
    int32 -> float32, scale by the float32 inverse step, round
    half-to-even, saturate.  Vectorized over any leading shape — one call
    converts all subarrays of a layer at once.  Output is ADC codes exact
    in float64, so downstream accumulation order is free.

    ``inv_step32`` may be a scalar or a float32 array broadcastable
    against ``d`` (per-subarray gain error under a
    :class:`~repro.core.variation.VariationModel`); ``offset`` (same
    broadcast rules, in code LSBs, added before rounding) models the
    per-subarray SAR comparator offset.  ``offset=None`` leaves the
    arithmetic byte-identical to the nominal two-op conversion.  A zero
    code is +0.0 (``round`` of a small negative value is -0.0), as the
    int32 codes of the jnp and kernel paths are.
    """
    d = np.asarray(d)
    acc = (d.astype(np.int32).astype(np.float32)
           * np.asarray(inv_step32, np.float32))
    if offset is not None:
        acc = acc + np.asarray(offset, np.float32)
    codes = np.clip(np.round(acc), code_lo, code_hi).astype(np.float64)
    codes += 0.0
    return codes


def calibrate_gain(x, w, spec: CIMSpec, percentile: float = 100.0) -> float:
    """Pick the integration gain k so the `percentile` of subarray dots
    fills the ADC range (the knob the paper's current mirrors provide).

    Quantization here must mirror :func:`cim_linear_reference` exactly
    (per-column weight scales), else the computed gain saturates the ADC.
    Pure numpy: the dots are exact small integers, so float64 BLAS
    reproduces the int32 einsum bit-for-bit at a fraction of the jit
    cost (calibration runs once per layer at network build).
    """
    x = np.asarray(x, np.float32)
    w = np.asarray(w, np.float32)
    xq = _quant_np(x.reshape(-1, x.shape[-1]), spec.a_bits)
    wq = _quant_np(w, spec.w_bits, axis=0)
    k_dim = w.shape[0]
    pad = (-k_dim) % spec.n_c
    if pad:
        xq = np.pad(xq, ((0, 0), (0, pad)))
        wq = np.pad(wq, ((0, pad), (0, 0)))
    n_sub = (k_dim + pad) // spec.n_c
    xs = xq.reshape(-1, n_sub, spec.n_c).transpose(1, 0, 2)
    ws = wq.reshape(n_sub, spec.n_c, -1)
    d = np.matmul(xs, ws)  # (n_sub, B, N) exact per-subarray integer dots
    mag = float(np.percentile(np.abs(d).astype(np.float32), percentile))
    if mag <= 0:
        return 1.0
    return max(1.0, spec.full_scale / mag)


def _quant_np(x: np.ndarray, bits: int, axis: Optional[int] = None
              ) -> np.ndarray:
    """Numpy mirror of :func:`quantize_symmetric` (int-valued float64)."""
    qmax = 2 ** (bits - 1) - 1
    amax = np.max(np.abs(x), axis=axis, keepdims=axis is not None)
    scale = np.maximum(amax, 1e-8).astype(np.float32) / qmax
    return np.clip(np.round(x / scale), -qmax - 1, qmax).astype(np.float64)


# ---------------------------------------------------------------------------
# Functional CIM matmul (jnp reference semantics; used by the simulator and
# as the CPU fallback for CIM-quantized serving)
# ---------------------------------------------------------------------------


def cim_matmul(xq: jax.Array, wq: jax.Array, spec: CIMSpec = DEFAULT_SPEC) -> jax.Array:
    """int8 x int8 -> f32 codesum through the per-subarray ADC pipeline.

    xq: (..., K) int8, wq: (K, N) int8.  Returns (..., N) float32 equal to
    ``sum_s adc_dequant(adc_quant(dot_s))`` — what the Rofm accumulates.
    """
    k_dim = wq.shape[0]
    pad = (-k_dim) % spec.n_c
    if pad:
        xq = jnp.pad(xq, [(0, 0)] * (xq.ndim - 1) + [(0, pad)])
        wq = jnp.pad(wq, ((0, pad), (0, 0)))
    n_sub = (k_dim + pad) // spec.n_c
    lead = xq.shape[:-1]
    xs = xq.reshape(*lead, n_sub, spec.n_c).astype(jnp.int32)
    ws = wq.reshape(n_sub, spec.n_c, -1).astype(jnp.int32)
    d = jnp.einsum("...sk,skn->...sn", xs, ws)  # exact per-subarray dots
    codes = adc_quantize(d, spec)
    return jnp.sum(codes, axis=-2).astype(jnp.float32) * spec.adc_step


def cim_linear_reference(x: jax.Array, w: jax.Array,
                         spec: CIMSpec = DEFAULT_SPEC,
                         w_scale: Optional[jax.Array] = None,
                         wq: Optional[jax.Array] = None) -> jax.Array:
    """Float-in/float-out CIM linear: quantize activations per-tensor,
    weights per-column (pre-quantized if wq given), run the ADC pipeline,
    dequantize."""
    if wq is None:
        wq, w_scale = quantize_symmetric(w, spec.w_bits, axis=0)
    xq, x_scale = quantize_symmetric(x, spec.a_bits)
    acc = cim_matmul(xq, wq, spec)
    return acc * x_scale * w_scale.reshape((1,) * (x.ndim - 1) + (-1,))
