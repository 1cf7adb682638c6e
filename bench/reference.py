"""Plain reference of the Domino CIM network the benchmark's cells serve.

Written from the semantics a configuration file states (layer shapes,
``n_c``, the w8a8 + per-subarray 8-bit ADC numerics, the calibration
rule, the device-variation draws) and importing nothing of the program.
It takes the same inputs the program is given (int8 weights with
per-column scales, frames, calibration frames, a variation corner and
its trial seed) and recomputes everything else itself: the float
calibration forward, each layer's activation scale and ADC gain, the
subarray partition of every convolution, the integer dots, the ADC
conversion, the digital code sums and the float64 block tails.

Numerics, per quantized layer with input ``x`` (float64):

* activations: ``xq = clip(round(x / a_scale), -a_max - 1, a_max)``;
* each subarray ``t`` (at most ``n_c`` contraction rows) takes an exact
  integer dot, converted by its ADC:
  ``code = clip(round(f32(d) * f32(inv_step) [+ f32(offset)]), -q-1, q)``
  (two f32 roundings, never a fused multiply-add);
* the layer output is ``sum_t code_t * ((1 / inv_step) * a_scale * s_w)``,
  then ReLU / max-pool / the residual add in float64.

The integer dots and the ADC conversion run on the default JAX device
(int8 x int8 -> int32 and f32: exact or IEEE alike on every backend);
everything in float64 runs in numpy.
"""
from __future__ import annotations

import math
import zlib
from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

#: rows / weight columns of the calibration subsample (the stated
#: calibration rule: a deterministic stride over both)
CALIB_ROWS = 4096
CALIB_COLS = 512


@dataclass(frozen=True)
class Layer:
    name: str
    kind: str                  # "conv" | "fc"
    h: int = 0
    w: int = 0
    c: int = 0
    m: int = 0
    k: int = 1
    s: int = 1
    p: int = 0
    pool_k: int = 0
    pool_s: int = 0
    residual_from: Optional[str] = None
    c_in: int = 0
    c_out: int = 0

    @property
    def e(self) -> int:
        return (self.h + 2 * self.p - self.k) // self.s + 1

    @property
    def f(self) -> int:
        return (self.w + 2 * self.p - self.k) // self.s + 1

    @property
    def macs(self) -> int:
        if self.kind == "fc":
            return self.c_in * self.c_out
        return self.e * self.f * self.m * self.c * self.k * self.k

    @property
    def shortcut(self) -> bool:
        return self.kind == "conv" and self.name.endswith("_sc")

    @property
    def weight_shape(self) -> Tuple[int, ...]:
        if self.kind == "fc":
            return (self.c_in, self.c_out)
        return (self.k, self.k, self.c, self.m)


def layers_of(cfg: dict) -> List[Layer]:
    return [Layer(**spec) for spec in cfg["layers"]]


def macs_per_frame(cfg: dict) -> int:
    return sum(layer.macs for layer in layers_of(cfg))


@dataclass(frozen=True)
class Numerics:
    """The crossbar and converter a configuration states."""

    n_c: int = 256
    w_bits: int = 8
    a_bits: int = 8
    adc_bits: int = 8
    clip_percentile: float = 99.9

    @property
    def w_max(self) -> int:
        return 2 ** (self.w_bits - 1) - 1

    @property
    def a_max(self) -> int:
        return 2 ** (self.a_bits - 1) - 1

    @property
    def q_max(self) -> int:
        return 2 ** (self.adc_bits - 1) - 1

    @property
    def full_scale(self) -> float:
        return float(self.n_c * self.w_max * self.a_max)

    def inv_step(self, gain: float) -> float:
        return gain * self.q_max / self.full_scale


def numerics_of(cfg: dict) -> Numerics:
    return Numerics(n_c=cfg["n_c"], w_bits=cfg["w_bits"],
                    a_bits=cfg["a_bits"], adc_bits=cfg["adc_bits"],
                    clip_percentile=cfg["clip_percentile"])


def conv_subarrays(layer: Layer, n_c: int
                   ) -> Tuple[Tuple[int, int, int, int, int], ...]:
    """Domino's partition of a convolution's K*K*C contraction into
    subarrays, in chain order: ``(row i, first tap j0, taps, c_lo,
    c_hi)``.  A filter row is one group; where ``C <= n_c`` up to
    ``n_c // C`` taps of the row share a subarray, otherwise each tap's
    channels split into ``ceil(C / n_c)`` equal slices."""
    k, c = layer.k, layer.c
    if c <= n_c:
        pack, splits = min(k, max(1, n_c // c)), 1
    else:
        pack, splits = 1, math.ceil(c / n_c)
    per_split = math.ceil(c / splits)
    out = []
    for i in range(k):
        for j0 in range(0, k, pack):
            for sc in range(splits):
                out.append((i, j0, min(pack, k - j0), sc * per_split,
                            min(c, (sc + 1) * per_split)))
    return tuple(out)


# ---------------------------------------------------------------------------
# Device-variation draws (the corner's stated random streams)
# ---------------------------------------------------------------------------


def _rng(seed: int, name: str, stream: int) -> np.random.Generator:
    return np.random.default_rng(
        [int(seed), zlib.crc32(name.encode("utf-8")), stream])


def perturb(q: np.ndarray, name: str, var: dict, seed: int,
            w_max: int) -> np.ndarray:
    """Conductance noise, then stuck-at cells from one uniform field."""
    sigma = var.get("conductance_sigma", 0.0)
    sa0, sa1 = var.get("stuck_zero", 0.0), var.get("stuck_one", 0.0)
    if not (sigma or sa0 or sa1):
        return q
    out = q.astype(np.float64)
    rng = _rng(seed, name, 0)
    if sigma:
        out = np.clip(np.round(out * (1.0 + rng.normal(0.0, sigma, q.shape))),
                      -float(w_max) - 1.0, float(w_max))
    if sa0 or sa1:
        u = rng.random(q.shape)
        out = np.where(u < sa0, 0.0, out)
        out = np.where((u >= sa0) & (u < sa0 + sa1), float(w_max), out)
    return out.astype(q.dtype)


def adc_draws(name: str, n_sub: int, inv_step: float, var: dict,
              seed: int) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Per-subarray (f32 inverse step, f32 offset), or None (nominal)."""
    g_sig = var.get("adc_gain_sigma", 0.0)
    o_sig = var.get("adc_offset_sigma", 0.0)
    if not (g_sig or o_sig):
        return None
    rng = _rng(seed, name, 1)
    gain = rng.normal(0.0, g_sig, n_sub) if g_sig else np.zeros(n_sub)
    off = rng.normal(0.0, o_sig, n_sub) if o_sig else np.zeros(n_sub)
    return (np.asarray(float(inv_step) * (1.0 + gain), np.float32),
            np.asarray(off, np.float32))


# ---------------------------------------------------------------------------
# Calibration: float32 forward at full precision, then per-layer scales
# ---------------------------------------------------------------------------


def float_inputs(layers: Sequence[Layer], head: str,
                 wf: Dict[str, np.ndarray], images: np.ndarray
                 ) -> Dict[str, np.ndarray]:
    """Every layer's float32 input under the plain float forward (a
    projection shortcut's input is its block's input)."""
    import jax.numpy as jnp
    from jax import lax

    caps: Dict[str, np.ndarray] = {}
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(images, jnp.float32)
        block_in = None
        for li, layer in enumerate(layers):
            if layer.shortcut:
                continue          # runs beside its residual target
            if layer.kind == "fc":
                if x.ndim == 4:
                    x = (jnp.mean(x, axis=(1, 2)) if head == "mean"
                         else x.reshape(x.shape[0], -1))
                caps[layer.name] = np.asarray(x)
                x = x @ jnp.asarray(wf[layer.name])
                if li < len(layers) - 1:
                    x = jax.nn.relu(x)
                continue
            if layer.name.endswith("_a"):
                block_in = x
            caps[layer.name] = np.asarray(x)
            y = _conv_f32(x, wf[layer.name], layer)
            if layer.residual_from is not None:
                nxt = layers[li + 1] if li + 1 < len(layers) else None
                if nxt is not None and nxt.shortcut:
                    caps[nxt.name] = np.asarray(block_in)
                    y = y + _conv_f32(block_in, wf[nxt.name], nxt)
                else:
                    y = y + block_in
            x = jax.nn.relu(y)
            if layer.pool_s:
                x = lax.reduce_window(
                    x, -jnp.inf, lax.max, (1, layer.pool_k, layer.pool_k, 1),
                    (1, layer.pool_s, layer.pool_s, 1), "VALID")
    return caps


def _conv_f32(x, w, layer: Layer):
    import jax.numpy as jnp
    from jax import lax

    return lax.conv_general_dilated(
        x, jnp.asarray(w), window_strides=(layer.s, layer.s),
        padding=[(layer.p, layer.p)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _calib_matrix(x: np.ndarray, w: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Calibration rows and weight matrix, (C, K, K) feature order:
    ``CALIB_ROWS`` windows at strided centres, stride-1 and zero-padded
    by ``(K - 1) // 2``."""
    if w.ndim == 2:
        cols = x.reshape(-1, x.shape[-1])
        if cols.shape[0] > CALIB_ROWS:
            cols = cols[::math.ceil(cols.shape[0] / CALIB_ROWS)]
        return cols, w
    k, _, c, m = w.shape
    b, h, wd, _ = x.shape
    total = b * h * wd
    step = math.ceil(total / CALIB_ROWS) if total > CALIB_ROWS else 1
    idx = np.arange(0, total, step)
    bi, rest = np.divmod(idx, h * wd)
    yi, xi = np.divmod(rest, wd)
    lo = (k - 1) // 2
    xp = np.zeros((b, h + k - 1, wd + k - 1, c), np.float32)
    xp[:, lo:lo + h, lo:lo + wd] = x
    dy, dx = np.meshgrid(np.arange(k), np.arange(k), indexing="ij")
    win = xp[bi[:, None, None], yi[:, None, None] + dy[None],
             xi[:, None, None] + dx[None]]
    cols = win.transpose(0, 3, 1, 2).reshape(len(idx), -1)
    return cols, w.transpose(2, 0, 1, 3).reshape(-1, m)


def _quant_max(x: np.ndarray, bits: int, axis=None) -> np.ndarray:
    q = 2 ** (bits - 1) - 1
    amax = np.max(np.abs(x), axis=axis, keepdims=axis is not None)
    scale = np.maximum(amax, 1e-8).astype(np.float32) / q
    return np.clip(np.round(x / scale), -q - 1, q).astype(np.float64)


def _gain(cols: np.ndarray, wmat: np.ndarray, num: Numerics) -> float:
    """Integration gain: the largest subarray dot of the calibration
    rows fills the ADC range (contiguous ``n_c`` blocks of the
    flattened contraction), never below 1."""
    xq = _quant_max(np.asarray(cols, np.float32), num.a_bits)
    wq = _quant_max(np.asarray(wmat, np.float32), num.w_bits, axis=0)
    pad = (-wmat.shape[0]) % num.n_c
    if pad:
        xq = np.pad(xq, ((0, 0), (0, pad)))
        wq = np.pad(wq, ((0, pad), (0, 0)))
    n_sub = xq.shape[1] // num.n_c
    d = np.matmul(xq.reshape(-1, n_sub, num.n_c).transpose(1, 0, 2),
                  wq.reshape(n_sub, num.n_c, -1))
    mag = float(np.percentile(np.abs(d).astype(np.float32), 100.0))
    return 1.0 if mag <= 0 else max(1.0, num.full_scale / mag)


def calibrate(layers: Sequence[Layer], head: str,
              wf: Dict[str, np.ndarray], images: np.ndarray,
              num: Numerics) -> Dict[str, Tuple[float, float]]:
    """Per layer ``(a_scale, gain)`` from the float forward of
    ``images``: the ``clip_percentile`` of the input's magnitudes fills
    the activation range; :func:`_gain` sets the ADC gain."""
    caps = float_inputs(layers, head, wf, images)
    out = {}
    for layer in layers:
        x = caps[layer.name]
        a_obs = float(np.percentile(np.abs(x), num.clip_percentile))
        cols, wmat = _calib_matrix(x, np.asarray(wf[layer.name], np.float32))
        if wmat.shape[1] > CALIB_COLS:
            wmat = wmat[:, ::math.ceil(wmat.shape[1] / CALIB_COLS)]
        out[layer.name] = (max(a_obs / num.a_max, 1e-8),
                           _gain(cols, wmat, num))
    return out


# ---------------------------------------------------------------------------
# The quantized forward
# ---------------------------------------------------------------------------


@partial(jax.jit,
         static_argnames=("subs", "stride", "e", "f", "lo", "hi"))
def _conv_codes(xp, w, inv, off, *, subs, stride, e, f, lo, hi):
    """Digital code sum of one conv layer: per subarray an exact int8
    dot and its ADC conversion.  ``xp``: (B, Hp, Wp, C) int8 padded
    codes; ``inv``/``off``: (T,) f32 (``off`` None = nominal)."""
    import jax.numpy as jnp
    from jax import lax

    acc = None
    span_e, span_f = stride * (e - 1) + 1, stride * (f - 1) + 1
    for t, (i, j0, taps, c_lo, c_hi) in enumerate(subs):
        cols = jnp.concatenate(
            [xp[:, i:i + span_e:stride, j:j + span_f:stride, c_lo:c_hi]
             for j in range(j0, j0 + taps)], axis=-1)
        wt = w[i, j0:j0 + taps, c_lo:c_hi].reshape(-1, w.shape[-1])
        d = lax.dot_general(cols, wt, (((3,), (0,)), ((), ())),
                            preferred_element_type=jnp.int32)
        codes = _convert(d, inv[t], None if off is None else off[t], lo, hi)
        acc = codes if acc is None else acc + codes
    return acc


def _convert(d, inv, off, lo, hi):
    import jax.numpy as jnp

    a = d.astype(jnp.float32) * inv
    if off is not None:
        # a max between the product and the add: two roundings, as stated
        a = jnp.maximum(a, jnp.finfo(jnp.float32).min) + off
    return jnp.clip(jnp.round(a), lo, hi).astype(jnp.int32)


@partial(jax.jit, static_argnames=("lo", "hi"))
def _fc_codes(xq, w, inv, off, *, lo, hi):
    """(B, S, n_c) int8 x (S, n_c, N) int8: one conversion per subarray
    ``s`` (a row block of the contraction), codes summed."""
    import jax.numpy as jnp
    from jax import lax

    d = lax.dot_general(xq, w, (((2,), (1,)), ((1,), (0,))),
                        preferred_element_type=jnp.int32)    # (S, B, N)
    off_b = None if off is None else off[:, None, None]
    return _convert(d, inv[:, None, None], off_b, lo, hi).sum(axis=0)


def _quant(x: np.ndarray, a_scale: float, a_max: int) -> np.ndarray:
    return np.clip(np.round(x / a_scale), -a_max - 1, a_max).astype(np.int8)


def quantized_forward(layers: Sequence[Layer], head: str,
                      wq: Dict[str, np.ndarray], ws: Dict[str, np.ndarray],
                      calib: Dict[str, Tuple[float, float]],
                      images: np.ndarray, num: Numerics,
                      variation: Optional[dict] = None,
                      var_seed: int = 0) -> np.ndarray:
    """Logits (B, classes) float64 of ``images`` (B, H, W, 3)."""
    import jax.numpy as jnp

    var = variation or {}
    lo, hi = float(-num.q_max - 1), float(num.q_max)

    def layer_params(layer: Layer, n_sub: int):
        a_scale, gain = calib[layer.name]
        inv_step = num.inv_step(gain)
        q = perturb(wq[layer.name], layer.name, var, var_seed, num.w_max)
        draws = adc_draws(layer.name, n_sub, inv_step, var, var_seed)
        if draws is None:
            inv, off = np.full(n_sub, np.float32(inv_step)), None
        else:
            inv, off = draws
        deq = (1.0 / inv_step * a_scale) * np.asarray(ws[layer.name],
                                                      np.float64)
        return a_scale, q, inv, off, deq

    def conv(layer: Layer, x: np.ndarray) -> np.ndarray:
        subs = conv_subarrays(layer, num.n_c)
        a_scale, q, inv, off, deq = layer_params(layer, len(subs))
        xq = _quant(x, a_scale, num.a_max)
        p = layer.p
        xp = np.pad(xq, ((0, 0), (p, p), (p, p), (0, 0)))
        codes = _conv_codes(
            jnp.asarray(xp), jnp.asarray(q), jnp.asarray(inv),
            None if off is None else jnp.asarray(off), subs=subs,
            stride=layer.s, e=layer.e, f=layer.f, lo=lo, hi=hi)
        return np.asarray(codes).astype(np.float64) * deq

    def fc(layer: Layer, x: np.ndarray) -> np.ndarray:
        n_sub = math.ceil(layer.c_in / num.n_c)
        # one converter draw per physical ADC slot the FC grid provides
        a_scale, q, inv, off, deq = layer_params(layer, 2 * n_sub + 1)
        pad = n_sub * num.n_c - layer.c_in
        xq = np.pad(_quant(x, a_scale, num.a_max), ((0, 0), (0, pad)))
        w = np.pad(q, ((0, pad), (0, 0)))
        codes = _fc_codes(
            jnp.asarray(xq.reshape(xq.shape[0], n_sub, num.n_c)),
            jnp.asarray(w.reshape(n_sub, num.n_c, -1)),
            jnp.asarray(inv[:n_sub]),
            None if off is None else jnp.asarray(off[:n_sub]), lo=lo, hi=hi)
        return np.asarray(codes).astype(np.float64) * deq

    x = np.asarray(images, np.float64)
    block_in = None
    for li, layer in enumerate(layers):
        if layer.shortcut:
            continue
        if layer.kind == "fc":
            if x.ndim == 4:
                x = (x.mean(axis=(1, 2)) if head == "mean"
                     else x.reshape(x.shape[0], -1))
            x = fc(layer, x)
            if li < len(layers) - 1:
                x = np.maximum(x, 0.0)
            continue
        if layer.name.endswith("_a"):
            block_in = x
        y = conv(layer, x)
        if layer.residual_from is not None:
            nxt = layers[li + 1] if li + 1 < len(layers) else None
            short = (conv(nxt, block_in) if nxt is not None and nxt.shortcut
                     else block_in)
            x = np.maximum(y + short, 0.0)
        else:
            x = np.maximum(y, 0.0)
            if layer.pool_s:
                b, e, f, m = x.shape
                ps = layer.pool_s
                x = x.reshape(b, e // ps, ps, f // ps, ps, m).max(axis=(2, 4))
    return x


class Reference:
    """A configuration's reference: calibrates once on the calibration
    frames, then serves logits for any frames and variation trial.
    ``params`` are the program's own inputs: per layer either an int8
    ``{"q", "s"}`` pair or float weights, quantized here per column."""

    def __init__(self, cfg: dict, params: Dict[str, object],
                 calib_images: np.ndarray):
        self.layers = layers_of(cfg)
        self.head = cfg["head"]
        self.num = numerics_of(cfg)
        self.wq, self.ws, wf = {}, {}, {}
        for name, p in params.items():
            if isinstance(p, dict):
                q, s = np.asarray(p["q"]), np.asarray(p["s"])
                wf[name] = (q.astype(np.float64) * s.astype(np.float64)
                            .reshape(-1)).astype(np.float32)
            else:
                wf[name] = np.asarray(p, np.float32)
                q, s = quantize_columns(wf[name], self.num.w_bits)
            self.wq[name], self.ws[name] = q, s
        self.calib = calibrate(self.layers, self.head, wf, calib_images,
                               self.num)

    def logits(self, images: np.ndarray, variation: Optional[dict] = None,
               var_seed: int = 0) -> np.ndarray:
        return quantized_forward(self.layers, self.head, self.wq, self.ws,
                                 self.calib, images, self.num, variation,
                                 var_seed)


def quantize_columns(w: np.ndarray, bits: int
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric float32 quantization per output column over the
    flattened contraction: (int8 of ``w``'s shape, (M,) f32 scale)."""
    q_max = 2 ** (bits - 1) - 1
    w32 = np.asarray(w, np.float32).reshape(-1, w.shape[-1])
    s = np.maximum(np.max(np.abs(w32), axis=0, keepdims=True),
                   np.float32(1e-8)) / np.float32(q_max)
    q = np.clip(np.round(w32 / s), -q_max - 1, q_max).astype(np.int8)
    return q.reshape(w.shape), s.reshape(-1)
