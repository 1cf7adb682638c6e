"""Pluggable PE numerics engines — the one seam every executor MACs
through.

The Domino PE's arithmetic used to be welded into each executor
(``BlockSimulator._pe_mac``, ``simulate_fc``, ``TraceExecutor``); this
module rips it out and re-lands it behind one interface, so the
per-cycle interpreter, the trace-compiled fast path, the streaming
wavefront and the FC grid all call the *same* engine object:

* :class:`ExactEngine` — the float64 ``gemm_rows`` path, bit-for-bit
  identical to the pre-engine executors (the default; every existing
  bitwise guarantee — interp == trace, streaming == sequential, batch
  invariance — is preserved unchanged);
* :class:`CIMEngine` — faithful w8a8 CIM numerics (paper §4.5): 8-bit
  weights resident per tile (one tile == one ``<= n_c``-row subarray, by
  the mapping planner's construction), activations quantized with a
  *per-layer static scale*, an exact integer subarray dot, the SAR-ADC
  round-and-saturate, and *digital* accumulation of ADC codes along the
  chain — exactly what Domino's Rofm adds "on the move".  Codes are
  small integers, hence exact in float64, so every executor-level
  association order yields identical bits: interp == trace == streaming
  under quantization *by construction*;
* :class:`PallasEngine` — the same quantization state, but the integer
  dot + ADC runs through the Pallas kernel
  (``kernels/cim_matmul.py::cim_matmul_pallas``, compiled on a TPU and
  interpreted elsewhere) on every executor path, the jitted trace path
  included.  Each tile call is one kernel subarray step, so its ADC
  codes are bitwise-identical to :class:`CIMEngine`'s.

ADC-code equality across the jnp / numpy / Pallas flavors holds because
all three compute the conversion identically: the exact integer dot is
cast ``int32 -> float32``, multiplied by the ``float32`` inverse step,
rounded half-to-even and saturated (see :meth:`CIMEngine._adc` and the
kernel body).

Calibration (the paper's per-layer integration-gain knob): a float
forward pass captures each layer's input (``models/cnn.py::
collect_layer_inputs``), from which the engine derives the per-layer
activation scale (w8a8's ``a_scale``) and runs
:func:`repro.core.cim.calibrate_gain` once at network build.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.cim import (
    CIMSpec,
    DEFAULT_SPEC,
    adc_convert,
    calibrate_gain,
    quantize_symmetric,
)
from repro.core.variation import VariationModel
from repro.telemetry.spans import count_device_call, span

#: engine registry keys accepted by ``make_engine`` / ``NetworkSimulator``
ENGINES = ("exact", "cim", "pallas")

#: elements per block of the blocked activation quantization
#: (:meth:`CIMEngine.quant_stream` with ``out``) and of the trace
#: executor's block tail: a 256 KiB float64 block buffer that stays in
#: the core's cache across its passes
_QBLOCK_ELEMS = 1 << 15


# ---------------------------------------------------------------------------
# Weight quantization shared by every quantized consumer (engines, the
# serving-side ``quantize_cnn_params_for_serving``): symmetric int8 with a
# per-output-column scale over the *flattened contraction* — (K*K*C, M)
# for conv kernels, (C_in, C_out) for FC — matching the crossbar layout.
# ---------------------------------------------------------------------------


def quantize_weight(w: np.ndarray, bits: int = 8
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """(K, K, C, M) or (C_in, C_out) float -> (q int8 same shape, s (M,)).

    Pure numpy, elementwise-identical to ``quantize_symmetric`` in f32
    (max / divide / round-half-even / clip are the same IEEE ops) — VGG's
    100M-element FC matrices quantize in milliseconds at network build
    instead of round-tripping through a per-shape jit.  ``bits`` scales
    the signed integer grid (``<= 8``; codes stay int8-resident — the
    bit-scalable precision lever of the per-layer DSE axis)."""
    if not 2 <= bits <= 8:
        raise ValueError(f"w_bits must be in [2, 8] (int8 storage): {bits}")
    q_max = 2 ** (bits - 1) - 1
    w32 = np.asarray(w, np.float32).reshape(-1, np.asarray(w).shape[-1])
    amax = np.max(np.abs(w32), axis=0, keepdims=True)
    s = np.maximum(amax, np.float32(1e-8)) / np.float32(q_max)
    q = np.clip(np.round(w32 / s), -q_max - 1, q_max).astype(np.int8)
    return (q.reshape(np.shape(w)),
            np.asarray(s, np.float64).reshape(np.shape(w)[-1]))


def dequantize_weight(q: np.ndarray, s: np.ndarray) -> np.ndarray:
    """Inverse of :func:`quantize_weight` (float64 view for exact paths
    and calibration)."""
    return np.asarray(q, np.float64) * np.asarray(s, np.float64).reshape(-1)


def is_quantized_leaf(leaf) -> bool:
    """A ``{"q", "s"}`` dict leaf — the CIM-resident serving format."""
    return isinstance(leaf, dict) and "q" in leaf and "s" in leaf


# ---------------------------------------------------------------------------
# Per-layer engine state (handles)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class TileTaps:
    """One tile's weight slice: which taps / channel slice it holds."""

    tap_row: int
    tap_col: int
    pack: int
    c_lo: int
    c_hi: int  # resolved (never None)


def conv_tile_slices(sched) -> Tuple[TileTaps, ...]:
    """The tile -> weight-slice map of a compiled ``BlockSchedule``."""
    out = []
    for prog in sched.tiles:
        c_hi = prog.c_hi if prog.c_hi is not None else sched.c_in
        out.append(TileTaps(prog.tap_row, prog.tap_col, prog.pack,
                            prog.c_lo, c_hi))
    return tuple(out)


@dataclass
class ConvHandle:
    """Engine-domain state for one conv layer's tile chain."""

    name: str
    c_out: int
    tile_w: List[np.ndarray]            # per tile (pack, Cs, M) float64
    # quantized extras (None on the exact engine)
    tile_w8: Optional[List[np.ndarray]] = None  # per tile (pack, Cs, M) int8
    deq: Optional[np.ndarray] = None    # (M,) code -> float multiplier
    a_scale: float = 1.0
    a_clip: float = 127.0               # activation code saturation
    inv_step32: Optional[np.float32] = None
    code_lo: float = 0.0
    code_hi: float = 0.0
    spec: Optional[CIMSpec] = None      # per-layer spec (calibrated gain)
    # batch-of-tiles view (quantized engines): every tile's resident
    # weights stacked on a zero-padded common contraction depth, so the
    # fused trace path runs ONE (T, R, kc) x (T, kc, M) batched exact
    # integer gemm + one vectorized ADC conversion for the whole layer
    kc: Optional[Tuple[int, ...]] = None      # per-tile pack * C_slice
    w_stack: Optional[np.ndarray] = None      # (T, max kc, M) float64
    w8_stack: Optional[np.ndarray] = None     # (T, max kc, M) int8
    w8_sub: Optional[np.ndarray] = None       # (T * n_c, M) int8 (Pallas)
    # per-subarray ADC variation (None = nominal scalar conversion):
    # float32 (T,) inverse step with gain error folded in, and the
    # comparator offset in code LSBs (see core/variation.py)
    adc_inv: Optional[np.ndarray] = None
    adc_off: Optional[np.ndarray] = None


@dataclass
class FCHandle:
    """Engine-domain state for one FC layer's tile grid."""

    name: str
    w: np.ndarray                       # (C_in, C_out) float64 (engine domain)
    w8: Optional[np.ndarray] = None     # int8 flavor (Pallas)
    deq: Optional[np.ndarray] = None
    a_scale: float = 1.0
    a_clip: float = 127.0
    inv_step32: Optional[np.float32] = None
    code_lo: float = 0.0
    code_hi: float = 0.0
    spec: Optional[CIMSpec] = None
    # per-subarray ADC variation over the FC grid, indexed by the global
    # subarray id ``k0 // n_c + i`` (grid tiles that straddle the same
    # n_c boundary share the same physical column ADC)
    adc_inv: Optional[np.ndarray] = None
    adc_off: Optional[np.ndarray] = None


@dataclass(frozen=True)
class LayerCalib:
    """Per-layer calibration: activation scale + ADC integration gain."""

    a_scale: float = 1.0
    gain: Optional[float] = None  # None = the spec's own gain


# ---------------------------------------------------------------------------
# The engines
# ---------------------------------------------------------------------------


class PEEngine:
    """Interface every executor MACs through.

    ``tile_mac`` is one conv tile's PE firing: the packed-tap window
    against the tile's resident weights, returning the value the tile
    transmits (a float psum for the exact engine, digitally-accumulable
    ADC codes for the quantized ones).  ``fc_mac`` is one FC grid tile's
    MVM slice.  ``finalize_*`` converts the digitally-accumulated total
    back to the real-valued domain at the block tail, *before* bias /
    activation / pooling.
    """

    name = "abstract"
    #: quantized engines need the per-layer calibration pass at build
    needs_calibration = False

    # -- conv ---------------------------------------------------------------
    def conv_handle(self, name: str, weights: np.ndarray,
                    tiles: Sequence[TileTaps],
                    prequant: Optional[Tuple[np.ndarray, np.ndarray]] = None
                    ) -> ConvHandle:
        raise NotImplementedError

    def tile_mac(self, h: ConvHandle, t: int, taps: Sequence[np.ndarray],
                 quantized: bool = False) -> np.ndarray:
        """taps[d]: (rows, Cs) float64 — the Rifm shift-buffer window
        (interp) or the gathered patch columns (trace), channel-sliced.
        Partial windows (row starts) pass fewer than ``pack`` taps.
        ``quantized=True`` marks taps already passed through
        :meth:`quant_stream` (skip per-tap quantization)."""
        raise NotImplementedError

    def finalize_conv(self, h: ConvHandle, acc: np.ndarray,
                      out: Optional[np.ndarray] = None) -> np.ndarray:
        """The identity; with ``out`` (float64, ``acc``'s shape), ``acc``
        copied there."""
        if out is None:
            return acc
        np.copyto(out, acc)
        return out

    # -- fc -----------------------------------------------------------------
    def fc_handle(self, name: str, w: np.ndarray,
                  prequant: Optional[Tuple[np.ndarray, np.ndarray]] = None
                  ) -> FCHandle:
        raise NotImplementedError

    def fc_mac(self, h: FCHandle, x: np.ndarray, k0: int, k1: int,
               n0: int, n1: int, quantized: bool = False) -> np.ndarray:
        raise NotImplementedError

    def finalize_fc(self, h: FCHandle, psum: np.ndarray,
                    n0: int, n1: int) -> np.ndarray:
        return psum

    # -- activation-domain hook ---------------------------------------------
    def quant_stream(self, h, x: np.ndarray) -> np.ndarray:
        """Convert an activation stream into the engine's input domain
        ONCE per run (identity on the exact engine; static per-layer
        int quantization on the quantized ones).  Executors that call
        this pass ``quantized=True`` to ``tile_mac``/``fc_mac`` so the
        same pixel is not re-quantized per (tile, tap) — quantization
        is elementwise with a static scale, so it commutes with the
        gather/slice and the bits are identical either way."""
        return x

    # -- calibration (no-op on the exact engine) ----------------------------
    def calibrate_layer(self, name: str, x: np.ndarray,
                        w: np.ndarray) -> None:
        pass


class ExactEngine(PEEngine):
    """The pre-engine float64 path, bit-for-bit: zeros accumulator, one
    ``gemm_rows`` per packed tap (row-position-invariant BLAS), identity
    finalization."""

    name = "exact"

    def __init__(self):
        # one-slot gemm scratch: within a block run every tile_mac has the
        # same (rows, M), so the product buffer is reused across tiles
        self._skey: Optional[Tuple[int, int]] = None
        self._sbuf: Optional[np.ndarray] = None

    def conv_handle(self, name, weights, tiles, prequant=None):
        if prequant is not None:
            weights = dequantize_weight(*prequant)
        weights = np.asarray(weights, np.float64)
        tile_w = [
            np.asarray(weights[tt.tap_row, tt.tap_col:tt.tap_col + tt.pack,
                               tt.c_lo:tt.c_hi], np.float64)
            for tt in tiles
        ]
        return ConvHandle(name=name, c_out=weights.shape[-1], tile_w=tile_w)

    def _scratch(self, rows: int, cols: int) -> np.ndarray:
        key = (rows, cols)
        if self._skey != key:
            self._skey, self._sbuf = key, np.empty(key, np.float64)
        return self._sbuf

    def tile_mac(self, h, t, taps, quantized=False):
        from repro.core.simulator import gemm_rows

        w = h.tile_w[t]
        acc = buf = None
        for d, px in enumerate(taps):
            if acc is None:
                acc = np.zeros((px.shape[0], h.c_out), np.float64)
                buf = self._scratch(px.shape[0], h.c_out)
            gemm_rows(px, w[d], out=buf)
            acc += buf
        return acc

    def fc_handle(self, name, w, prequant=None):
        if prequant is not None:
            w = dequantize_weight(*prequant)
        return FCHandle(name=name, w=np.asarray(w, np.float64))

    def fc_mac(self, h, x, k0, k1, n0, n1, quantized=False):
        from repro.core.simulator import gemm_rows

        return gemm_rows(x, h.w[k0:k1, n0:n1])


class CIMEngine(PEEngine):
    """w8a8 + per-subarray SAR ADC, digitally accumulated (paper §4.5).

    One conv tile is one crossbar subarray (``pack * C_slice <= n_c`` by
    the planner), so ``tile_mac`` is: quantize the window with the
    layer's static activation scale, take the *exact* integer dot over
    the tile's resident int8 weights, and convert once through the ADC.
    The returned codes are integers (exact in float64), so chain/group/
    batch association order cannot change a single bit — the quantized
    pipeline inherits every bitwise executor guarantee for free.
    """

    name = "cim"
    needs_calibration = True

    #: default activation-clip percentile (xBARSimV1-style percentile
    #: clipping): the max-based scale let one outlier pixel stretch the
    #: int8 range and starve every other activation of resolution
    CLIP_PERCENTILE = 99.9

    def __init__(self, spec: CIMSpec = DEFAULT_SPEC,
                 use_calibrated_gain: bool = True,
                 clip_percentile: Optional[float] = None,
                 variation: Optional[VariationModel] = None):
        self.spec = spec
        self.use_calibrated_gain = use_calibrated_gain
        self.clip_percentile = (self.CLIP_PERCENTILE if clip_percentile
                                is None else float(clip_percentile))
        if not 0.0 < self.clip_percentile <= 100.0:
            raise ValueError(
                f"clip_percentile must be in (0, 100]: {clip_percentile}")
        self.calib: Dict[str, LayerCalib] = {}
        #: per-layer bit-scalable spec overrides (kept OUT of ``calib``
        #: so ``calibrate_engine``'s already-calibrated skip still works)
        self.layer_specs: Dict[str, CIMSpec] = {}
        #: per-layer activation-clip percentile overrides (satellite of
        #: the precision search: the global 99.9 is wrong for layers
        #: whose activation tails carry signal)
        self.clip_overrides: Dict[str, float] = {}
        #: device-variation model injected into every handle built after
        #: it is set (``None`` = ideal arithmetic; swap via
        #: ``NetworkSimulator.set_variation`` for Monte-Carlo trials)
        self.variation = variation

    # -- calibration ---------------------------------------------------------

    def set_layer(self, name: str, a_scale: float = 1.0,
                  gain: Optional[float] = None) -> "CIMEngine":
        self.calib[name] = LayerCalib(a_scale=a_scale, gain=gain)
        return self

    def set_layer_spec(self, name: str, *, w_bits: Optional[int] = None,
                       a_bits: Optional[int] = None,
                       adc_bits: Optional[int] = None,
                       clip_percentile: Optional[float] = None
                       ) -> "CIMEngine":
        """Per-layer bit-scalable precision / calibration override.

        Replaces the named layer's ``(w_bits, a_bits, adc_bits)`` on top
        of the engine-wide spec (geometry — ``n_c``/``n_m``/``gain`` —
        stays shared) and optionally its activation-clip percentile.
        Must be set before handles are built / calibration runs."""
        base = self.layer_specs.get(name, self.spec)
        kw = {}
        if w_bits is not None:
            kw["w_bits"] = int(w_bits)
        if a_bits is not None:
            kw["a_bits"] = int(a_bits)
        if adc_bits is not None:
            kw["adc_bits"] = int(adc_bits)
        if kw:
            self.layer_specs[name] = replace(base, **kw)
        if clip_percentile is not None:
            cp = float(clip_percentile)
            if not 0.0 < cp <= 100.0:
                raise ValueError(
                    f"clip_percentile must be in (0, 100]: {cp}")
            self.clip_overrides[name] = cp
        return self

    def _base_spec(self, name: str) -> CIMSpec:
        return self.layer_specs.get(name, self.spec)

    def calibrate_layer(self, name, x, w):
        """Derive (a_scale, gain) from one layer's captured float input.

        ``a_scale`` fills the int8 activation range with the
        ``clip_percentile`` of observed magnitudes (percentile clipping:
        the rare outlier saturates instead of stretching the whole
        range — SNIPPETS.md snippet 1 / xBARSimV1 style); ``gain`` runs
        the paper's integration-gain calibration over the layer's
        im2col'd contraction (conv kernels are flattened the same way
        ``models/cnn.py`` feeds the CIM reference)."""
        spec = self._base_spec(name)
        clip = self.clip_overrides.get(name, self.clip_percentile)
        x = np.asarray(x, np.float32)
        mags = np.abs(x)
        if clip >= 100.0:
            a_obs = float(np.max(mags))
        else:
            a_obs = float(np.percentile(mags, clip))
        a_scale = max(a_obs / spec.a_max, 1e-8)
        gain = None
        if self.use_calibrated_gain:
            cols, wmat = _calibration_matrix(x, np.asarray(w, np.float32))
            if wmat.shape[1] > _CALIB_COLS:
                # weight columns quantize independently (per-column
                # scales), so a deterministic column stride is
                # self-consistent — it just reads fewer ADC channels
                wmat = wmat[:, ::math.ceil(wmat.shape[1] / _CALIB_COLS)]
            gain = calibrate_gain(cols, wmat, spec)
        self.calib[name] = LayerCalib(a_scale=a_scale, gain=gain)

    def _layer_spec(self, name: str) -> Tuple[CIMSpec, float]:
        cal = self.calib.get(name, LayerCalib())
        spec = self._base_spec(name)
        if cal.gain is not None and self.use_calibrated_gain:
            spec = replace(spec, gain=cal.gain)
        return spec, cal.a_scale

    # -- device variation ----------------------------------------------------

    def _perturbed(self, name: str, q: np.ndarray, spec: CIMSpec
                   ) -> np.ndarray:
        """Apply weight-cell variation to the FULL quantized tensor,
        before tile slicing — every derived view (per-tile, stacked,
        Pallas operand) then sees identical integers, preserving the
        nominal path's engine-equality invariants under fault."""
        vm = self.variation
        if vm is None or not vm.has_weight:
            return q
        return vm.perturb_weights(name, q, spec.w_max)

    def _adc_variation(self, name: str, n_sub: int, spec: CIMSpec):
        vm = self.variation
        if vm is None or not vm.has_adc:
            return None, None
        return vm.adc_params(name, n_sub, float(spec.adc_inv_step))

    # -- handles -------------------------------------------------------------

    def _common(self, name: str, s_w: np.ndarray):
        spec, a_scale = self._layer_spec(name)
        # code -> float: ADC step back to dot units, then the w8a8 scales
        deq = (spec.adc_step * a_scale) * np.asarray(s_w, np.float64)
        return dict(
            deq=deq, a_scale=a_scale, a_clip=float(spec.a_max),
            inv_step32=np.float32(spec.adc_inv_step),
            code_lo=float(-spec.q_max - 1), code_hi=float(spec.q_max),
            spec=spec,
        )

    def conv_handle(self, name, weights, tiles, prequant=None):
        spec, _ = self._layer_spec(name)
        if prequant is not None and spec.w_bits == 8:
            q, s = np.asarray(prequant[0]), np.asarray(prequant[1])
            s = np.asarray(s, np.float64).reshape(-1)
        else:
            # per-layer w_bits below the serving format's 8: requantize
            # from the float weights onto the narrower grid
            with span("engine.quantize_w", cat="engine", layer=name):
                q, s = quantize_weight(weights, spec.w_bits)
        with span("engine.draw", cat="engine", layer=name):
            q = self._perturbed(name, q, spec)
            adc_inv, adc_off = self._adc_variation(name, len(tiles), spec)
        tile_q = [
            np.ascontiguousarray(
                q[tt.tap_row, tt.tap_col:tt.tap_col + tt.pack,
                  tt.c_lo:tt.c_hi])
            for tt in tiles
        ]
        for tt, tq in zip(tiles, tile_q):
            if tt.pack * (tt.c_hi - tt.c_lo) > self.spec.n_c:
                raise ValueError(
                    f"{name}: tile holds {tt.pack}x{tt.c_hi - tt.c_lo} "
                    f"weight rows > n_c={self.spec.n_c} — not one subarray")
        # batch-of-tiles view: each tile's (pack * Cs, M) weight slab on a
        # zero-padded common depth — padded rows contribute nothing to the
        # exact integer dot, so the fused path's codes match the per-tile
        # path's bit-for-bit.  Dots are exact in f32 whenever the
        # subarray full-scale fits f32's integer range (n_c <= 1024 at
        # w8a8) — half the BLAS traffic of f64 for bit-identical codes
        m = q.shape[-1]
        dot_dt = np.float32 if spec.full_scale <= 2 ** 24 else np.float64
        kc = tuple(tt.pack * (tt.c_hi - tt.c_lo) for tt in tiles)
        w_stack = np.zeros((len(tiles), max(kc), m), dot_dt)
        for i, tq in enumerate(tile_q):
            w_stack[i, :kc[i]] = tq.reshape(kc[i], m)
        return ConvHandle(
            name=name, c_out=m,
            tile_w=[tq.astype(np.float64) for tq in tile_q],
            tile_w8=[tq.astype(np.int8) for tq in tile_q],
            kc=kc, w_stack=w_stack,
            w8_stack=w_stack.astype(np.int8),
            adc_inv=adc_inv, adc_off=adc_off,
            **self._common(name, s),
        )

    def fc_handle(self, name, w, prequant=None):
        spec, _ = self._layer_spec(name)
        if prequant is not None and spec.w_bits == 8:
            q, s = np.asarray(prequant[0]), np.asarray(prequant[1])
            s = np.asarray(s, np.float64).reshape(-1)
        else:
            with span("engine.quantize_w", cat="engine", layer=name):
                q, s = quantize_weight(w, spec.w_bits)
        # one physical per-subarray ADC every n_c weight rows; grid tiles
        # index into this shared pool by k0 // n_c (see fc_mac)
        n_alloc = 2 * math.ceil(q.shape[0] / spec.n_c) + 1
        with span("engine.draw", cat="engine", layer=name):
            q = self._perturbed(name, q, spec)
            adc_inv, adc_off = self._adc_variation(name, n_alloc, spec)
        return FCHandle(name=name, w=q.astype(np.float64),
                        w8=q.astype(np.int8),
                        adc_inv=adc_inv, adc_off=adc_off,
                        **self._common(name, s))

    # -- the numerics --------------------------------------------------------

    def _quant(self, x: np.ndarray, h) -> np.ndarray:
        """Static per-layer activation quantization (int-valued f64)."""
        return np.clip(np.round(x / h.a_scale), -h.a_clip - 1, h.a_clip)

    def _adc(self, d: np.ndarray, h, t: Optional[int] = None) -> np.ndarray:
        """The SAR conversion, bit-for-bit the jnp/Pallas arithmetic —
        the shared :func:`repro.core.cim.adc_convert` (exact int dot ->
        int32 -> float32, scale by the f32 inverse step, round
        half-to-even, saturate).  ``t`` selects the tile's per-subarray
        ADC parameters when a variation model is attached."""
        if h.adc_inv is None:
            return adc_convert(d, h.inv_step32, h.code_lo, h.code_hi)
        i = 0 if t is None else t
        return adc_convert(d, h.adc_inv[i], h.code_lo, h.code_hi,
                           h.adc_off[i])

    def quant_stream(self, h, x, out=None):
        """Without ``out``: :meth:`_quant` (int-valued float64).  With
        ``out`` (int8, ``x``'s shape, any strides — the trace executor's
        raster interior): one blocked pass writes the codes there, the
        same bits as ``self._quant(x, h).astype(np.int8)``.  Each block of
        ``_QBLOCK_ELEMS`` elements is divided, rounded half-to-even and
        saturated in place in one reused float64 buffer, so no full-size
        float64 temporary exists; the division is float64 even for a
        float32 ``x``, as the float64 padded copy made it.  Saturation is
        ``np.maximum`` then ``np.minimum``: ``np.clip``'s values at half
        its cost."""
        if out is None:
            return self._quant(x, h)
        assert out.shape == x.shape and out.dtype == np.int8, out
        lo, hi = -h.a_clip - 1, h.a_clip
        b, rows, row_shape = x.shape[0], x.shape[1], x.shape[2:]
        row = math.prod(row_shape)
        step = max(1, _QBLOCK_ELEMS // row)
        tmp = np.empty(min(rows, step) * row, np.float64)
        for i in range(b):
            for r0 in range(0, rows, step):
                r1 = min(rows, r0 + step)
                blk = tmp[:(r1 - r0) * row].reshape((r1 - r0,) + row_shape)
                np.divide(x[i, r0:r1], h.a_scale, out=blk, dtype=np.float64)
                np.rint(blk, out=blk)
                np.maximum(blk, lo, out=blk)
                np.minimum(blk, hi, out=blk)
                out[i, r0:r1] = blk
        return out

    def tile_mac(self, h, t, taps, quantized=False):
        from repro.core.simulator import gemm_rows

        w = h.tile_w[t]
        d = None
        for i, px in enumerate(taps):
            if not quantized:
                px = self._quant(px, h)
            p = gemm_rows(px, w[i])
            d = p if d is None else d + p  # exact ints: order-free
        return self._adc(d, h, t)

    def tiles_mac(self, h, patches):
        """Batch-of-tiles MAC — the fused trace path's one call per
        layer chunk.  ``patches``: (T, R, max kc) int-valued float64,
        already quantized, zero-beyond-``h.kc[t]`` irrelevant (the
        stacked weights are zero there).  One batched exact integer
        gemm (f32/f64 BLAS is exact for these magnitudes — the stacked
        weights' dtype encodes which), ONE vectorized ADC conversion
        across all T subarrays, then the digital code sum — integers
        exact in f64, so this equals the per-tile chain/group fold
        bit-for-bit in any association order."""
        d = np.matmul(patches, h.w_stack)            # (T, R, M) exact dots
        if h.adc_inv is None:
            codes = adc_convert(d, h.inv_step32, h.code_lo, h.code_hi)
        else:
            codes = adc_convert(d, h.adc_inv[:, None, None],
                                h.code_lo, h.code_hi,
                                h.adc_off[:, None, None])
        return codes.sum(axis=0)

    def tiles_mac_fn(self, h):
        """The jit flavor of :meth:`tiles_mac`, for ``TraceExecutor``'s
        jitted path: ``(fn, operands)`` where ``fn(x, *operands)`` is
        traceable, ``x`` the (T, R, max kc) int8 patch stack, and the
        result the (R, M) code sums.  One batched ``lax.dot_general``
        with int32 accumulation, the shared f32 ADC conversion, and an
        exact int32 code sum (a zero sum is +0.0 on the host), all under
        the ``cim_mac`` named scope."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from repro.core.cim import adc_convert_jnp

        if h.adc_inv is None:
            inv, off = np.float32(h.inv_step32), None
        else:
            # per-subarray ADC variation rides the same fused dot: the
            # (T,) arrays broadcast over the (T, R, M) dot tensor
            inv = np.asarray(h.adc_inv, np.float32).reshape(-1, 1, 1)
            off = np.asarray(h.adc_off, np.float32).reshape(-1, 1, 1)
        clo, chi = np.float32(h.code_lo), np.float32(h.code_hi)

        def fn(x, w8):
            with jax.named_scope("cim_mac"):
                d = lax.dot_general(x, w8, (((2,), (1,)), ((0,), (0,))),
                                    preferred_element_type=jnp.int32)
                return adc_convert_jnp(d, inv, clo, chi, off).sum(axis=0)

        return fn, (h.w8_stack,)

    def finalize_conv(self, h, acc, out=None):
        """Code sums times ``deq``, in float64 whatever exact-integer dtype
        ``acc`` has; with ``out`` (float64, ``acc``'s shape) written
        there."""
        return np.multiply(acc, h.deq, out=out, dtype=np.float64)

    def fc_mac(self, h, x, k0, k1, n0, n1, quantized=False):
        xq = x if quantized else self._quant(x, h)
        w = h.w[k0:k1, n0:n1]
        # the FC grid tile holds (k1 - k0) weight rows; when the spec's
        # subarray is smaller, the tile spans several subarrays — one
        # conversion each, codes accumulated digitally (matching the
        # Pallas kernel's n_c-wide K steps bit-for-bit).  All subarrays
        # convert in ONE vectorized call: zero-padding K to a multiple
        # of n_c adds nothing to the exact dots, and the f64 code sum
        # is association-order-free (small integers)
        n_c = h.spec.n_c
        kk = k1 - k0
        pad = (-kk) % n_c
        if pad:
            xq = np.concatenate(
                [xq, np.zeros((xq.shape[0], pad), xq.dtype)], axis=1)
            w = np.concatenate(
                [w, np.zeros((pad, w.shape[1]), w.dtype)], axis=0)
        n_sub = (kk + pad) // n_c
        xs = xq.reshape(-1, n_sub, n_c).transpose(1, 0, 2)
        ws = w.reshape(n_sub, n_c, -1)
        d = np.matmul(xs, ws)                # (n_sub, B, N) exact dots
        if h.adc_inv is None:
            codes = adc_convert(d, h.inv_step32, h.code_lo, h.code_hi)
        else:
            sub = k0 // n_c + np.arange(n_sub)
            codes = adc_convert(d, h.adc_inv[sub, None, None],
                                h.code_lo, h.code_hi,
                                h.adc_off[sub, None, None])
        return codes.sum(axis=0)

    def finalize_fc(self, h, psum, n0, n1):
        return psum * h.deq[n0:n1]


class PallasEngine(CIMEngine):
    """CIM numerics driven by the Pallas kernel: each tile/FC-grid MAC is
    one ``cim_matmul_pallas`` call whose single K-step *is* the tile's
    subarray (the kernel zero-pads K to ``n_c`` — padding rows contribute
    nothing to the exact dot), emitting raw ADC codes.  Bitwise-identical
    codes to :class:`CIMEngine` by construction.  The kernel is compiled
    on a TPU and interpreted elsewhere
    (``kernels/cim_matmul.py::interpret_mode``)."""

    name = "pallas"

    def _codes(self, xq8: np.ndarray, wq8: np.ndarray, spec: CIMSpec,
               adc_var: Optional[np.ndarray] = None) -> np.ndarray:
        import jax.numpy as jnp

        from repro.kernels.cim_matmul import cim_matmul_pallas

        codes = cim_matmul_pallas(
            jnp.asarray(xq8), jnp.asarray(wq8), spec, emit_codes=True,
            adc_var=None if adc_var is None else jnp.asarray(adc_var))
        count_device_call((xq8, wq8, adc_var), codes)
        return np.asarray(codes, np.float64)

    def tile_mac(self, h, t, taps, quantized=False):
        n = len(taps)
        if not quantized:
            taps = [self._quant(px, h) for px in taps]
        xq = np.concatenate(taps, axis=1).astype(np.int8)
        wq = h.tile_w8[t][:n].reshape(-1, h.c_out)
        av = None
        if h.adc_inv is not None:  # one tile == one subarray == one K step
            av = np.stack([h.adc_inv[t:t + 1], h.adc_off[t:t + 1]], axis=1)
        return self._codes(xq, wq, h.spec, av)

    def _chain_operands(self, h):
        """A tile chain's kernel operands: the (T * n_c, M) int8 weights,
        each tile's ``kc``-row slab zero-padded to its own ``n_c``-row K
        block, and the (T, 2) per-subarray ADC table (None = nominal) —
        kernel K step i is chain tile i."""
        if h.w8_sub is None:
            t, kcm, m = h.w8_stack.shape
            sub = np.zeros((t, h.spec.n_c, m), np.int8)
            sub[:, :kcm] = h.w8_stack
            h.w8_sub = sub.reshape(t * h.spec.n_c, m)
        av = None
        if h.adc_inv is not None:
            av = np.stack([h.adc_inv, h.adc_off], axis=1)
        return h.w8_sub, av

    def tiles_mac(self, h, patches):
        """Batch-of-tiles MAC through ONE multi-tile ``emit_codes``
        kernel invocation: each tile's ``kc`` activation columns land in
        its own ``n_c``-wide K block (weights zero-padded past ``kc``),
        so each kernel K grid step is exactly one chain tile's subarray
        and the kernel's in-VMEM code accumulation IS the chain/group
        digital fold — bitwise-identical to :meth:`CIMEngine.tiles_mac`."""
        from repro.kernels.cim_matmul import cim_chain_codes_pallas

        t, r, kcm = patches.shape
        n_c = h.spec.n_c
        w, av = self._chain_operands(h)
        x = np.zeros((r, t, n_c), np.int8)
        x[:, :, :kcm] = patches.transpose(1, 0, 2)
        codes = cim_chain_codes_pallas(x.reshape(r, t * n_c), w, h.spec,
                                       adc_var=av)
        count_device_call((x, w, av), codes)
        return np.asarray(codes, np.float64)

    def tiles_mac_fn(self, h):
        """The jit flavor of :meth:`tiles_mac`: the same n_c-block
        layout, built on the traced patch stack, into the same kernel
        (under the ``cim_mac`` named scope)."""
        import jax
        import jax.numpy as jnp

        from repro.kernels.cim_matmul import cim_chain_codes_pallas

        spec = h.spec

        def fn(x, w, av):
            t, r, kcm = x.shape
            x = jnp.pad(x, ((0, 0), (0, 0), (0, spec.n_c - kcm)))
            x = x.transpose(1, 0, 2).reshape(r, t * spec.n_c)
            with jax.named_scope("cim_mac"):
                return cim_chain_codes_pallas(x, w, spec, adc_var=av)

        return fn, self._chain_operands(h)

    def fc_mac(self, h, x, k0, k1, n0, n1, quantized=False):
        xq = (x if quantized else self._quant(x, h)).astype(np.int8)
        av = None
        if h.adc_inv is not None:
            # the kernel zero-pads K to n_c exactly like CIMEngine.fc_mac,
            # so K step i is global subarray k0 // n_c + i
            n_sub = -(-(k1 - k0) // h.spec.n_c)
            sub = k0 // h.spec.n_c + np.arange(n_sub)
            av = np.stack([h.adc_inv[sub], h.adc_off[sub]], axis=1)
        return self._codes(xq, np.ascontiguousarray(h.w8[k0:k1, n0:n1]),
                           h.spec, av)


#: module-level default — the drop-in for every pre-engine call site
EXACT_ENGINE = ExactEngine()


def make_engine(engine, cim_spec: Optional[CIMSpec] = None) -> PEEngine:
    """Resolve an engine selection (name or instance) to a ``PEEngine``."""
    if isinstance(engine, PEEngine):
        if cim_spec is not None:
            raise ValueError(
                "pass cim_spec only with an engine *name*; an engine "
                "instance already carries its spec")
        return engine
    if engine == "exact":
        if cim_spec is not None:
            raise ValueError("cim_spec has no effect on the exact engine")
        return ExactEngine()
    spec = cim_spec if cim_spec is not None else DEFAULT_SPEC
    if engine == "cim":
        return CIMEngine(spec)
    if engine == "pallas":
        return PallasEngine(spec)
    raise ValueError(f"engine must be one of {ENGINES}: {engine!r}")


# ---------------------------------------------------------------------------
# Calibration driver
# ---------------------------------------------------------------------------

#: cap on im2col rows fed to calibrate_gain (deterministic stride
#: subsample — calibration reads magnitudes, not every pixel)
_CALIB_ROWS = 4096
#: cap on weight columns fed to calibrate_gain (per-column quantization
#: makes a column subsample self-consistent)
_CALIB_COLS = 512


def _calibration_matrix(x: np.ndarray, w: np.ndarray
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """(layer input, weight) -> (im2col'd activations, flat weight matrix)
    in the same (C, K, K) feature order ``models/cnn.py`` uses.

    Pure numpy, and the row subsample happens *before* patch extraction
    (the stride walks the same flattened (b, y, x) positions the old
    full-tensor im2col kept), so calibration cost is bounded by
    ``_CALIB_ROWS`` windows per layer instead of materializing the whole
    k*k*C patch tensor — at ImageNet sizes that one change takes network
    build from minutes to seconds."""
    if w.ndim == 2:
        cols = x.reshape(-1, x.shape[-1])
        if cols.shape[0] > _CALIB_ROWS:
            cols = cols[::math.ceil(cols.shape[0] / _CALIB_ROWS)]
        return cols, w
    k, _, c, m = w.shape
    b, h, wd, _ = x.shape
    total = b * h * wd
    # magnitudes, not geometry: unit stride + SAME padding samples densest
    # and never yields an empty patch set (late layers can be smaller than
    # their kernel)
    step = math.ceil(total / _CALIB_ROWS) if total > _CALIB_ROWS else 1
    idx = np.arange(0, total, step)
    bi, rest = np.divmod(idx, h * wd)
    yi, xi = np.divmod(rest, wd)
    lo = (k - 1) // 2
    xp = np.zeros((b, h + k - 1, wd + k - 1, c), np.float32)
    xp[:, lo:lo + h, lo:lo + wd] = x
    dy, dx = np.meshgrid(np.arange(k), np.arange(k), indexing="ij")
    # (rows, k, k, C) windows at the sampled centres
    win = xp[bi[:, None, None], yi[:, None, None] + dy[None],
             xi[:, None, None] + dx[None]]
    cols = win.transpose(0, 3, 1, 2).reshape(len(idx), -1)  # (C, K, K) order
    return cols, w.transpose(2, 0, 1, 3).reshape(-1, m)


def calibrate_engine(engine: PEEngine, cnn, params: Dict[str, np.ndarray],
                     images: np.ndarray) -> None:
    """Run the float forward on ``images``, capture every layer's input
    and hand each (input, weight) pair to the engine's per-layer
    calibration.  Layers the engine already knows are left alone (a
    pre-calibrated engine instance can be reused across simulators)."""
    if not engine.needs_calibration:
        return
    todo = [l.name for l in cnn.layers if l.name not in
            getattr(engine, "calib", {})]
    if not todo:
        return
    import jax.numpy as jnp

    from repro.models.cnn import collect_layer_inputs
    from repro.telemetry.spans import span

    with span(f"calibrate:{cnn.name}", engine=engine.name, layers=len(todo)):
        p32 = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        inputs = collect_layer_inputs(p32, jnp.asarray(images, jnp.float32),
                                      cnn)
        for name in todo:
            engine.calibrate_layer(name, np.asarray(inputs[name]),
                                   params[name])
