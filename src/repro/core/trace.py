"""Trace compiler: the Domino simulator's vectorized fast path.

The per-cycle interpreter (``core/simulator.py``) executes a compiled
:class:`~repro.core.schedule.BlockSchedule` one ``(tile, cycle)`` event
at a time — a Python loop over ``cycles x tiles`` that dominates
whole-network wall time (VGG-11 places 918 tiles).  This module lowers
the *same* schedule into a **trace plan** executed as a handful of
batched gather/gemm ops, bitwise-equal to the interpreter:

* :func:`compile_trace` decodes each tile's periodic instruction table
  (the MAC phases are read from the emitted ``FROM_PE`` words, the Rifm
  row gate from the positional controller) and precomputes

  - the ``(tile, tap) -> padded-pixel flat-index`` gather arrays — the
    pixel each MAC event reads from the raster stream,
  - the Rifm row/column gates as dense boolean masks (``row_mask`` over
    padded rows, ``phase_mask`` over table phases),
  - the chain/group reduction pattern as ordered tile segments (the
    segment-sum the Rofm adders perform "on the move"),
  - the analytic event counts (MACs, buffer ops, instruction fetches)
    and routed send links that the interpreter would tally per cycle;

* :class:`TraceExecutor` runs the plan: per tile one gather + ``pack``
  gemms, then the segment fold in exact interpreter order (own MAC +
  west psum, chain total + north group-sum), tail bias/activation/pool
  — numpy by default, ``jax.jit`` behind the ``use_jax`` flag.

Quantized engines (``engine="cim"``/``"pallas"``) take a **fused
integer-native lowering** of the same plan instead of the per-tile
loop: all T tiles' gathers feed one zero-padded ``(T, rows, kc)`` patch
tensor, the engine's batch-of-tiles MAC runs one batched exact integer
gemm against the stacked resident weights, the per-subarray SAR ADC
conversion vectorizes across *all* tiles of the layer at once (one
:func:`repro.core.cim.adc_convert` call per chunk instead of one Python
call per tile), and the chain/group segment fold collapses to a single
code sum over the tile axis.  This is bitwise-equal to the per-tile
fold *by construction*: ADC codes are small integers exact in float64,
so association order cannot change a bit — ``fused=False`` keeps the
per-tile reference path alive for the equality tests.  ``use_jax=True``
on a quantized engine selects the jit flavor — int8 gathers +
``lax.dot_general(..., preferred_element_type=int32)`` + the shared f32
conversion — which, unlike the exact engine's float32 jit, is *also*
bitwise (every op is exact-integer or the shared elementwise
conversion), so it composes with streaming.

Bitwise equality holds because every float op is replayed in the
interpreter's association order: the per-pixel ``(B, C) @ (C, M)`` MACs
become one ``(B*E*F, C) @ (C, M)`` gemm (same sequential k-reduction
per output element), and the psum/group-sum adds keep their exact
operand order.  ``tests/test_trace.py`` asserts OFM, ``SimCounters``
and ``TrafficCounters`` equality across every ``CNN_BENCHMARKS`` conv
geometry; the interpreter stays the oracle.  Every matrix product goes
through :func:`~repro.core.simulator.gemm_rows`, which pads remainder
row blocks so BLAS's k-reduction order is row-position invariant
(OpenBLAS would otherwise hand short operands to gemv/edge kernels
with a different order) — so the guarantee is bitwise at *every* batch
size, including unbatched ``B == 1`` runs with inexact float data, and
a sample's bits never depend on its batch neighbours.

``SimCounters``/``TrafficCounters`` are derived analytically from the
plan — hop counts still come from :meth:`MeshNoC.route` via the shared
transport layer (``NoCTransport.record_bulk``), exactly as the
interpreter's routed sends do.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np

from repro.core.instructions import BUF_PUSH, FROM_PE, Instruction, Port
from repro.core.schedule import BlockSchedule
from repro.core.simulator import SimCounters, _standalone_transport
from repro.core.transport import CHAIN, GROUP, PSUM_BYTES, NoCTransport
from repro.telemetry.spans import span


@dataclass(frozen=True)
class TileTrace:
    """One tile's vectorized execution record, lowered from its table."""

    tile_id: int
    pack: int
    c_lo: int
    c_hi: int                     # resolved (never None)
    gather: np.ndarray            # (pack, E*F) int32 flat padded-pixel idx
    # the dense gate masks the gather arrays were built from — the
    # executor consumes only ``gather``; these stay on the plan so tests
    # and tooling can inspect/validate the lowering without re-deriving it
    row_mask: np.ndarray          # (Hp,) bool — Rifm positional row gate
    phase_mask: np.ndarray        # (period,) bool — MAC column phases
    has_north_buf: bool           # group tail folding a BUF_PUSH/POP pair
    dst_east: Optional[int]       # chain psum target (tx E), local id
    dst_south: Optional[int]      # group-sum target (tx S), local id


@dataclass(frozen=True)
class TracePlan:
    """A BlockSchedule lowered to gather/gemm form + analytic counters."""

    sched: BlockSchedule
    tiles: Tuple[TileTrace, ...]
    segments: Tuple[Tuple[int, int], ...]  # per-group [start, end) tile runs
    fires: int                    # MAC/send events per tile = E*F
    macs_per_fire: int            # sum over tiles of pack * C_slice * M
    n_pix: int                    # padded raster stream length Hp*Wp
    drain_cycles: int             # interpreter run length n_pix + 2*chain


def compile_trace(sched: BlockSchedule) -> TracePlan:
    """Lower a compiled schedule into a trace plan.

    Everything is derived from the schedule alone: MAC phases and send
    directions are *decoded from the emitted instruction words*, the row
    gate from the Rifm controller — so the plan executes the tables, not
    a re-derivation of the convolution.
    """
    s = sched
    e, f, wp, hp = s.e, s.f, s.wp, s.hp
    tiles: List[TileTrace] = []
    macs_per_fire = 0
    for prog in s.tiles:
        decoded = [Instruction.decode(wd) for wd in prog.table]
        phases = [ph for ph, ins in enumerate(decoded) if ins.has(FROM_PE)]
        assert len(phases) == f, (s.layer_name, prog.tile_id)
        phase_mask = np.zeros(wp, bool)
        phase_mask[phases] = True
        row_mask = np.fromiter(
            (prog.gate.row_active(r) for r in range(hp)), bool, hp)
        rows = np.flatnonzero(row_mask)          # the E gated padded rows
        assert rows.size == e, (s.layer_name, prog.tile_id)
        cols = np.asarray(phases, np.int64)      # the F MAC column phases
        # tap d reads the pixel `pack-1-d` slots back in the shift buffer
        gather = np.stack([
            (rows[:, None] * wp + (cols[None, :] - prog.pack + 1 + d)).ravel()
            for d in range(prog.pack)
        ]).astype(np.int32)
        c_hi = prog.c_hi if prog.c_hi is not None else s.c_in
        macs_per_fire += prog.pack * (c_hi - prog.c_lo) * s.c_out
        tiles.append(TileTrace(
            tile_id=prog.tile_id, pack=prog.pack, c_lo=prog.c_lo, c_hi=c_hi,
            gather=gather, row_mask=row_mask, phase_mask=phase_mask,
            has_north_buf=any(ins.has(BUF_PUSH) for ins in decoded),
            dst_east=prog.dst_east if any(
                ins.tx_to(Port.E) for ins in decoded) else None,
            dst_south=prog.dst_south if any(
                ins.tx_to(Port.S) for ins in decoded) else None,
        ))
    gs = s.group_size
    segments = tuple((g * gs, (g + 1) * gs) for g in range(s.k))
    hand = s.handoff
    return TracePlan(
        sched=s, tiles=tuple(tiles), segments=segments, fires=hand.out_elems,
        macs_per_fire=macs_per_fire, n_pix=hand.stream_len,
        drain_cycles=hand.stream_len + hand.drain,
    )


class TraceExecutor:
    """Drop-in fast path for :class:`~repro.core.simulator.BlockSimulator`.

    Same constructor shape and ``run`` contract; no per-cycle state, so
    one executor can serve many runs (``transport``/``counters`` may be
    reassigned between runs — the whole-network simulator does).
    """

    def __init__(self, sched: BlockSchedule, weights: np.ndarray,
                 bias: Optional[np.ndarray] = None,
                 transport: Optional[NoCTransport] = None,
                 counters: Optional[SimCounters] = None,
                 plan: Optional[TracePlan] = None,
                 use_jax: bool = False,
                 engine=None, handle=None,
                 fused: bool = True):
        from repro.core.engine import EXACT_ENGINE, conv_tile_slices

        k = sched.k
        assert weights.shape[:2] == (k, k)
        self.sched = sched
        self.bias = bias
        self.engine = engine if engine is not None else EXACT_ENGINE
        self.handle = handle if handle is not None else \
            self.engine.conv_handle(sched.layer_name, weights,
                                    conv_tile_slices(sched))
        self.counters = counters if counters is not None else SimCounters()
        self.transport = transport if transport is not None \
            else _standalone_transport(sched.chain_len)
        self.plan = plan if plan is not None else compile_trace(sched)
        self.use_jax = use_jax
        # quantized engines ride the fused batch-of-tiles lowering when
        # they expose it; fused=False pins the per-tile reference fold
        self.fused = fused and hasattr(self.engine, "tiles_mac")
        if use_jax and self.engine.name != "exact" and not self.fused:
            raise ValueError(
                f"use_jax=True on the {self.engine.name!r} engine is the "
                "fused integer jit flavor — it has no per-tile form "
                "(fused=False)")
        # the engine handle owns the tap/channel-sliced weights; keep the
        # attribute for the jax path and external inspection
        self.weights: List[np.ndarray] = self.handle.tile_w
        self._psum_bytes = sched.c_out * PSUM_BYTES
        self._jax_fn = None
        # zero-initialized work buffers reused across runs (the batched
        # streaming numerics pass calls each executor once per frame
        # chunk, so the padded raster / gather buffers are hot)
        self._scratch: dict = {}

    # -- execution -----------------------------------------------------------

    #: per-buffer cap on cross-run scratch retention (f64 elements) —
    #: larger buffers (ImageNet head layers) stay transient so a parked
    #: simulator does not pin hundreds of MB between calls
    _SCRATCH_CAP_ELEMS = 1 << 22

    def _scratch_buf(self, key: str, shape: Tuple[int, ...],
                     dtype) -> np.ndarray:
        """A zero-initialized scratch array reused across runs.

        Safe because every caller fully overwrites the elements it later
        reads back variable data from, and the zero pad (the raster
        border, the short-``kc`` gather tail) is never written — so the
        zeros from the first allocation persist bit-exactly."""
        buf = self._scratch.get(key)
        if buf is not None and buf.shape == shape \
                and buf.dtype == np.dtype(dtype):
            return buf
        buf = np.zeros(shape, dtype)
        if buf.size <= self._SCRATCH_CAP_ELEMS:
            self._scratch[key] = buf
        return buf

    def run(self, ifm: np.ndarray, account: bool = True) -> np.ndarray:
        """ifm: (H, W, C) or (B, H, W, C) -> OFM (..., E, F, M); bitwise
        identical to ``BlockSimulator.run`` on the same schedule.

        ``account=False`` runs the math only — no ``SimCounters``
        increments and no routed transport records.  The streaming
        executor's batched numerics pass uses it; per-frame accounting
        is then replayed analytically via :meth:`_account`."""
        s = self.sched
        squeeze = ifm.ndim == 3
        if squeeze:
            ifm = ifm[None]
        b = ifm.shape[0]
        assert ifm.shape[1:] == (s.h, s.w, s.c_in), ifm.shape
        if self.use_jax and self.engine.name == "exact":
            out = self._run_jax(ifm)
        else:
            padded = self._scratch_buf(
                "padded", (b, s.hp, s.wp, s.c_in), np.float64)
            padded[:, s.pad:s.pad + s.h, s.pad:s.pad + s.w] = ifm
            stream = padded.reshape(b, -1, s.c_in)
            if not self.fused:
                out = self._execute_np(stream)
            elif self.use_jax:
                out = self._run_jax_quant(stream)
            else:
                out = self._execute_quant(stream)
        if account:
            self._account()
        return out[0] if squeeze else out

    def _execute_np(self, stream: np.ndarray) -> np.ndarray:
        """The whole block as gathers + engine MACs + the segment fold,
        in the interpreter's exact association order."""
        s, plan = self.sched, self.plan
        engine, handle = self.engine, self.handle
        # engine input domain, once per run (identity for exact; static
        # per-layer int quantization for CIM/Pallas — elementwise, so it
        # commutes with the gathers below)
        stream = engine.quant_stream(handle, stream)
        b = stream.shape[0]
        ef = plan.fires
        gsum: Optional[np.ndarray] = None
        for lo, hi in plan.segments:
            acc: Optional[np.ndarray] = None
            for t in range(lo, hi):
                tt = plan.tiles[t]
                # the gathered patch columns are the tile's packed-tap
                # window — the same taps _pe_mac feeds the engine, whose
                # per-tap accumulation order is fixed inside tile_mac
                taps = []
                for d in range(tt.pack):
                    patch = stream[:, tt.gather[d]]
                    if tt.c_lo != 0 or tt.c_hi != s.c_in:
                        patch = patch[:, :, tt.c_lo:tt.c_hi]
                    taps.append(patch.reshape(b * ef, -1))
                m = engine.tile_mac(handle, t, taps,
                                    quantized=True).reshape(b, ef, s.c_out)
                # chain: own MAC + west psum (acc = mac; acc += west)
                acc = m if acc is None else m + acc
            # group fold: chain total + running group-sum from the north
            gsum = acc if gsum is None else acc + gsum
        assert gsum is not None
        return self._tail_np(gsum.reshape(b, s.e, s.f, s.c_out))

    #: fused-path working-set cap: f64 elements allowed in the largest
    #: intermediate ((T, rows, kc) patches / (T, rows, M) dots) per chunk
    _QCHUNK_ELEMS = 1 << 23

    def _gather_tiles(self, qs: np.ndarray, lo: int, hi: int,
                      buf: Optional[np.ndarray] = None) -> np.ndarray:
        """Gather fires [lo, hi) of every tile into one zero-padded
        (T, B*rows, max kc) patch tensor — the same per-tile gathers
        ``_execute_np`` feeds ``tile_mac``, stacked.  Rows are b-major
        (matching ``patch.reshape(b * ef, -1)``); columns are tap-major
        then channel (matching the stacked weight slabs).  ``qs`` is the
        int8 view of the quantized stream (8x less gather traffic); the
        buffer carries the engine's exact-dot dtype (f32 when the
        subarray full-scale fits f32's integer range)."""
        s, plan = self.sched, self.plan
        kcs = self.handle.kc
        b, efc = qs.shape[0], hi - lo
        if buf is None:
            buf = np.zeros((len(plan.tiles), b * efc, max(kcs)),
                           self.handle.w_stack.dtype)
        for i, tt in enumerate(plan.tiles):
            px = qs[:, tt.gather[:, lo:hi]]          # (B, pack, efc, C)
            if tt.c_lo != 0 or tt.c_hi != s.c_in:
                px = px[..., tt.c_lo:tt.c_hi]
            buf[i, :, :kcs[i]] = \
                px.transpose(0, 2, 1, 3).reshape(b * efc, kcs[i])
        return buf

    def _quant_chunks(self, ef: int, b: int):
        """Fire-axis chunking for the fused path: bounds the patch / dot
        working set.  Chunk boundaries cannot change a bit — conversion
        is elementwise and every accumulation is an exact integer sum."""
        t = len(self.plan.tiles)
        kcs = self.handle.kc
        width = max(1, t * b * max(max(kcs), self.sched.c_out))
        chunk = max(1, min(ef, self._QCHUNK_ELEMS // width))
        return [(lo, min(ef, lo + chunk)) for lo in range(0, ef, chunk)]

    def _execute_quant(self, stream: np.ndarray) -> np.ndarray:
        """The fused integer-native path: one stacked gather, one
        batch-of-tiles engine MAC (batched exact integer gemm + ONE
        vectorized ADC conversion across all T subarrays), and the
        chain/group fold collapsed to a single code sum over tiles.
        Bitwise-equal to ``_execute_np``'s per-tile fold: ADC codes are
        integers exact in f64, so association order is free."""
        s = self.sched
        engine, handle = self.engine, self.handle
        # quantized codes are int8-ranged by construction — the compact
        # view moves 8x fewer bytes through the gathers
        qs = engine.quant_stream(handle, stream).astype(np.int8)
        b, ef, m = qs.shape[0], self.plan.fires, s.c_out
        out = np.empty((b, ef, m), np.float64)
        kcm = max(self.handle.kc)
        for lo, hi in self._quant_chunks(ef, b):
            buf = self._scratch_buf(
                "qbuf", (len(self.plan.tiles), b * (hi - lo), kcm),
                self.handle.w_stack.dtype)
            buf = self._gather_tiles(qs, lo, hi, buf)
            codes = engine.tiles_mac(handle, buf)    # (B*rows, M) code sums
            out[:, lo:hi] = codes.reshape(b, hi - lo, m)
        return self._tail_np(out.reshape(b, s.e, s.f, m))

    # -- quantized jax fast path (bitwise, unlike the exact f32 one) ---------

    def _run_jax_quant(self, stream: np.ndarray) -> np.ndarray:
        """jit flavor of the fused path: int8 gathers + the engine's jit
        MAC (:meth:`CIMEngine.tiles_mac_fn` — one batched
        ``lax.dot_general(..., preferred_element_type=int32)`` and the
        shared f32 ADC conversion on the CIM engine, the Pallas kernel on
        the Pallas engine) + the exact integer code sum.  Every op is
        exact-integer or the shared elementwise conversion, so this path
        is *bitwise* equal to the numpy fused/per-tile paths (codes are
        < 2^24, exact in f32)."""
        s = self.sched
        qs = self.engine.quant_stream(self.handle, stream)
        if self._jax_fn is None:
            with span(f"jit_build:{self.sched.layer_name}", cat="jit"):
                self._jax_fn = self._build_jax_qfn()
        csum = self._jax_fn(qs.astype(np.int8))
        b = stream.shape[0]
        out = np.asarray(csum, np.float64).reshape(b, s.e, s.f, s.c_out)
        return self._tail_np(out)

    def _build_jax_qfn(self):
        """The jitted step with the engine's operands bound first:
        ``step(stream)``; ``step.func`` and ``step.args`` are the jitted
        function and those operands (to lower it from shapes)."""
        import jax
        import jax.numpy as jnp

        plan = self.plan
        ef = plan.fires
        kcs, kcm = self.handle.kc, max(self.handle.kc)
        mac, operands = self.engine.tiles_mac_fn(self.handle)

        def fn(ops, stream):
            b = stream.shape[0]
            pats = []
            for i, tt in enumerate(plan.tiles):
                p = jnp.take(stream, tt.gather, axis=1)  # (B, pack, EF, C)
                p = p[..., tt.c_lo:tt.c_hi].transpose(0, 2, 1, 3)
                p = p.reshape(b * ef, kcs[i])
                if kcs[i] < kcm:
                    p = jnp.pad(p, ((0, 0), (0, kcm - kcs[i])))
                pats.append(p)
            return mac(jnp.stack(pats), *ops)      # (B*EF, M) code sums

        return functools.partial(jax.jit(fn), operands)

    def _tail_np(self, out: np.ndarray) -> np.ndarray:
        """Block-tail M-type program: dequantization (quantized engines),
        bias, activation, Fig. 9 pooling — each fold replayed in the
        interpreter's operand order."""
        s = self.sched
        b = out.shape[0]
        out = self.engine.finalize_conv(self.handle, out)
        if self.bias is not None:
            out = out + self.bias
        if s.tail.activation == "relu":
            out = np.maximum(out, 0.0)
        ps = s.tail.pool_s
        if ps:
            assert s.e % ps == 0 and s.f % ps == 0, (
                f"pooling {ps} does not tile the {s.e}x{s.f} OFM")
            win = out.reshape(b, s.e // ps, ps, s.f // ps, ps, s.c_out)
            # running row max in y order (POOL_STORE then POOL_MAX ...)
            row = win[:, :, :, :, 0]
            for y in range(1, ps):
                row = np.maximum(row, win[:, :, :, :, y])
            # fold window rows in x order (row buffer merge, POOL_OUT)
            res = row[:, :, 0]
            for x in range(1, ps):
                res = np.maximum(res, row[:, :, x])
            out = res
        return out

    # -- jax fast path (behind the flag; float32, approximate) ---------------

    def _run_jax(self, ifm: np.ndarray) -> np.ndarray:
        """``jax.jit``-compiled variant of the same plan.  Computes in
        float32 (no x64 requirement), so it is *allclose* to — not
        bitwise-equal with — the numpy path; counters are identical."""
        if self._jax_fn is None:
            with span(f"jit_build:{self.sched.layer_name}", cat="jit"):
                self._jax_fn = self._build_jax_fn()
        out = self._jax_fn(np.asarray(ifm, np.float32))
        return np.asarray(out, np.float64)

    def _build_jax_fn(self):
        import jax
        import jax.numpy as jnp

        s, plan = self.sched, self.plan
        ef = plan.fires
        bias = None if self.bias is None else np.asarray(self.bias, np.float32)
        # Within one group the (tile, tap) pairs partition a slice of the
        # K*K*C contraction exactly once each, so each group is ONE
        # im2col-style gemm (patches concatenated along the contraction
        # axis, packed-tap weights stacked), and the group fold is the
        # same segment sum the Rofm adders perform.  Summation order
        # inside a group differs from the interpreter (this path is
        # allclose, not bitwise — the numpy path is the bitwise one), but
        # a few big gemms are what XLA's CPU backend actually runs fast.
        wcats = [
            np.concatenate(
                [self.weights[t][d] for t in range(lo, hi)
                 for d in range(self.weights[t].shape[0])],
                axis=0).astype(np.float32)
            for lo, hi in plan.segments
        ]

        def fn(ifm, wstacks):
            b = ifm.shape[0]
            padded = jnp.zeros((b, s.hp, s.wp, s.c_in), jnp.float32)
            padded = padded.at[:, s.pad:s.pad + s.h,
                               s.pad:s.pad + s.w].set(ifm)
            stream = padded.reshape(b, -1, s.c_in)
            gsum = None
            for (lo, hi), wstack in zip(plan.segments, wstacks):
                cols = []
                for t in range(lo, hi):
                    tt = plan.tiles[t]
                    for d in range(tt.pack):
                        patch = jnp.take(stream, tt.gather[d], axis=1)
                        cols.append(patch[:, :, tt.c_lo:tt.c_hi])
                patches = jnp.concatenate(cols, axis=2)  # (B, EF, K_group)
                g = (patches.reshape(b * ef, -1) @ wstack
                     ).reshape(b, ef, s.c_out)
                gsum = g if gsum is None else g + gsum
            out = gsum.reshape(b, s.e, s.f, s.c_out)
            if bias is not None:
                out = out + bias
            if s.tail.activation == "relu":
                out = jnp.maximum(out, 0.0)
            ps = s.tail.pool_s
            if ps:
                win = out.reshape(b, s.e // ps, ps, s.f // ps, ps, s.c_out)
                out = win.max(axis=(2, 4))
            return out

        jitted = jax.jit(fn)
        return lambda ifm: jitted(ifm, wcats)

    # -- analytic counters (same events the interpreter tallies per cycle) ---

    def _account(self) -> None:
        s, plan = self.sched, self.plan
        fires = plan.fires
        cnt = self.counters
        transport = self.transport
        cnt.cycles += plan.drain_cycles
        cnt.instr_fetches += s.chain_len * plan.n_pix
        cnt.macs += fires * plan.macs_per_fire
        north_tiles = sum(1 for tt in plan.tiles if tt.has_north_buf)
        cnt.buf_push += north_tiles * fires
        cnt.buf_pop += north_tiles * fires
        if s.tail.activation:
            cnt.act_ops += fires * s.c_out
        ps = s.tail.pool_s
        if ps:
            cnt.pool_ops += s.e * (s.f - s.f // ps) * s.c_out
        for tt in plan.tiles:
            if tt.dst_east is not None:
                h = transport.record_bulk(tt.tile_id, tt.dst_east, CHAIN,
                                          self._psum_bytes, fires)
                cnt.chain_hops += fires * max(1, h)  # 1 cycle/hop latency
            if tt.dst_south is not None:
                h = transport.record_bulk(tt.tile_id, tt.dst_south, GROUP,
                                          self._psum_bytes, fires)
                cnt.group_hops += fires * max(1, h)


def simulate_block_trace(sched: BlockSchedule, weights: np.ndarray,
                         ifm: np.ndarray,
                         bias: Optional[np.ndarray] = None,
                         **kw) -> np.ndarray:
    """One-shot convenience: compile + execute a block on the fast path."""
    return TraceExecutor(sched, weights, bias=bias, **kw).run(ifm)
