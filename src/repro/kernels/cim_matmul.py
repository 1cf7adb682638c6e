"""Pallas TPU kernel: Domino CIM crossbar matmul (w8a8 + per-subarray ADC).

One grid step along K processes exactly one CIM subarray (``n_c`` rows =
the ADC accumulation granularity), so the kernel's arithmetic *is* the
array's: an exact int8xint8->int32 dot over n_c rows (the MXU analogue of
the bit-line/current-mirror/charge-share pipeline — see
``kernels/ref.cim_matmul_bitplane_ref`` for the circuit-level proof of
equivalence), followed by the SAR-ADC round/saturate, followed by digital
accumulation of ADC codes (what Domino's Rofm adds "on the move").

Tiling: x (bm, n_c) and w (n_c, bn) blocks live in VMEM; the f32 output
block doubles as the code accumulator (codes are integers, exactly
representable in f32 far beyond any realistic K).  MXU-aligned defaults:
bm = bn = 256, n_c = 256.

The kernel is compiled (Mosaic) exactly when JAX's default backend is a
TPU and runs in Pallas interpret mode everywhere else — see
:func:`interpret_mode`; no caller chooses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.cim import CIMSpec, DEFAULT_SPEC, adc_convert_jnp

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def interpret_mode() -> bool:
    """True when the kernel runs in Pallas interpret mode: the default
    backend is not a TPU (the CPU test and validation target)."""
    return jax.default_backend() != "tpu"


def _codes(d, inv, q_max: int, off=None):
    """One subarray's SAR conversion as f32 codes (exact integers)."""
    return adc_convert_jnp(d, inv, -float(q_max + 1), float(q_max),
                           off).astype(jnp.float32)


def _cim_kernel(x_ref, w_ref, o_ref, *, nk: int, inv_step: float, step: float,
                q_max: int, emit_codes: bool):
    """One (bm, bn) output block; K-steps iterate subarrays."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # exact integer dot over one subarray (n_c rows) — MXU int8 path
    d = jax.lax.dot_general(
        x_ref[...], w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    # SAR ADC: round & saturate to adc_bits codes, then digital
    # accumulation of codes (integers — exact in f32)
    o_ref[...] += _codes(d, inv_step, q_max)

    if not emit_codes:
        @pl.when(k == nk - 1)
        def _scale():
            o_ref[...] *= step


def _cim_kernel_var(x_ref, w_ref, adc_ref, o_ref, *, nk: int, step: float,
                    q_max: int, emit_codes: bool):
    """The device-variation flavor: K step ``k``'s subarray converts with
    its OWN per-ADC ``(inverse step, offset)`` pair, row ``k`` of the
    whole (nk, 2) f32 table held in SMEM (a (1, 2) VMEM block violates
    the TPU's (8, 128) block tiling) — the same f32 multiply(+add)/
    round/saturate ops as the numpy :func:`repro.core.cim.adc_convert`,
    so codes stay bitwise across backends under a ``VariationModel``."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    d = jax.lax.dot_general(
        x_ref[...], w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    o_ref[...] += _codes(d, adc_ref[k, 0], q_max, adc_ref[k, 1])

    if not emit_codes:
        @pl.when(k == nk - 1)
        def _scale():
            o_ref[...] *= step


@functools.partial(
    jax.jit,
    static_argnames=("spec", "block_m", "block_n", "emit_codes"),
)
def cim_matmul_pallas(xq: jax.Array, wq: jax.Array,
                      spec: CIMSpec = DEFAULT_SPEC,
                      block_m: int = 256, block_n: int = 256,
                      emit_codes: bool = False,
                      adc_var: "jax.Array | None" = None) -> jax.Array:
    """(M, K) int8 @ (K, N) int8 -> (M, N) f32 through the CIM pipeline.

    Pads every dim to its block multiple; K blocks are ``spec.n_c`` wide so
    each K-step is one subarray.  ``emit_codes=True`` skips the final
    step scaling and returns the raw digitally-accumulated ADC code sums
    (integers in f32) — the quantity the engine layer accumulates along
    a tile chain.  ``adc_var`` is an optional (nk, 2) f32 array of
    per-subarray ``[inverse step, offset]`` ADC parameters (device
    variation); K step ``k`` reads row ``k``.  It is a traced operand,
    so Monte-Carlo trials reuse one compiled kernel.
    """
    m, k_dim = xq.shape
    k2, n = wq.shape
    assert k_dim == k2, (xq.shape, wq.shape)
    n_c = spec.n_c

    bm = min(block_m, _round_up(m, 8))
    bn = min(block_n, _round_up(n, 128))
    mp, kp, np_ = _round_up(m, bm), _round_up(k_dim, n_c), _round_up(n, bn)
    if (mp, kp) != (m, k_dim):
        xq = jnp.pad(xq, ((0, mp - m), (0, kp - k_dim)))
    if (kp, np_) != (k_dim, n):
        wq = jnp.pad(wq, ((0, kp - k_dim), (0, np_ - n)))

    nk = kp // n_c
    grid = (mp // bm, np_ // bn, nk)

    in_specs = [
        pl.BlockSpec((bm, n_c), lambda i, j, k: (i, k)),
        pl.BlockSpec((n_c, bn), lambda i, j, k: (k, j)),
    ]
    if adc_var is None:
        kernel = functools.partial(
            _cim_kernel, nk=nk, inv_step=spec.adc_inv_step,
            step=spec.adc_step, q_max=spec.q_max, emit_codes=emit_codes,
        )
        operands = (xq, wq)
    else:
        assert adc_var.shape == (nk, 2), (adc_var.shape, nk)
        kernel = functools.partial(
            _cim_kernel_var, nk=nk, step=spec.adc_step,
            q_max=spec.q_max, emit_codes=emit_codes,
        )
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands = (xq, wq, adc_var.astype(jnp.float32))
    interpret = interpret_mode()
    kwargs = {} if interpret else {"compiler_params": _COMPILER_PARAMS}
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
        **kwargs,
    )(*operands)
    return out[:m, :n]


def cim_chain_codes_pallas(xq: jax.Array, wq: jax.Array,
                           spec: CIMSpec = DEFAULT_SPEC,
                           block_m: int = 256, block_n: int = 256,
                           adc_var: "jax.Array | None" = None) -> jax.Array:
    """Multi-tile ``emit_codes`` invocation: one kernel call for a whole
    tile chain.

    ``xq``: (R, T * n_c) int8 with each chain tile's ``kc <= n_c``
    activation columns occupying its own ``n_c``-wide K block; ``wq``:
    (T * n_c, M) int8 with each tile's weight slab zero-padded past its
    ``kc`` rows (padding contributes nothing to the exact integer dot).
    Each K grid step is then exactly one chain tile's subarray, so the
    kernel's in-VMEM code accumulation *is* the chain/group digital fold
    the Rofm performs "on the move" — the returned (R, M) f32 code sums
    are bitwise the per-tile engine fold.
    """
    assert xq.shape[1] == wq.shape[0] and xq.shape[1] % spec.n_c == 0, (
        xq.shape, wq.shape, spec.n_c)
    return cim_matmul_pallas(
        xq, wq, spec, block_m=block_m, block_n=block_n, emit_codes=True,
        adc_var=None if adc_var is None else jnp.asarray(adc_var))


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult
