"""Pure-jnp oracles for every Pallas kernel in this package."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.cim import CIMSpec, DEFAULT_SPEC, adc_quantize


# ---------------------------------------------------------------------------
# cim_matmul oracles
# ---------------------------------------------------------------------------


def cim_matmul_ref(xq: jax.Array, wq: jax.Array,
                   spec: CIMSpec = DEFAULT_SPEC) -> jax.Array:
    """Oracle for the Pallas CIM matmul: per-subarray exact int dot ->
    ADC quantize -> digital code accumulation.  Returns f32 (M, N)."""
    m, k = xq.shape
    k2, n = wq.shape
    assert k == k2
    pad = (-k) % spec.n_c
    if pad:
        xq = jnp.pad(xq, ((0, 0), (0, pad)))
        wq = jnp.pad(wq, ((0, pad), (0, 0)))
    n_sub = (k + pad) // spec.n_c
    xs = xq.reshape(m, n_sub, spec.n_c).astype(jnp.int32)
    ws = wq.reshape(n_sub, spec.n_c, n).astype(jnp.int32)
    d = jnp.einsum("msk,skn->msn", xs, ws)
    codes = adc_quantize(d, spec)
    return jnp.sum(codes, axis=1).astype(jnp.float32) * spec.adc_step


def cim_matmul_bitplane_ref(xq: jax.Array, wq: jax.Array,
                            spec: CIMSpec = DEFAULT_SPEC) -> jax.Array:
    """The *circuit-faithful* oracle: explicitly decomposes weights into 8
    bit planes across bit lines, applies the current-mirror significances
    (k/8, k/4, k/2, k per 4-bit group), joins the two integrator groups by
    the 16:1 charge redistribution, and runs inputs bit-serially with
    charge-averaged significance — then the ADC.

    Mathematically this must equal :func:`cim_matmul_ref`; the property
    test in tests/test_kernels.py asserts exact agreement.  It exists to
    demonstrate that the "one exact int dot then ADC" shortcut used by the
    fast paths is the true circuit semantics, not an approximation.
    """
    assert spec.w_bits == 8 and spec.a_bits == 8
    m, k = xq.shape
    k2, n = wq.shape
    pad = (-k) % spec.n_c
    if pad:
        xq = jnp.pad(xq, ((0, 0), (0, pad)))
        wq = jnp.pad(wq, ((0, pad), (0, 0)))
    n_sub = (k + pad) // spec.n_c

    # two's-complement bit planes: w = -128*b7 + sum_{j<7} 2^j * b_j
    wu = wq.astype(jnp.int32) & 0xFF  # unsigned view of the stored cells
    planes = [(wu >> j) & 1 for j in range(8)]  # b0..b7, single-level cells

    xu = xq.astype(jnp.int32) & 0xFF
    x_bits = [(xu >> i) & 1 for i in range(8)]  # bit-serial input cycles

    xs_bits = [xb.reshape(m, n_sub, spec.n_c) for xb in x_bits]
    w_planes = [p.reshape(n_sub, spec.n_c, n) for p in planes]

    total = jnp.zeros((m, n_sub, n), dtype=jnp.float32)
    for i, xb in enumerate(xs_bits):  # input bit-serial cycle i
        # --- analog core for one input bit ---
        # lower 4-bit group: mirrors k/8, k/4, k/2, k  (ratios 1,2,4,8)
        lo = sum(
            jnp.einsum("msk,skn->msn", xb, w_planes[j]).astype(jnp.float32)
            * (2 ** j)
            for j in range(4)
        )
        # upper group: same mirror ratios; b7 carries the two's-complement sign
        hi = sum(
            jnp.einsum("msk,skn->msn", xb, w_planes[j]).astype(jnp.float32)
            * (2 ** (j - 4))
            for j in range(4, 7)
        )
        hi = hi + jnp.einsum(
            "msk,skn->msn", xb, w_planes[7]
        ).astype(jnp.float32) * (-(2 ** 3))
        # 16:1 charge redistribution joins the groups: hi*16 + lo
        joined = hi * 16.0 + lo
        # input-bit significance via charge averaging across cycles
        sign = -1.0 if i == 7 else 1.0  # two's-complement input MSB
        total = total + joined * sign * (2 ** i)

    codes = adc_quantize(total.astype(jnp.int32), spec)
    return jnp.sum(codes, axis=1).astype(jnp.float32) * spec.adc_step


def int8_matmul_exact_ref(xq: jax.Array, wq: jax.Array) -> jax.Array:
    """Lossless int8 matmul (what an ideal, infinite-resolution ADC gives)."""
    return jax.lax.dot_general(
        xq.astype(jnp.int32), wq.astype(jnp.int32),
        (((1,), (0,)), ((), ())),
    ).astype(jnp.float32)

