"""Precision splitting — the paper's Eqs. (2)-(5) / (19)-(22), generalized.

An FP32 value ``v`` is decomposed into ``n`` low-precision terms

    v  ~=  a_0  +  a_1 * 2**-s  +  a_2 * 2**-2s  + ...

where each ``a_i`` is stored in a narrow dtype (bf16 on TPU, fp16 for the
paper-faithful reproduction) and ``s`` is the *scale shift* applied to each
residual before the narrowing cast (the paper's ``x 2**11`` of Eq. (18); we use
``s = mantissa bits`` of the target dtype so the residual's leading bits land in
the representable range, eliminating the underflow / gradual-underflow band the
paper analyzes in Eqs. (13)-(17)).

All casts use round-to-nearest-even (RN), the CUDA default the paper assumes;
an RZ variant is provided for reproducing the paper's Table 2 analysis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Mantissa bits (explicit, excluding the implicit leading 1) per storage dtype.
MANTISSA_BITS = {
    jnp.bfloat16.dtype: 7,
    jnp.float16.dtype: 10,
    jnp.float32.dtype: 23,
    jnp.dtype(jnp.float8_e4m3fn): 3,
    jnp.dtype(jnp.float8_e5m2): 2,
}


def _cast_rz(x: jax.Array, dtype) -> jax.Array:
    """Round-toward-zero cast of f32 -> {bf16, f16} (for Table-2 style analysis).

    bf16 is the upper 16 bits of f32, so RZ is a plain mask. f16 RZ is emulated
    by clearing the 13 low mantissa bits *after* aligning to the f16 quantum —
    we do it via frexp/ldexp which is exact for normal numbers (the RZ variant
    is an analysis tool; production splits use RN casts).
    """
    dtype = jnp.dtype(dtype)
    if dtype == jnp.bfloat16.dtype:
        bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
        return jax.lax.bitcast_convert_type(
            (bits & jnp.uint32(0xFFFF0000)).astype(jnp.uint32), jnp.float32
        ).astype(jnp.bfloat16)
    if dtype == jnp.float16.dtype:
        m, e = jnp.frexp(x.astype(jnp.float32))
        p = 11  # implicit + 10 explicit
        t = jnp.trunc(m * (2.0**p))
        return jnp.ldexp(t, e - p).astype(jnp.float16)
    raise ValueError(f"unsupported RZ cast target {dtype}")


def split(x: jax.Array, dtype, n_splits: int, scale_bits: int,
          rounding: str = "rn") -> list[jax.Array]:
    """Split f32 ``x`` into ``n_splits`` terms of ``dtype``.

    Returns ``[a_0, ..., a_{n-1}]`` with ``x ~= sum_i f32(a_i) * 2**(-i*scale_bits)``.
    ``scale_bits`` is applied to each residual before the cast (exponent-only,
    exact — it never touches the mantissa), reproducing the paper's Eq. (18).
    """
    x = x.astype(jnp.float32)
    dtype = jnp.dtype(dtype)
    cast = (lambda v: v.astype(dtype)) if rounding == "rn" else (
        lambda v: _cast_rz(v, dtype))
    scale = jnp.float32(2.0 ** scale_bits)
    out = []
    r = x
    for i in range(n_splits):
        a = cast(r)
        out.append(a)
        if i + 1 < n_splits:
            # the barrier keeps XLA from folding the narrowing round trip
            # away (TPU allows excess precision), which would zero every
            # correction term
            r = (r - jax.lax.optimization_barrier(a).astype(jnp.float32)) \
                * scale
    return out


def reconstruct(parts: list[jax.Array], scale_bits: int) -> jax.Array:
    """Inverse of :func:`split` (up to representation error) in f32."""
    acc = jnp.zeros_like(parts[-1], dtype=jnp.float32)
    # smallest terms first for a numerically faithful epilogue (paper's Code 3
    # adds frag_dc/2048 into frag_c — we fold scale groups from the tail).
    for i, a in reversed(list(enumerate(parts))):
        acc = acc + a.astype(jnp.float32) * jnp.float32(2.0 ** (-i * scale_bits))
    return acc
