"""Plain FP8 (1/5/2 a.k.a. e5m2) helpers — the paper's baseline format.

The paper's FP8 is IEEE-style 1 sign / 5 exponent / 2 mantissa with denormals
and RNE rounding (Table A1): normal range [2^-14, (1-2^-3)*2^16], denormals
down to 2^-16, machine epsilon 2^-3.  That is bit-identical to ml_dtypes'
``float8_e5m2``, which JAX exposes as ``jnp.float8_e5m2``; ``astype`` performs
round-to-nearest-even.

We also expose e4m3 for the mixed-format ablation (not used by the paper).

The simulated truncations round in f32 arithmetic instead of casting to FP8
and back: a TPU v5e compiles an f32 -> FP8 -> f32 convert pair inside one
XLA fusion to a no-op (measured: the round trip returned its input), while
a payload stored as FP8 in memory is rounded correctly.  Both are bitwise
equal to the ``astype`` round trip wherever the cast is honoured
(tests/test_s2fp8.py sweeps them against ml_dtypes).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Format constants (paper Table A1 / Figure A1).
E5M2_MAX = 57344.0          # (1 - 2**-3) * 2**16
E5M2_MIN_NORMAL = 2.0 ** -14
E5M2_MIN_SUBNORMAL = 2.0 ** -16
E4M3_MAX = 448.0
E4M3_MIN_NORMAL = 2.0 ** -6


def _round_to_grid(x, *, mantissa_bits: int, min_normal: float,
                   max_finite: float, overflow: float) -> jnp.ndarray:
    """Round ``x`` to the nearest (ties to even) value of an FP8 grid, in
    f32.  Normal range: ``reduce_precision`` with f32's own 8 exponent bits
    rounds only the mantissa.  Below ``min_normal`` the grid is fixed point
    with spacing ``min_normal * 2^-mantissa_bits``, rounded by exact
    power-of-two scaling (``reduce_precision`` to the narrow exponent would
    flush these subnormals to zero).  Past ``max_finite`` the result is
    ``overflow`` with x's sign, as the FP8 cast gives (inf for e5m2, NaN
    for e4m3fn)."""
    xf = x.astype(jnp.float32)
    normal = jax.lax.reduce_precision(xf, exponent_bits=8,
                                      mantissa_bits=mantissa_bits)
    step = min_normal * 2.0 ** -mantissa_bits
    sub = jnp.round(xf / step) * step
    r = jnp.where(jnp.abs(xf) < min_normal, sub, normal)
    r = jnp.where(jnp.abs(r) > max_finite,
                  jnp.copysign(jnp.float32(overflow), xf), r)
    return r.astype(x.dtype)


def truncate_e5m2(x: jnp.ndarray) -> jnp.ndarray:
    """RNE-truncate to FP8 e5m2 and return in the input's float dtype.

    Overflow goes to +-inf in e5m2; the paper's S2FP8 construction guarantees
    |Y| <= 2^15 so saturation never triggers post-transform, but raw FP8
    baselines *do* overflow — that divergence is part of the reproduction, so
    we intentionally do not clamp here.
    """
    return _round_to_grid(x, mantissa_bits=2, min_normal=E5M2_MIN_NORMAL,
                          max_finite=E5M2_MAX, overflow=jnp.inf)


def truncate_e4m3(x: jnp.ndarray) -> jnp.ndarray:
    """RNE-truncate to FP8 e4m3fn (ablation format; overflow is NaN)."""
    return _round_to_grid(x, mantissa_bits=3, min_normal=E4M3_MIN_NORMAL,
                          max_finite=E4M3_MAX, overflow=jnp.nan)


def cast_e5m2(x: jnp.ndarray) -> jnp.ndarray:
    """Cast to the 1-byte payload dtype (storage, not simulation)."""
    return x.astype(jnp.float8_e5m2)
