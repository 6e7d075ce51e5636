"""Pallas TPU kernels for S2FP8 quantization (stats + apply + fused truncate).

The paper (§5) describes two HW components: (1) a statistics unit computing
(mu, m) per tensor, (2) an exponent-shift / mantissa-squeeze unit applied
before the 8-bit truncation.  On TPU these become:

  * ``stats``  — a blocked reduction over the tensor resident in HBM,
    streamed through VMEM tiles; partials accumulate in (1,1) SMEM cells
    across the sequential grid (TPU grid iterations run in order on a core).
  * ``apply``  — an elementwise VPU map: y = sign(x)*2^(alpha*log2|x|+beta),
    cast RNE to float8_e5m2 in-register, written back as the 1-byte payload.
  * ``truncate`` — the Eq. 5 round-trip (forward map -> FP8 RNE -> inverse
    map) fused into ONE elementwise kernel: one HBM read + one HBM write,
    where the reference jnp path issues three elementwise passes.
  * ``truncate_fused`` — stats AND the truncate round-trip in a single
    ``pallas_call`` with a two-phase sequential grid: phase 0 streams the
    tensor once to accumulate (sum, max, count), phase 1 streams it again
    applying forward->RNE->inverse.  Two HBM passes total instead of the
    reference path's ~five.

Block shapes default to (256, 512): 256*512*4B = 512 KiB per input tile —
comfortably inside the ~16 MiB v5e VMEM with double-buffering, and the
lane dim (512) is a multiple of 128 for clean vectorization.

All entry points take ``interpret=None`` which resolves via
``repro.kernels.auto_interpret()``: compiled on TPU, interpreter elsewhere.
Inputs must be 2-D and block-divisible — arbitrary rank and ragged shapes
are handled one layer up in ``repro.kernels.dispatch``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.s2fp8 import (FMT_MAX_FINITE, FMT_QDTYPE, FMT_TARGET_MAX,
                              TARGET_MAX_LOG2, stats_from_reduction)
from repro.kernels import auto_interpret
from repro.obs import spans

DEFAULT_BLOCK = (256, 512)
_NEG_INF = -jnp.inf
# Scalar accumulators live in SMEM, whole-array and resident across the
# sequential grid: Mosaic stores scalars to SMEM only, never to VMEM.
_SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _resolve(interpret):
    return auto_interpret() if interpret is None else interpret


def _stats_kernel(x_ref, sum_ref, max_ref, cnt_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        sum_ref[0, 0] = 0.0
        max_ref[0, 0] = _NEG_INF
        cnt_ref[0, 0] = 0.0

    x = x_ref[...].astype(jnp.float32)
    absx = jnp.abs(x)
    nz = absx > 0.0
    logx = jnp.where(nz, jnp.log2(jnp.where(nz, absx, 1.0)), 0.0)
    sum_ref[0, 0] += jnp.sum(logx)
    max_ref[0, 0] = jnp.maximum(max_ref[0, 0], jnp.max(jnp.where(nz, logx, _NEG_INF)))
    cnt_ref[0, 0] += jnp.sum(nz.astype(jnp.float32))


def _apply_kernel(alpha_ref, beta_ref, x_ref, out_ref, *, fmt):
    alpha = alpha_ref[0, 0]
    beta = beta_ref[0, 0]
    x = x_ref[...].astype(jnp.float32)
    absx = jnp.abs(x)
    nz = absx > 0.0
    ylog = alpha * jnp.log2(jnp.where(nz, absx, 1.0)) + beta
    y = jnp.where(nz, jnp.sign(x) * jnp.exp2(ylog), 0.0)
    # clamp at the format's max finite, mirroring core/s2fp8.py quantize:
    # a no-op for fresh stats, saturation (not inf) under stale bank stats
    y = jnp.clip(y, -FMT_MAX_FINITE[fmt], FMT_MAX_FINITE[fmt])
    out_ref[...] = y.astype(FMT_QDTYPE[fmt])


def _dequant_kernel(alpha_ref, beta_ref, y_ref, out_ref):
    alpha = alpha_ref[0, 0]
    beta = beta_ref[0, 0]
    y = y_ref[...].astype(jnp.float32)
    absy = jnp.abs(y)
    nz = absy > 0.0
    xlog = (jnp.log2(jnp.where(nz, absy, 1.0)) - beta) / alpha
    out_ref[...] = jnp.where(nz, jnp.sign(y) * jnp.exp2(xlog), 0.0)


def _truncate_body(x, alpha, beta, fmt):
    """Forward map -> clamp -> FP8 RNE -> inverse map, elementwise
    in-register.

    The op sequence mirrors core/s2fp8.py's truncate_value exactly so that
    (given identical alpha, beta) the result is bitwise identical to the
    reference path.  The clamp at the format's max finite is a no-op for
    fresh stats and saturates (instead of inf) under stale delayed stats.
    """
    qdtype = FMT_QDTYPE[fmt]
    fmax = FMT_MAX_FINITE[fmt]
    absx = jnp.abs(x)
    nz = absx > 0.0
    ylog = alpha * jnp.log2(jnp.where(nz, absx, 1.0)) + beta
    y = jnp.where(nz, jnp.sign(x) * jnp.exp2(ylog), 0.0).astype(jnp.float32)
    y = jnp.clip(y, -fmax, fmax)
    yq = y.astype(qdtype).astype(jnp.float32)
    absyq = jnp.abs(yq)
    nzq = absyq > 0.0
    xlog = (jnp.log2(jnp.where(nzq, absyq, 1.0)) - beta) / alpha
    return jnp.where(nzq, jnp.sign(yq) * jnp.exp2(xlog), 0.0)


def _truncate_kernel(alpha_ref, beta_ref, x_ref, out_ref, *, fmt):
    x = x_ref[...].astype(jnp.float32)
    out_ref[...] = _truncate_body(x, alpha_ref[0, 0], beta_ref[0, 0], fmt)


def _truncate_fused_kernel(x_ref, out_ref, stats_ref, *, fmt, target_max):
    """Two-phase grid (phase, i, j): phase 0 reduces stats into the
    persistent (1, 3) stats output [sum, max, count]; phase 1 re-reads the
    tensor and applies the fused truncate round-trip."""
    phase = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when((phase == 0) & (i == 0) & (j == 0))
    def _init():
        stats_ref[0, 0] = 0.0
        stats_ref[0, 1] = _NEG_INF
        stats_ref[0, 2] = 0.0

    x = x_ref[...].astype(jnp.float32)
    absx = jnp.abs(x)
    nz = absx > 0.0
    logx = jnp.where(nz, jnp.log2(jnp.where(nz, absx, 1.0)), 0.0)

    @pl.when(phase == 0)
    def _reduce():
        stats_ref[0, 0] += jnp.sum(logx)
        stats_ref[0, 1] = jnp.maximum(stats_ref[0, 1],
                                      jnp.max(jnp.where(nz, logx, _NEG_INF)))
        stats_ref[0, 2] += jnp.sum(nz.astype(jnp.float32))

    @pl.when(phase == 1)
    def _apply():
        # Shared scalar epilogue — pure jnp, runs fine in-kernel, and any
        # change to the degenerate-case conventions propagates here.
        alpha, beta = stats_from_reduction(stats_ref[0, 0], stats_ref[0, 1],
                                           stats_ref[0, 2], target_max)
        out_ref[...] = _truncate_body(x, alpha, beta, fmt)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def stats_pallas(x: jnp.ndarray, *, block=DEFAULT_BLOCK, interpret: bool | None = None):
    """Blocked (sum_log, max_log, count) reduction. x must be 2-D, block-divisible."""
    interpret = _resolve(interpret)
    m, n = x.shape
    bm, bn = min(block[0], m), min(block[1], n)
    grid = (m // bm, n // bn)
    s, mx, c = pl.pallas_call(
        _stats_kernel,
        name=spans.STATS,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=[_SMEM_SPEC] * 3,
        out_shape=[jax.ShapeDtypeStruct((1, 1), jnp.float32)] * 3,
        interpret=interpret,
    )(x)
    return s[0, 0], mx[0, 0], c[0, 0]


@functools.partial(jax.jit, static_argnames=("fmt", "block", "interpret"))
def quant_apply_pallas(x: jnp.ndarray, alpha, beta, *, fmt: str = "e5m2",
                       block=DEFAULT_BLOCK, interpret: bool | None = None):
    """Forward map + FP8 cast with externally supplied (alpha, beta)."""
    interpret = _resolve(interpret)
    m, n = x.shape
    bm, bn = min(block[0], m), min(block[1], n)
    grid = (m // bm, n // bn)
    scalar_spec = pl.BlockSpec((1, 1), lambda i, j: (0, 0))
    return pl.pallas_call(
        functools.partial(_apply_kernel, fmt=fmt),
        name=spans.QUANT_APPLY,
        grid=grid,
        in_specs=[scalar_spec, scalar_spec,
                  pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), FMT_QDTYPE[fmt]),
        interpret=interpret,
    )(jnp.asarray(alpha, jnp.float32).reshape(1, 1),
      jnp.asarray(beta, jnp.float32).reshape(1, 1), x)


@functools.partial(jax.jit, static_argnames=("fmt", "block", "interpret"))
def quant_pallas(x: jnp.ndarray, *, fmt: str = "e5m2", block=DEFAULT_BLOCK,
                 interpret: bool | None = None):
    """Full S2FP8 quantization: returns (payload, alpha, beta)."""
    interpret = _resolve(interpret)
    s, mx, c = stats_pallas(x, block=block, interpret=interpret)
    alpha, beta = stats_from_reduction(s, mx, c, FMT_TARGET_MAX[fmt])
    payload = quant_apply_pallas(x, alpha, beta, fmt=fmt, block=block,
                                 interpret=interpret)
    return payload, alpha, beta


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def dequant_pallas(payload, alpha, beta, *, block=DEFAULT_BLOCK,
                   interpret: bool | None = None):
    """Inverse map back to f32."""
    interpret = _resolve(interpret)
    m, n = payload.shape
    bm, bn = min(block[0], m), min(block[1], n)
    grid = (m // bm, n // bn)
    scalar_spec = pl.BlockSpec((1, 1), lambda i, j: (0, 0))
    return pl.pallas_call(
        _dequant_kernel,
        name=spans.DEQUANT,
        grid=grid,
        in_specs=[scalar_spec, scalar_spec,
                  pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(alpha.reshape(1, 1), beta.reshape(1, 1), payload)


@functools.partial(jax.jit, static_argnames=("fmt", "block", "interpret"))
def truncate_apply_pallas(x: jnp.ndarray, alpha, beta, *, fmt: str = "e5m2",
                          block=DEFAULT_BLOCK, interpret: bool | None = None):
    """Fused Eq. 5 round-trip with externally supplied (alpha, beta):
    ONE elementwise kernel (one HBM read, one HBM write).  This is the
    delayed-stats fast path and the bitwise-parity path (stats from the
    same reduction the reference uses -> bitwise-identical output)."""
    interpret = _resolve(interpret)
    m, n = x.shape
    bm, bn = min(block[0], m), min(block[1], n)
    grid = (m // bm, n // bn)
    scalar_spec = pl.BlockSpec((1, 1), lambda i, j: (0, 0))
    return pl.pallas_call(
        functools.partial(_truncate_kernel, fmt=fmt),
        name=spans.TRUNCATE_APPLY,
        grid=grid,
        in_specs=[scalar_spec, scalar_spec,
                  pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(alpha, jnp.float32).reshape(1, 1),
      jnp.asarray(beta, jnp.float32).reshape(1, 1), x)


@functools.partial(jax.jit,
                   static_argnames=("fmt", "target_max", "block", "interpret"))
def truncate_fused_pallas(x: jnp.ndarray, *, fmt: str = "e5m2",
                          target_max: float = TARGET_MAX_LOG2,
                          block=DEFAULT_BLOCK, interpret: bool | None = None):
    """Single-``pallas_call`` fused truncate: in-kernel stats reduction
    (phase 0) + fused apply->RNE->inverse (phase 1).  Two HBM passes over
    the tensor instead of the reference path's ~five.  Returns
    (truncated_f32, alpha, beta).

    The blocked reduction order differs from the monolithic jnp reduction,
    so alpha/beta (and hence the output) match the reference to float
    tolerance, not bit-for-bit — use ``truncate_apply_pallas`` with exact
    stats when bitwise parity matters.
    """
    interpret = _resolve(interpret)
    m, n = x.shape
    bm, bn = min(block[0], m), min(block[1], n)
    grid = (2, m // bm, n // bn)
    out, stats = pl.pallas_call(
        functools.partial(_truncate_fused_kernel, fmt=fmt,
                          target_max=target_max),
        name=spans.TRUNCATE_FUSED,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bn), lambda p, i, j: (i, j))],
        out_specs=[pl.BlockSpec((bm, bn), lambda p, i, j: (i, j)),
                   _SMEM_SPEC],
        out_shape=[jax.ShapeDtypeStruct((m, n), jnp.float32),
                   jax.ShapeDtypeStruct((1, 3), jnp.float32)],
        interpret=interpret,
    )(x)
    alpha, beta = stats_from_reduction(stats[0, 0], stats[0, 1], stats[0, 2],
                                       target_max)
    return out, alpha, beta
