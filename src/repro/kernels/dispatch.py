"""Shape/rank-generalizing dispatch for the S2FP8 Pallas kernels.

The raw kernels in s2fp8_quant.py / s2fp8_matmul.py are deliberately strict:
2-D, block-divisible inputs only (that is the shape the TPU wants).  Real
tensors are none of those things — conv kernels are 4-D, bias rows are 1-D,
vocab projections are 50257-wide.  This layer closes the gap:

  * arbitrary rank  — tensors are flattened and re-tiled to a (rows, LANE)
    2-D layout (LANE = 512, a multiple of the 128-lane VPU width);
  * ragged shapes   — zero-padded up to the block grid.  Zero is the one
    value S2FP8 treats specially everywhere (excluded from stats, mapped to
    itself by both transforms), so zero-padding is exact: padding never
    perturbs stats, truncation, or GEMM results;
  * platform        — ``interpret=None`` resolves via
    ``repro.kernels.auto_interpret()`` (compiled on TPU, interpreter
    elsewhere);
  * stats modes     — every truncate entry point accepts precomputed
    ``stats=(alpha, beta)`` (the delayed-stats fast path: one HBM pass) or
    computes them, either exactly (same monolithic reduction as the
    reference — bitwise-parity mode) or in-kernel (``fused_stats=True``,
    the two-phase single-kernel path).

core/backend.py builds the user-facing backend objects on top of these.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import s2fp8
from repro.kernels import auto_interpret
from repro.kernels.ref import gemm_dims
from repro.kernels.s2fp8_matmul import (pick_gemm_block, s2fp8_matmul_pallas,
                                        s2fp8_matmul_batched_pallas)
from repro.kernels.s2fp8_quant import (DEFAULT_BLOCK, dequant_pallas,
                                       quant_apply_pallas, quant_pallas,
                                       stats_pallas, truncate_apply_pallas,
                                       truncate_fused_pallas)
from repro.obs import spans

# Lane width for the flattened layout of non-2-D tensors.
LANE = 512
# Hardware tile alignment every block is padded to: TPU f32 tiles are
# (8, 128) (sublane x lane); interpret mode does not care, but compiled
# Mosaic does, so ragged shapes are padded to these multiples BEFORE the
# block grid is derived.
SUBLANE_ALIGN = 8
LANE_ALIGN = 128


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


@jax.named_scope(spans.S2FP8_QUANTIZE)
def _pad_axis(x: jnp.ndarray, axis: int, target: int) -> jnp.ndarray:
    pad = target - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def pad_to_lane(x: jnp.ndarray, align: int = LANE_ALIGN) -> jnp.ndarray:
    """Zero-pad the trailing axis up to a multiple of ``align`` (the MXU
    lane width).  Exact for S2FP8 payload math: zero elements carry a zero
    payload, are excluded from stats, and contribute nothing to any
    contraction — so a padded attention/GEMM over payloads equals the
    unpadded one on the original columns."""
    return _pad_axis(x, x.ndim - 1,
                     _ceil_to(max(x.shape[-1], 1), align))


def as_blocked_2d(x: jnp.ndarray, block=DEFAULT_BLOCK) -> jnp.ndarray:
    """Reshape/zero-pad an arbitrary-rank tensor into a tile-aligned,
    block-divisible 2-D layout the kernels accept.  Invert with
    :func:`from_blocked_2d`."""
    if x.ndim == 2:
        x2 = x
    else:
        flat = x.reshape(-1)
        lane = min(LANE, _ceil_to(max(flat.shape[0], 1), LANE_ALIGN))
        # widen the lane so the block width and tile alignment both divide
        # it: all later padding then lands on whole trailing rows, never
        # interleaved mid-row (from_blocked_2d's flatten-and-slice inverse
        # requires the flattened element order to be a prefix)
        lane = _ceil_to(lane, math.lcm(min(block[1], lane), LANE_ALIGN))
        flat = _pad_axis(flat, 0, _ceil_to(max(flat.shape[0], 1), lane))
        x2 = flat.reshape(-1, lane)
    x2 = _pad_axis(x2, 0, _ceil_to(x2.shape[0], SUBLANE_ALIGN))
    x2 = _pad_axis(x2, 1, _ceil_to(x2.shape[1], LANE_ALIGN))
    bm = min(block[0], x2.shape[0])
    bn = min(block[1], x2.shape[1])
    x2 = _pad_axis(x2, 0, _ceil_to(x2.shape[0], bm))
    return _pad_axis(x2, 1, _ceil_to(x2.shape[1], bn))


def from_blocked_2d(y2: jnp.ndarray, shape: Tuple[int, ...]) -> jnp.ndarray:
    """Undo :func:`as_blocked_2d`: strip padding, restore the original shape."""
    if len(shape) == 2:
        return y2[: shape[0], : shape[1]]
    size = 1
    for d in shape:
        size *= d
    return y2.reshape(-1)[:size].reshape(shape)


# ---------------------------------------------------------------------------
# quantization / stats
# ---------------------------------------------------------------------------

def stats_partials_nd(x: jnp.ndarray, *, block=DEFAULT_BLOCK,
                      interpret: Optional[bool] = None):
    """Raw (log_sum, log_max, count) triplet via the Pallas blocked
    reduction, any rank/shape.  Zero-padding is exact (zeros are excluded
    from the reduction), so partials from disjoint shards combine with
    (+, max, +) — the sharded-stats building block."""
    x2 = as_blocked_2d(x.astype(jnp.float32), block)
    return stats_pallas(x2, block=block, interpret=interpret)


def stats_nd(x: jnp.ndarray, *, target_max: float = s2fp8.TARGET_MAX_LOG2,
             block=DEFAULT_BLOCK, interpret: Optional[bool] = None):
    """(alpha, beta) via the Pallas blocked reduction, any rank/shape."""
    s, mx, c = stats_partials_nd(x, block=block, interpret=interpret)
    return s2fp8.stats_from_reduction(s, mx, c, target_max)


def quant_nd(x: jnp.ndarray, *, stats=None, fmt: str = "e5m2",
             block=DEFAULT_BLOCK, interpret: Optional[bool] = None):
    """(payload, alpha, beta) with payload in x's shape, any rank.

    ``stats=(alpha, beta)`` skips the in-kernel reduction and quantizes
    with the given scalars (exact-stats / delayed-stats paths); ``fmt``
    selects the payload format (e5m2 / e4m3).
    """
    x2 = as_blocked_2d(x.astype(jnp.float32), block)
    if stats is None:
        payload2, alpha, beta = quant_pallas(x2, fmt=fmt, block=block,
                                             interpret=interpret)
    else:
        alpha, beta = stats
        payload2 = quant_apply_pallas(x2, alpha, beta, fmt=fmt, block=block,
                                      interpret=interpret)
    return from_blocked_2d(payload2, x.shape), alpha, beta


def dequant_nd(payload: jnp.ndarray, alpha, beta, *, dtype=jnp.float32,
               block=DEFAULT_BLOCK, interpret: Optional[bool] = None):
    """Dense tensor from an e5m2 payload of any rank."""
    p2 = as_blocked_2d(payload, block)
    out2 = dequant_pallas(p2, jnp.asarray(alpha, jnp.float32),
                          jnp.asarray(beta, jnp.float32),
                          block=block, interpret=interpret)
    return from_blocked_2d(out2, payload.shape).astype(dtype)


# ---------------------------------------------------------------------------
# fused truncate (Eq. 5)
# ---------------------------------------------------------------------------

def truncate_nd(x: jnp.ndarray, *, stats=None, fmt: str = "e5m2",
                fused_stats: bool = False, block=DEFAULT_BLOCK,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """Fused S2FP8 truncation of an arbitrary-rank tensor.

    Stats selection (in priority order):
      * ``stats=(alpha, beta)`` — delayed-stats mode: no reduction at all,
        a single elementwise HBM pass.
      * ``fused_stats=True``    — the two-phase single-kernel path
        (in-kernel blocked reduction; float-tolerance parity with the ref).
      * default                 — exact stats via the same monolithic jnp
        reduction the reference uses, then the fused elementwise kernel:
        bitwise-identical to ``s2fp8.truncate_value`` and still only two
        HBM passes over the tensor.
    """
    target_max = s2fp8.FMT_TARGET_MAX[fmt]
    x2 = as_blocked_2d(x.astype(jnp.float32), block)
    if stats is None and fused_stats:
        out2, _, _ = truncate_fused_pallas(x2, fmt=fmt, target_max=target_max,
                                           block=block, interpret=interpret)
    else:
        if stats is None:
            stats = s2fp8.compute_stats_jit(x, target_max=target_max)
        alpha, beta = stats
        out2 = truncate_apply_pallas(x2, alpha, beta, fmt=fmt,
                                     block=block, interpret=interpret)
    return from_blocked_2d(out2, x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# quantized GEMM
# ---------------------------------------------------------------------------

class GemmPlan(NamedTuple):
    """The grid of one payload GEMM: its logical dims, its blocks and the
    dims it pads them to (multiples of the blocks)."""
    m: int
    k: int
    n: int
    bm: int
    bk: int
    bn: int
    mp: int
    kp: int
    np: int


def gemm_plan(layout: str, a_shape, b_shape, bm=None, bk=None,
              bn=None) -> GemmPlan:
    """Alignment and tile plan of a 2-D GEMM of stored operand shapes.

    Per-layout tile alignment: a GEMM dim needs the 128-lane multiple
    only where it is the LANE (last) dim of a stored operand or of the
    output; row dims need sublane (8).  M: sublane everywhere except
    "tn" (lane of the stored [K, M] operand).  K: lane of A ("nn") or
    of both operands ("nt"), rows-only under "tn".  N: always the
    output's lane.  This keeps small-M inference GEMMs at 8-row padding
    instead of inflating them 16x.  The blocks are the caller's, else the
    tile plan (``pick_gemm_block``) of the aligned dims; each dim is then
    padded to a multiple of its block.
    """
    m, k, n = gemm_dims(layout, a_shape, b_shape)
    ma = _ceil_to(m, LANE_ALIGN if layout == "tn" else SUBLANE_ALIGN)
    ka = _ceil_to(k, SUBLANE_ALIGN if layout == "tn" else LANE_ALIGN)
    na = _ceil_to(n, LANE_ALIGN)
    hm, hk, hn = pick_gemm_block(ma, ka, na)
    bm_ = min(hm if bm is None else bm, ma)
    bk_ = min(hk if bk is None else bk, ka)
    bn_ = min(hn if bn is None else bn, na)
    return GemmPlan(m, k, n, bm_, bk_, bn_, _ceil_to(ma, bm_),
                    _ceil_to(ka, bk_), _ceil_to(na, bn_))


def _gemm_pad_plan(layout, a_payload, b_payload, bm, bk, bn, axis0: int):
    """:func:`gemm_plan` of the 2-D GEMM tile of each operand, and the
    operands zero-padded to it (``axis0`` = index of the tile's first
    axis: 0 for plain GEMMs, 1 for batched ones — the leading batch axis
    needs no padding).  The plan's MACs, padded MACs and dequantized
    elements go to ``spans.RECORDER``'s ``gemm/*`` counters, once per
    trace.  Returns ``(a_pad, b_pad, plan)``.
    """
    p = gemm_plan(layout, a_payload.shape[axis0:], b_payload.shape[axis0:],
                  bm, bk, bn)
    ga, gb = (a_payload.shape[0], b_payload.shape[0]) if axis0 else (1, 1)
    g = max(ga, gb)
    macs = p.mp * p.kp * p.np
    spans.counter(spans.GEMM_MACS, g * macs)
    spans.counter(spans.GEMM_PADDED_MACS, g * (macs - p.m * p.k * p.n))
    spans.counter(spans.GEMM_DEQUANT_ELEMS,
                  g * (macs // p.bn + macs // p.bm))
    spans.counter(spans.GEMM_OPERAND_ELEMS,
                  ga * p.m * p.k + gb * p.k * p.n)
    (ar, ac), (br, bc) = {"nn": ((p.mp, p.kp), (p.kp, p.np)),
                          "nt": ((p.mp, p.kp), (p.np, p.kp)),
                          "tn": ((p.kp, p.mp), (p.kp, p.np))}[layout]
    a_pad = _pad_axis(_pad_axis(a_payload, axis0, ar), axis0 + 1, ac)
    b_pad = _pad_axis(_pad_axis(b_payload, axis0, br), axis0 + 1, bc)
    return a_pad, b_pad, p


def qmatmul_nd(a_payload, a_alpha, a_beta, b_payload, b_alpha, b_beta, *,
               layout: str = "nn", epilogue_stats=None, fmt: str = "e5m2",
               bm: Optional[int] = None, bk: Optional[int] = None,
               bn: Optional[int] = None,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """C[M,N] = dequant(A) @ dequant(B) under ``layout``, arbitrary M/K/N.

    Ragged dims are zero-padded to the block grid (payload zeros dequantize
    to 0.0, contributing nothing to the accumulation; the Eq. 5 epilogue
    maps zero to zero) and the result is sliced back.  Block sizes default
    to the tile plan of the aligned (M, K, N) in
    ``s2fp8_matmul.pick_gemm_block`` (``REPRO_GEMM_BLOCK`` overrides).
    ``epilogue_stats=(alpha, beta)`` fuses the output-site truncation into
    the kernel's last K step.
    """
    a_pad, b_pad, p = _gemm_pad_plan(layout, a_payload, b_payload, bm, bk,
                                     bn, axis0=0)
    oa, ob = (None, None) if epilogue_stats is None else epilogue_stats
    out = s2fp8_matmul_pallas(a_pad, jnp.asarray(a_alpha, jnp.float32),
                              jnp.asarray(a_beta, jnp.float32),
                              b_pad, jnp.asarray(b_alpha, jnp.float32),
                              jnp.asarray(b_beta, jnp.float32),
                              oa, ob, layout=layout, fmt=fmt,
                              bm=p.bm, bk=p.bk, bn=p.bn, interpret=interpret)
    return out[:p.m, :p.n]


def qmatmul_batched_nd(a_payload, a_alpha, a_beta, b_payload, b_alpha, b_beta,
                       *, layout: str = "nn", out_batch: Optional[int] = None,
                       epilogue_stats=None, fmt: str = "e5m2",
                       bm: Optional[int] = None, bk: Optional[int] = None,
                       bn: Optional[int] = None,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    """C[Go,M,N] = batched dequant-GEMM under ``layout``, arbitrary M/K/N.

    The leading batch axes need no padding (block batch size is 1); the
    trailing two dims of each operand get the same per-layout tile
    alignment + block-grid zero-padding as :func:`qmatmul_nd`
    (``_gemm_pad_plan``; exact for S2FP8).  Broadcast (``Ga``/``Gb``
    dividing the combined batch) and ``out_batch`` reduction semantics
    live in ``s2fp8_matmul_batched_pallas``.
    """
    a_pad, b_pad, p = _gemm_pad_plan(layout, a_payload, b_payload, bm, bk,
                                     bn, axis0=1)
    oa, ob = (None, None) if epilogue_stats is None else epilogue_stats
    out = s2fp8_matmul_batched_pallas(
        a_pad, jnp.asarray(a_alpha, jnp.float32),
        jnp.asarray(a_beta, jnp.float32),
        b_pad, jnp.asarray(b_alpha, jnp.float32),
        jnp.asarray(b_beta, jnp.float32),
        oa, ob, layout=layout, out_batch=out_batch, fmt=fmt,
        bm=p.bm, bk=p.bk, bn=p.bn, interpret=interpret)
    return out[:, :p.m, :p.n]
