"""Pure-jnp oracles for every Pallas kernel in this package.

Each function here is the semantic ground truth the kernels/tests compare
against (assert_allclose in tests/test_kernels.py).  No pallas imports.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fp8, s2fp8


# --------------------------------------------------------------------------
# s2fp8_quant: stats + forward map + e5m2 cast
# --------------------------------------------------------------------------

def s2fp8_quant_ref(x: jnp.ndarray):
    """Returns (payload_e5m2, alpha, beta) for a 2-D tensor."""
    t = s2fp8.quantize(x)
    return t.payload, t.alpha, t.beta


def s2fp8_dequant_ref(payload, alpha, beta, dtype=jnp.float32):
    return s2fp8.dequantize(s2fp8.S2FP8Tensor(payload, alpha, beta), dtype)


def s2fp8_truncate_ref(x, stats=None, fmt: str = "e5m2"):
    """Eq. 5 round-trip oracle for the fused truncate kernel (any rank)."""
    if fmt == "e4m3":
        return s2fp8.truncate_value_e4m3(x, stats=stats)
    return s2fp8.truncate_value(x, stats=stats)


# --------------------------------------------------------------------------
# s2fp8_matmul: C = dequant(A) @ dequant(B), f32 accumulation
# --------------------------------------------------------------------------

# GEMM operand layouts.  The payload-domain training path (core/qdot.py)
# computes the backward GEMMs dA = g·Bᵀ and dB = Aᵀ·g directly from the
# payloads the forward saved — the layout selects which operand is consumed
# transposed via dot_general dimension numbers (the Pallas kernel swaps
# BlockSpec index maps to match), so no payload transpose is ever
# materialized in HBM.
#
#   "nn": C[M,N] = A[M,K]  @ B[K,N]
#   "nt": C[M,N] = A[M,K]  @ B[N,K]ᵀ      (B stored row-major [N,K])
#   "tn": C[M,N] = A[K,M]ᵀ @ B[K,N]       (A stored row-major [K,M])
GEMM_LAYOUTS = ("nn", "nt", "tn")
GEMM_CONTRACT = {
    "nn": (((1,), (0,)), ((), ())),
    "nt": (((1,), (1,)), ((), ())),
    "tn": (((0,), (0,)), ((), ())),
}


# The same layouts as einsum specs (for the accumulation bound below).
GEMM_SPECS = {"nn": "mk,kn->mn", "nt": "mk,nk->mn", "tn": "km,kn->mn"}


def gemm_dims(layout: str, a_shape, b_shape):
    """(m, k, n) of the logical GEMM for stored operand shapes."""
    if layout == "nn":
        (m, k), (k2, n) = a_shape, b_shape
    elif layout == "nt":
        (m, k), (n, k2) = a_shape, b_shape
    elif layout == "tn":
        (k, m), (k2, n) = a_shape, b_shape
    else:
        raise ValueError(f"unknown GEMM layout {layout!r}; want {GEMM_LAYOUTS}")
    if k != k2:
        raise ValueError(f"contraction mismatch: {a_shape} x {b_shape} "
                         f"under layout {layout!r}")
    return m, k, n


def s2fp8_matmul_ref(a_payload, a_alpha, a_beta, b_payload, b_alpha, b_beta,
                     out_alpha=None, out_beta=None, *, layout: str = "nn",
                     fmt: str = "e5m2"):
    """Dequant-GEMM oracle with optional fused-epilogue semantics.

    ``out_alpha/out_beta`` — when given, the output is Eq. 5-truncated with
    those stats (the kernel's in-VMEM epilogue, expressed elementwise)."""
    a = s2fp8_dequant_ref(a_payload, a_alpha, a_beta)
    b = s2fp8_dequant_ref(b_payload, b_alpha, b_beta)
    # f32 products on every platform (a TPU's default contraction rounds
    # f32 operands to bf16), like the payload kernels' MXU_PRECISION
    y = jax.lax.dot_general(a, b, GEMM_CONTRACT[layout],
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    if out_alpha is not None:
        y = s2fp8_truncate_ref(y, stats=(out_alpha, out_beta), fmt=fmt)
    return y


_F32_UNIT_ROUNDOFF = 2.0 ** -24


def dot_error_bound(spec: str, a, b) -> np.ndarray:
    """Elementwise bound on ``|x - y|`` for two f32 evaluations x, y of
    ``einsum(spec, a, b)`` that add the K products in different orders.

    Each evaluation lies within ``gamma_K * (|a| . |b|)`` of the exact
    value, ``gamma_K = K u / (1 - K u)``, ``u = 2^-24`` (Higham, Accuracy
    and Stability of Numerical Algorithms, 2nd ed., eq. 3.5), whatever its
    order, K-blocking or zero padding; two of them therefore differ by at
    most twice that.  This is the tolerance between a tiled kernel and an
    XLA dot: neither fixes the summation order (XLA:CPU's depends on the
    operand shapes, so a lane-padded kernel tile sums differently from the
    unpadded oracle).
    """
    ins, out = spec.split("->")
    sa, sb = ins.split(",")
    k = math.prod(np.shape(a)[sa.index(c)]
                  for c in (set(sa) & set(sb)) - set(out))
    gamma = k * _F32_UNIT_ROUNDOFF / (1.0 - k * _F32_UNIT_ROUNDOFF)
    return 2.0 * gamma * np.einsum(spec, np.abs(np.asarray(a, np.float64)),
                                   np.abs(np.asarray(b, np.float64)),
                                   optimize=True)


def truncation_bracket(y, bound, truncate):
    """``(lo, hi)`` with ``lo <= truncate(x) <= hi`` for every f32 x within
    ``bound`` of ``y``.  Eq. 5 truncation is monotone (positive alpha,
    monotone maps, round-to-nearest), so the truncated interval endpoints
    bracket it: where y sits farther than ``bound`` from a rounding
    boundary, ``lo == hi`` and the comparison is exact."""
    y = np.asarray(y, np.float64)
    lo = np.nextafter(np.float32(y - bound), np.float32(-np.inf))
    hi = np.nextafter(np.float32(y + bound), np.float32(np.inf))
    return np.asarray(truncate(lo)), np.asarray(truncate(hi))


# Batched variants: one leading batch axis on both operands, per-slice
# contraction per GEMM_CONTRACT, dot_general batch dims (0, 0).
GEMM_CONTRACT_BATCHED = {
    "nn": (((2,), (1,)), ((0,), (0,))),
    "nt": (((2,), (2,)), ((0,), (0,))),
    "tn": (((1,), (1,)), ((0,), (0,))),
}


def _expand_batch(x, g: int):
    """[Gx, ...] -> [G, ...] where slice ``g_i`` is ``x[g_i % Gx]`` — the
    trailing-aligned broadcast of the batched payload GEMM."""
    gx = x.shape[0]
    if gx == g:
        return x
    return jnp.broadcast_to(x[None], (g // gx,) + x.shape
                            ).reshape((g,) + x.shape[1:])


def s2fp8_matmul_batched_ref(a_payload, a_alpha, a_beta,
                             b_payload, b_alpha, b_beta,
                             out_alpha=None, out_beta=None, *,
                             layout: str = "nn", out_batch=None,
                             fmt: str = "e5m2"):
    """Batched dequant-GEMM oracle: ``a [Ga, ., .] x b [Gb, ., .]`` over
    combined batch ``G = max(Ga, Gb)`` (operand slice for step ``g`` is
    ``g % Gx``); ``out_batch < G`` sums groups of ``G // out_batch``
    (``g // out_batch`` constant within a group) — the broadcast-operand
    gradient reduction.  Per-slice layout semantics match
    :func:`s2fp8_matmul_ref`."""
    g = max(a_payload.shape[0], b_payload.shape[0])
    if g % a_payload.shape[0] or g % b_payload.shape[0]:
        raise ValueError(f"batch sizes {a_payload.shape[0]} / "
                         f"{b_payload.shape[0]} do not divide evenly")
    go = g if out_batch is None else out_batch
    if g % go:
        raise ValueError(f"out_batch {go} does not divide batch {g}")
    a = _expand_batch(s2fp8_dequant_ref(a_payload, a_alpha, a_beta), g)
    b = _expand_batch(s2fp8_dequant_ref(b_payload, b_alpha, b_beta), g)
    y = jax.lax.dot_general(a, b, GEMM_CONTRACT_BATCHED[layout],
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    if go != g:
        y = y.reshape((g // go, go) + y.shape[1:]).sum(axis=0)
    if out_alpha is not None:
        y = s2fp8_truncate_ref(y, stats=(out_alpha, out_beta), fmt=fmt)
    return y


# --------------------------------------------------------------------------
# selective_scan (Mamba-1 recurrence)
# --------------------------------------------------------------------------

def selective_scan_ref(x, dt, bmat, cmat, a, d_skip):
    """x, dt: [B,S,di]; bmat, cmat: [B,S,n]; a: [di,n]; d_skip: [di].
    Returns (y [B,S,di], h_final [B,di,n]).  Pure lax.scan oracle."""
    b, s, di = x.shape
    n = bmat.shape[-1]

    def step(h, inp):
        xt, dtt, bt, ct = inp
        da = jnp.exp(dtt[:, :, None] * a)
        h = h * da + (dtt * xt)[:, :, None] * bt[:, None, :]
        y = jnp.einsum("bdn,bn->bd", h, ct) + d_skip * xt
        return h, y

    h0 = jnp.zeros((b, di, n), jnp.float32)
    xs = tuple(jnp.moveaxis(v.astype(jnp.float32), 1, 0)
               for v in (x, dt, bmat, cmat))
    hn, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1), hn


# --------------------------------------------------------------------------
# flash_attention: causal / full softmax(QK^T/sqrt(d)) V
# --------------------------------------------------------------------------

def attention_ref(q, k, v, *, causal: bool = True, window: int | None = None):
    """q: [B,H,Sq,D], k/v: [B,H,Sk,D] (kv heads already broadcast). f32 math."""
    q = q.astype(jnp.float32)
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    d = q.shape[-1]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(d).astype(jnp.float32)
    sq, sk = q.shape[2], k.shape[2]
    qpos = jnp.arange(sq)[:, None] + (sk - sq)  # align ends (decode-friendly)
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask = mask & (kpos <= qpos)
    if window is not None:
        mask = mask & (kpos > qpos - window)
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)
