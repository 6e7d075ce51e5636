"""Pallas TPU flash attention (online softmax), causal / sliding-window.

Target kernel for the prefill hot-spot.  Memory-hierarchy reasoning:
Q/K/V tiles stream HBM->VMEM; the (bq, bk) score tile lives only in
registers/VMEM (never HBM — this is the flash insight, reexpressed for TPU);
running max / denominator / output accumulator live in VMEM scratch across
the sequential kv-grid.  Default tiles (bq, bk) = (512, 512) with d<=256:
~ (2*512*d*4 + 512*512*4 + 512*d*4) bytes ~= 2.6 MiB for d=128 — fits VMEM
with double buffering.  MXU dims are multiples of 128.

The masked logit fill is -1e30 (finite) instead of -inf so the online
rescaling never produces NaN; fully-masked tiles are additionally zeroed
via the mask on the probability tile.

The second half of this module is the *payload-domain* variant (ISSUE 6):
Q/K/V arrive as 1-byte S2FP8 payloads with per-site bank (alpha, beta)
scalars, are dequantized in-tile on the VPU right before the MXU issue,
and the output tile gets the fused Eq. 5 truncation epilogue
(s2fp8_matmul.py idiom) before it ever leaves VMEM.  The backward is the
recompute schedule of models/flash.py split into two kernels (dq, and
per-head dk/dv) so no output block is revisited after its flush.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import auto_interpret
from repro.kernels.dispatch import pad_to_lane
from repro.kernels.s2fp8_matmul import MXU_PRECISION, _dequant
from repro.kernels.s2fp8_quant import _truncate_body
from repro.obs import spans

_MASK_VALUE = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s,
                  *, sq, sk, bq, bk, causal, window):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _MASK_VALUE)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0].astype(jnp.float32)              # (bq, d)
    k = k_ref[0].astype(jnp.float32)              # (bk, d)
    v = v_ref[0].astype(jnp.float32)              # (bk, d)
    d = q.shape[-1]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=MXU_PRECISION,
                            preferred_element_type=jnp.float32)
    s = s * (1.0 / jnp.sqrt(jnp.float32(d)))      # (bq, bk)

    # Position mask. Query rows are aligned to the END of the kv axis so the
    # same kernel serves self-attention (sq == sk) and chunked decode.
    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + (sk - sq)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), dtype=jnp.bool_)
    if causal:
        mask = mask & (kpos <= qpos)
    if window is not None:
        mask = mask & (kpos > qpos - window)
    s = jnp.where(mask, s, _MASK_VALUE)

    m_prev = m_s[:, :1]                            # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)   # (bq, bk)
    l_new = l_s[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), precision=MXU_PRECISION,
        preferred_element_type=jnp.float32)
    m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
    l_s[...] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(ik == nk - 1)
    def _fin():
        denom = l_s[:, :1]
        denom = jnp.where(denom == 0.0, 1.0, denom)
        o_ref[0] = (acc_s[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "bq", "bk", "interpret"))
def flash_attention_pallas(q, k, v, *, causal=True, window=None,
                           bq=512, bk=512, interpret: bool | None = None):
    """q: [B,H,Sq,D]; k,v: [B,H,Sk,D] (kv heads pre-broadcast). Returns like q."""
    if interpret is None:
        interpret = auto_interpret()
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = min(bq, sq)
    bk = min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, sk, bq, bk)
    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * h, sk, d)
    vr = v.reshape(b * h, sk, d)
    grid = (b * h, sq // bq, sk // bk)
    kernel = functools.partial(_flash_kernel, sq=sq, sk=sk, bq=bq, bk=bk,
                               causal=causal, window=window)
    out = pl.pallas_call(
        kernel,
        name=spans.FLASH,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, iq, ik: (bh, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, sq, d)


# ===========================================================================
# Payload-domain flash attention (ISSUE 6)
# ===========================================================================
#
# Tile lifecycle (forward): per (head, iq) output block, the sequential
# inner kv-grid streams one (bq, d) Q payload tile and (bk, d) K/V payload
# tiles HBM->VMEM at 1 byte/element, dequantizes them on the VPU with the
# site's (alpha, beta), issues QK^T on the MXU, and keeps the (bq, bk)
# score/prob tile plus the running (max, denom, acc) entirely in
# VMEM scratch.  At the last kv step the accumulator is normalized, the
# rowwise logsumexp is emitted (the only O(S) residual), and — when the
# output site's stats are fused — the tile is truncated in-register via
# Eq. 5 before the single HBM writeback.  Nothing O(S^2) ever touches HBM.


def _attn_mask(iq, ik, bq, bk, sq, sk, causal, window):
    """(bq, bk) position mask; query rows END-aligned to the kv axis."""
    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + (sk - sq)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), dtype=jnp.bool_)
    if causal:
        mask = mask & (kpos <= qpos)
    if window is not None:
        mask = mask & (kpos > qpos - window)
    return mask


def _qflash_fwd_kernel(qa, qb, ka, kb, va, vb, oa, ob,
                       q_ref, k_ref, v_ref, o_ref, lse_ref,
                       m_s, l_s, acc_s,
                       *, sq, sk, bq, bk, causal, window, scale, fmt,
                       epilogue):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _MASK_VALUE)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # 1-byte HBM->VMEM stream; Eq. 4 inverse map on the VPU, straight into
    # the MXU contraction.
    q = _dequant(q_ref[0], qa[0, 0], qb[0, 0])     # (bq, d) f32
    k = _dequant(k_ref[0], ka[0, 0], kb[0, 0])     # (bk, d) f32
    v = _dequant(v_ref[0], va[0, 0], vb[0, 0])     # (bk, d) f32

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=MXU_PRECISION,
                            preferred_element_type=jnp.float32) * scale
    mask = _attn_mask(iq, ik, bq, bk, sq, sk, causal, window)
    s = jnp.where(mask, s, _MASK_VALUE)

    m_prev = m_s[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    l_new = l_s[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), precision=MXU_PRECISION,
        preferred_element_type=jnp.float32)
    m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
    l_s[...] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(ik == nk - 1)
    def _fin():
        l_fin = l_s[:, :1]
        denom = jnp.where(l_fin == 0.0, 1.0, l_fin)
        acc = acc_s[...] / denom
        lse_ref[0] = m_s[:, :1] + jnp.log(jnp.maximum(l_fin, 1e-30))
        if epilogue:
            # fused Eq. 5 epilogue: the output tile leaves VMEM already in
            # the out site's representable set (s2fp8_matmul.py idiom)
            acc = _truncate_body(acc, oa[0, 0], ob[0, 0], fmt)
        o_ref[0] = acc


def _qflash_dq_kernel(qa, qb, ka, kb, va, vb, ga, gb,
                      q_ref, k_ref, v_ref, g_ref, lse_ref, del_ref,
                      dq_ref, acc_s,
                      *, sq, sk, bq, bk, causal, window, scale):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)

    q = _dequant(q_ref[0], qa[0, 0], qb[0, 0])
    k = _dequant(k_ref[0], ka[0, 0], kb[0, 0])
    v = _dequant(v_ref[0], va[0, 0], vb[0, 0])
    do = _dequant(g_ref[0], ga[0, 0], gb[0, 0])
    lse = lse_ref[0]                               # (bq, 1)
    dlt = del_ref[0]                               # (bq, 1)

    # score-tile recompute from the 1-byte payloads (no saved probs)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=MXU_PRECISION,
                            preferred_element_type=jnp.float32) * scale
    mask = _attn_mask(iq, ik, bq, bk, sq, sk, causal, window)
    s = jnp.where(mask, s, _MASK_VALUE)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)

    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             precision=MXU_PRECISION,
                             preferred_element_type=jnp.float32)
    ds = p * (dp - dlt) * scale
    acc_s[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                      precision=MXU_PRECISION,
                                      preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _fin():
        dq_ref[0] = acc_s[...]


def _qflash_dkdv_kernel(qa, qb, ka, kb, va, vb, ga, gb,
                        q_ref, k_ref, v_ref, g_ref, lse_ref, del_ref,
                        dk_ref, dv_ref, dk_s, dv_s,
                        *, sq, sk, bq, bk, causal, window, scale):
    ik = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    q = _dequant(q_ref[0], qa[0, 0], qb[0, 0])
    k = _dequant(k_ref[0], ka[0, 0], kb[0, 0])
    v = _dequant(v_ref[0], va[0, 0], vb[0, 0])
    do = _dequant(g_ref[0], ga[0, 0], gb[0, 0])
    lse = lse_ref[0]
    dlt = del_ref[0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=MXU_PRECISION,
                            preferred_element_type=jnp.float32) * scale
    mask = _attn_mask(iq, ik, bq, bk, sq, sk, causal, window)
    s = jnp.where(mask, s, _MASK_VALUE)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)

    dv_s[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                     precision=MXU_PRECISION,
                                     preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             precision=MXU_PRECISION,
                             preferred_element_type=jnp.float32)
    ds = p * (dp - dlt) * scale
    dk_s[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                     precision=MXU_PRECISION,
                                     preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _fin():
        dk_ref[0] = dk_s[...]
        dv_ref[0] = dv_s[...]


def _scalar(v):
    return jnp.asarray(v, jnp.float32).reshape(1, 1)


def _chunk(block, s):
    """Largest block <= `block` that divides the sequence length."""
    return math.gcd(min(block, s), s)


def qflash_fwd_pallas(qp, kp, vp, q_stats, k_stats, v_stats, *, g,
                      causal=True, window=None, scale=None, out_stats=None,
                      fmt="e5m2", bq=512, bk=512, interpret=None):
    """Payload-domain flash forward.

    qp: [BH, Sq, d] FP8 payload with BH = B*KV*G; kp/vp: [BKV, Sk, d]
    payloads.  Grouped-query K/V blocks are re-read per query group via the
    `bh // g` index map — never materialized per head.  ``*_stats`` are
    the bank (alpha, beta) scalar pairs.  Ragged head dims are zero-padded
    to the 128-lane grid (exact for S2FP8); ``scale`` is the caller's true
    1/sqrt(d).  Returns (out f32 [BH, Sq, d], lse f32 [BH, Sq, 1]); when
    ``out_stats`` is given the output tile gets the fused Eq. 5 truncation
    epilogue before leaving VMEM.
    """
    if interpret is None:
        interpret = auto_interpret()
    bh, sq, d0 = qp.shape
    bkv, sk, _ = kp.shape
    assert bh == bkv * g, (qp.shape, kp.shape, g)
    if scale is None:
        scale = 1.0 / math.sqrt(d0)
    qp, kp, vp = pad_to_lane(qp), pad_to_lane(kp), pad_to_lane(vp)
    d = qp.shape[-1]
    bq = _chunk(bq, sq)
    bk = _chunk(bk, sk)
    epilogue = out_stats is not None
    oa, ob = out_stats if epilogue else (1.0, 0.0)
    kernel = functools.partial(
        _qflash_fwd_kernel, sq=sq, sk=sk, bq=bq, bk=bk, causal=causal,
        window=window, scale=float(scale), fmt=fmt, epilogue=epilogue)
    scal = pl.BlockSpec((1, 1), lambda h, iq, ik: (0, 0))
    out, lse = pl.pallas_call(
        kernel,
        name=spans.QFLASH_FWD,
        grid=(bh, sq // bq, sk // bk),
        in_specs=[scal] * 8 + [
            pl.BlockSpec((1, bq, d), lambda h, iq, ik: (h, iq, 0)),
            pl.BlockSpec((1, bk, d), lambda h, iq, ik: (h // g, ik, 0)),
            pl.BlockSpec((1, bk, d), lambda h, iq, ik: (h // g, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda h, iq, ik: (h, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, iq, ik: (h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(_scalar(q_stats[0]), _scalar(q_stats[1]),
      _scalar(k_stats[0]), _scalar(k_stats[1]),
      _scalar(v_stats[0]), _scalar(v_stats[1]),
      _scalar(oa), _scalar(ob), qp, kp, vp)
    return out[..., :d0], lse


def qflash_bwd_pallas(qp, kp, vp, gp, q_stats, k_stats, v_stats, g_stats,
                      lse, delta, *, g, causal=True, window=None, scale=None,
                      bq=512, bk=512, interpret=None):
    """Recompute-based payload flash backward (two kernels).

    Residual inputs are the 1-byte Q/K/V payloads plus the quantized
    output cotangent ``gp`` [BH, Sq, d] and the rowwise ``lse``/``delta``
    [BH, Sq, 1] f32 columns; score tiles are recomputed per (bq, bk) block.
    The trailing unit axis keeps their (1, bq, 1) blocks on the TPU's
    (8, 128) tiling rule (a (1, bq) block over [BH, Sq] breaks it).
    The dq kernel accumulates over the sequential kv grid; the dk/dv
    kernel accumulates over the sequential q grid and emits PER-HEAD
    [BH, Sk, d] gradients (each output block written exactly once — the
    TPU revisit constraint); the caller reduces the query-group axis.
    Returns raw f32 (dq, dk_per_head, dv_per_head).
    """
    if interpret is None:
        interpret = auto_interpret()
    bh, sq, d0 = qp.shape
    bkv, sk, _ = kp.shape
    assert bh == bkv * g and gp.shape == qp.shape, (qp.shape, kp.shape, g)
    if scale is None:
        scale = 1.0 / math.sqrt(d0)
    qp, kp, vp, gp = (pad_to_lane(t) for t in (qp, kp, vp, gp))
    d = qp.shape[-1]
    bq = _chunk(bq, sq)
    bk = _chunk(bk, sk)
    common = dict(sq=sq, sk=sk, bq=bq, bk=bk, causal=causal, window=window,
                  scale=float(scale))
    scalars = (_scalar(q_stats[0]), _scalar(q_stats[1]),
               _scalar(k_stats[0]), _scalar(k_stats[1]),
               _scalar(v_stats[0]), _scalar(v_stats[1]),
               _scalar(g_stats[0]), _scalar(g_stats[1]))

    scal_q = pl.BlockSpec((1, 1), lambda h, iq, ik: (0, 0))
    dq = pl.pallas_call(
        functools.partial(_qflash_dq_kernel, **common),
        name=spans.QFLASH_BWD_DQ,
        grid=(bh, sq // bq, sk // bk),
        in_specs=[scal_q] * 8 + [
            pl.BlockSpec((1, bq, d), lambda h, iq, ik: (h, iq, 0)),
            pl.BlockSpec((1, bk, d), lambda h, iq, ik: (h // g, ik, 0)),
            pl.BlockSpec((1, bk, d), lambda h, iq, ik: (h // g, ik, 0)),
            pl.BlockSpec((1, bq, d), lambda h, iq, ik: (h, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, iq, ik: (h, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, iq, ik: (h, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, iq, ik: (h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(*scalars, qp, kp, vp, gp, lse, delta)

    scal_k = pl.BlockSpec((1, 1), lambda h, ik, iq: (0, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_qflash_dkdv_kernel, **common),
        name=spans.QFLASH_BWD_DKDV,
        grid=(bh, sk // bk, sq // bq),
        in_specs=[scal_k] * 8 + [
            pl.BlockSpec((1, bq, d), lambda h, ik, iq: (h, iq, 0)),
            pl.BlockSpec((1, bk, d), lambda h, ik, iq: (h // g, ik, 0)),
            pl.BlockSpec((1, bk, d), lambda h, ik, iq: (h // g, ik, 0)),
            pl.BlockSpec((1, bq, d), lambda h, ik, iq: (h, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, ik, iq: (h, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, ik, iq: (h, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda h, ik, iq: (h, ik, 0)),
            pl.BlockSpec((1, bk, d), lambda h, ik, iq: (h, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, sk, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
    )(*scalars, qp, kp, vp, gp, lse, delta)
    return dq[..., :d0], dk[..., :d0], dv[..., :d0]


# ---------------------------------------------------------------------------
# pure-jnp grouped flash references (CPU / ref-backend path)
# ---------------------------------------------------------------------------
# Op-for-op ports of models/flash.py's forward/backward schedule, kept in
# lockstep on purpose: tests pin the payload node's VJP against it, and the
# zero-reduction jaxpr assertion counts on the backward containing no
# reduce primitives besides the delta identity (computed by the caller).
# Inputs here are DEQUANTIZED payloads, so with shared site stats these
# equal the Fig. 4 truncate->flash->truncate chain on f32 tensors.


def _chunk_mask(iq, ik, q_chunk, kv_chunk, sq, sk, causal, window):
    qpos = iq * q_chunk + jnp.arange(q_chunk)[:, None] + (sk - sq)
    kpos = ik * kv_chunk + jnp.arange(kv_chunk)[None, :]
    mask = jnp.ones((q_chunk, kv_chunk), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    return mask


def flash_fwd_reference(q, k, v, *, causal=True, window=None,
                        q_chunk=512, kv_chunk=512):
    """Grouped flash forward, f32 in/out; returns (out, lse [B,KV,G,Sq,1])."""
    b, kvh, g, sq, d = q.shape
    sk = k.shape[2]
    q_chunk = _chunk(q_chunk, sq)
    kv_chunk = _chunk(kv_chunk, sk)
    nq, nk = sq // q_chunk, sk // kv_chunk
    scale = 1.0 / math.sqrt(d)
    kc = k.reshape(b, kvh, nk, kv_chunk, d)
    vc = v.reshape(b, kvh, nk, kv_chunk, d)
    qc = q.reshape(b, kvh, g, nq, q_chunk, d)

    def q_step(iq):
        qi = jax.lax.dynamic_index_in_dim(qc, iq, 3, keepdims=False) \
            .astype(jnp.float32)

        def kv_step(carry, ik):
            m, l, acc = carry
            ki = jax.lax.dynamic_index_in_dim(kc, ik, 2, keepdims=False)
            vi = jax.lax.dynamic_index_in_dim(vc, ik, 2, keepdims=False)
            s = jnp.einsum("bkgqd,bksd->bkgqs", qi,
                           ki.astype(jnp.float32)) * scale
            mask = _chunk_mask(iq, ik, q_chunk, kv_chunk, sq, sk, causal,
                               window)
            s = jnp.where(mask[None, None, None], s, _MASK_VALUE)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(mask[None, None, None], jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * corr + jnp.einsum("bkgqs,bksd->bkgqd", p,
                                              vi.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kvh, g, q_chunk, 1), _MASK_VALUE, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, q_chunk, 1), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, q_chunk, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        l = jnp.where(l == 0.0, 1.0, l)
        return acc / l, lse

    outs = jax.lax.map(q_step, jnp.arange(nq))
    out = jnp.moveaxis(outs[0], 0, 3).reshape(b, kvh, g, sq, d)
    lse = jnp.moveaxis(outs[1], 0, 3).reshape(b, kvh, g, sq, 1)
    return out, lse


def flash_bwd_reference(q, k, v, dout, lse, delta, *, causal=True,
                        window=None, q_chunk=512, kv_chunk=512):
    """Grouped flash backward over precomputed (lse, delta); f32 in/out.

    Contains NO reduce primitives — every contraction is a dot_general and
    delta (the flash-2 rowwise identity) is supplied by the caller.
    """
    b, kvh, g, sq, d = q.shape
    sk = k.shape[2]
    q_chunk = _chunk(q_chunk, sq)
    kv_chunk = _chunk(kv_chunk, sk)
    nq, nk = sq // q_chunk, sk // kv_chunk
    scale = 1.0 / math.sqrt(d)

    kc = k.reshape(b, kvh, nk, kv_chunk, d)
    vc = v.reshape(b, kvh, nk, kv_chunk, d)
    qc = q.reshape(b, kvh, g, nq, q_chunk, d)
    dc = dout.astype(jnp.float32).reshape(b, kvh, g, nq, q_chunk, d)
    lc = lse.reshape(b, kvh, g, nq, q_chunk, 1)
    dl = delta.reshape(b, kvh, g, nq, q_chunk, 1)

    def q_step(carry, iq):
        dk_acc, dv_acc = carry
        qi = jax.lax.dynamic_index_in_dim(qc, iq, 3, keepdims=False) \
            .astype(jnp.float32)
        di = jax.lax.dynamic_index_in_dim(dc, iq, 3, keepdims=False)
        li = jax.lax.dynamic_index_in_dim(lc, iq, 3, keepdims=False)
        deli = jax.lax.dynamic_index_in_dim(dl, iq, 3, keepdims=False)

        def kv_step(inner, ik):
            dq_acc, dk_a, dv_a = inner
            ki = jax.lax.dynamic_index_in_dim(kc, ik, 2, keepdims=False) \
                .astype(jnp.float32)
            vi = jax.lax.dynamic_index_in_dim(vc, ik, 2, keepdims=False) \
                .astype(jnp.float32)
            s = jnp.einsum("bkgqd,bksd->bkgqs", qi, ki) * scale
            mask = _chunk_mask(iq, ik, q_chunk, kv_chunk, sq, sk, causal,
                               window)
            s = jnp.where(mask[None, None, None], s, _MASK_VALUE)
            p = jnp.exp(s - li)
            p = jnp.where(mask[None, None, None], p, 0.0)
            dv_blk = jnp.einsum("bkgqs,bkgqd->bksd", p, di)
            dp = jnp.einsum("bkgqd,bksd->bkgqs", di, vi)
            ds = p * (dp - deli) * scale
            dq_blk = jnp.einsum("bkgqs,bksd->bkgqd", ds, ki)
            dk_blk = jnp.einsum("bkgqs,bkgqd->bksd", ds, qi)
            dk_a = jax.lax.dynamic_update_index_in_dim(
                dk_a, jax.lax.dynamic_index_in_dim(dk_a, ik, 2,
                                                   keepdims=False)
                + dk_blk, ik, 2)
            dv_a = jax.lax.dynamic_update_index_in_dim(
                dv_a, jax.lax.dynamic_index_in_dim(dv_a, ik, 2,
                                                   keepdims=False)
                + dv_blk, ik, 2)
            return (dq_acc + dq_blk, dk_a, dv_a), None

        dq0 = jnp.zeros((b, kvh, g, q_chunk, d), jnp.float32)
        (dqi, dk_acc, dv_acc), _ = jax.lax.scan(
            kv_step, (dq0, dk_acc, dv_acc), jnp.arange(nk))
        return (dk_acc, dv_acc), dqi

    dk0 = jnp.zeros((b, kvh, nk, kv_chunk, d), jnp.float32)
    dv0 = jnp.zeros((b, kvh, nk, kv_chunk, d), jnp.float32)
    (dkc, dvc), dqs = jax.lax.scan(q_step, (dk0, dv0), jnp.arange(nq))
    dq = jnp.moveaxis(dqs, 0, 3).reshape(b, kvh, g, sq, d)
    dk = dkc.reshape(b, kvh, sk, d)
    dv = dvc.reshape(b, kvh, sk, d)
    return dq, dk, dv
