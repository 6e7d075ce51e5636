"""Pallas TPU kernel: paged S2FP8 decode attention via block-table gather.

One decode step attends a single query token per slot against that slot's
KV blocks in the shared payload pool (serving/paged_cache.py).  The block
table and per-slot positions are **scalar-prefetched**
(``pltpu.PrefetchScalarGridSpec``) so the pool's BlockSpec index map can
read ``table[slot, j]`` directly: the grid walks each slot's logical
blocks, the DMA engine fetches exactly that slot's payload blocks
HBM->VMEM at 1 byte/element, and the Eq. 4 inverse map (shared
``_dequant``) runs on the VPU right before the MXU contractions.  No dense
fp32 cache — and nothing proportional to the whole pool — is ever
materialized; per (slot, kv-head) the resident set is one (G, hd) query
tile, one (block, hd) K/V payload block pair and the (G, ·) running
max/denominator/accumulator scratch of the online softmax.

Positions mask per-slot: block j covers cache positions [j*block,
(j+1)*block); rows past ``positions[slot]`` — right-padding, not-yet-
written tail, the trash block 0 that dead slots' table rows point at —
are masked to -1e30 before the softmax update.  Position 0 is always
"valid" even for dead slots; the pool's zero-init and the encode clamp
keep every maskable value finite, so dead-slot outputs are finite garbage
the host discards.

TPU-tiling note: payload blocks are (block, hd) fp8 tiles — production
block sizes should respect the fp8 minimum tile (32, 128); the serving
default (16, 64) targets the interpret-mode CI path and small heads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import auto_interpret
from repro.kernels.s2fp8_matmul import MXU_PRECISION, _dequant
from repro.obs import spans

_MASK_VALUE = -1e30


def _scalar(v):
    return jnp.asarray(v, jnp.float32).reshape(1, 1)


def _paged_kernel(table_ref, pos_ref, ka, kb, va, vb,
                  q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *, blk):
    b = pl.program_id(0)
    j = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _MASK_VALUE)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0, 0].astype(jnp.float32)               # (G, hd)
    k = _dequant(k_ref[0, 0], ka[0, 0], kb[0, 0])     # (blk, hd)
    v = _dequant(v_ref[0, 0], va[0, 0], vb[0, 0])
    d = q.shape[-1]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=MXU_PRECISION,
                            preferred_element_type=jnp.float32)
    s = s * (1.0 / jnp.sqrt(jnp.float32(d)))          # (G, blk)

    kpos = j * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos <= pos_ref[b]
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

    @pl.when(j == nb - 1)
    def _fin():
        denom = l_s[:, :1]
        denom = jnp.where(denom == 0.0, 1.0, denom)
        o_ref[0, 0] = (acc_s[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("fmt", "interpret"))
def paged_decode_attention(q, kp, vp, k_alpha, k_beta, v_alpha, v_beta,
                           table, positions, *, fmt: str = "e5m2",
                           interpret: bool | None = None):
    """q: [B, KV, G, hd] f32; kp/vp: [n_blocks, KV, block, hd] fp8 payload;
    table: [B, max_blocks] int32 (0 = trash); positions: [B] int32 current
    per-slot cache position.  Returns [B, KV, G, hd] f32.

    ``fmt`` documents the payload grid; the dequant map itself is driven by
    the payload dtype.  ``interpret=None`` auto-detects (compiled on TPU).
    """
    del fmt
    interpret = auto_interpret() if interpret is None else interpret
    b, kvh, g, hd = q.shape
    _, kvh2, blk, hd2 = kp.shape
    assert (kvh, hd) == (kvh2, hd2), (q.shape, kp.shape)
    slots, max_b = table.shape
    assert slots == b, (slots, b)

    scalar = pl.BlockSpec((1, 1), lambda bi, h, j, tr, pr: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kvh, max_b),
        in_specs=[
            scalar, scalar, scalar, scalar,
            pl.BlockSpec((1, 1, g, hd),
                         lambda bi, h, j, tr, pr: (bi, h, 0, 0)),
            pl.BlockSpec((1, 1, blk, hd),
                         lambda bi, h, j, tr, pr: (tr[bi, j], h, 0, 0)),
            pl.BlockSpec((1, 1, blk, hd),
                         lambda bi, h, j, tr, pr: (tr[bi, j], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda bi, h, j, tr, pr: (bi, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, blk=blk),
        name=spans.PAGED_DECODE,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, hd), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(table, jnp.int32), jnp.asarray(positions, jnp.int32),
      _scalar(k_alpha), _scalar(k_beta), _scalar(v_alpha), _scalar(v_beta),
      q, kp, vp)


def paged_decode_reference(q, kp, vp, k_alpha, k_beta, v_alpha, v_beta,
                           table, positions):
    """Pure-jnp gather + dequant + masked softmax oracle for the kernel."""
    b, kvh, g, hd = q.shape
    blk = kp.shape[2]
    max_b = table.shape[1]
    kg = jnp.moveaxis(kp[table], 1, 2).reshape(b, kvh, max_b * blk, hd)
    vg = jnp.moveaxis(vp[table], 1, 2).reshape(b, kvh, max_b * blk, hd)
    kf = _dequant(kg, k_alpha, k_beta)
    vf = _dequant(vg, v_alpha, v_beta)
    s = jnp.einsum("bkgd,bksd->bkgs", q.astype(jnp.float32), kf)
    s = s / jnp.sqrt(jnp.float32(hd))
    kpos = jnp.arange(max_b * blk)
    mask = kpos[None, :] <= positions[:, None]        # [B, S]
    s = jnp.where(mask[:, None, None, :], s, _MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgs,bksd->bkgd", p, vf)
