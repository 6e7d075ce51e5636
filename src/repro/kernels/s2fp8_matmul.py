"""Pallas TPU kernel: S2FP8 GEMM with in-tile dequantization, f32 accumulation,
transposed operand layouts, and a fused output-truncation epilogue.

This is the paper's "tensor processing engine which requires the alpha and
beta factors while doing the calculations" (§5), adapted to the TPU memory
hierarchy: FP8 payload tiles stream HBM->VMEM at 1 byte/element (the
bandwidth win), the inverse shift/squeeze map runs on the VPU per tile, and
the dequantized f32 tiles feed the MXU with f32 accumulation (the paper's
FP32-accumulate requirement, native on TPU).

Three additions make the kernel the *training* GEMM (core/qdot.py):

  * ``layout`` in {"nn", "nt", "tn"} — the backward GEMMs dA = g·Bᵀ and
    dB = Aᵀ·g consume the forward's saved payloads transposed.  A layout is
    purely a BlockSpec index-map swap plus matching dot_general dimension
    numbers inside the tile; no payload transpose ever touches HBM.
  * ``out_alpha/out_beta`` — a fused Eq. 5 epilogue: on the last K step the
    accumulated f32 output tile is truncated in VMEM with the output site's
    (alpha, beta) (forward map -> clamp at format max -> FP8 RNE -> inverse
    map, shared ``_truncate_body``), so Fig. 4's separate output-truncation
    pass disappears.  The clamp turns stale-bank-stats overflow into
    saturation, never inf.
  * a (M, K, N, platform)-keyed block heuristic (``pick_gemm_block``) with a
    ``REPRO_GEMM_BLOCK=bm,bk,bn`` env override, replacing the fixed
    (256, 256, 256) tiles — see kernels/README.md for the sweep.

Grid is (M/bm, N/bn, K/bk) with K innermost; the output tile lives in VMEM
across the K loop (constant index_map) and acts as the accumulator.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import auto_interpret
from repro.kernels.ref import GEMM_CONTRACT, GEMM_LAYOUTS, gemm_dims
from repro.kernels.s2fp8_quant import _truncate_body
from repro.obs import spans

# Dequantized tiles carry full 24-bit mantissas (the Eq. 4 inverse map is
# not linear in the payload), and the MXU's default f32 contraction rounds
# its operands to bf16: measured on a v5e, that put the payload GEMM up to
# 4x outside the f32 accumulation bound of kernels/ref.py.  HIGHEST keeps
# every product exact to f32, as the paper's FP32-accumulate GEMM (§5) and
# the oracles assume.  Every dot of the payload kernels uses it.
MXU_PRECISION = jax.lax.Precision.HIGHEST


def _dequant(y, alpha, beta):
    y = y.astype(jnp.float32)
    absy = jnp.abs(y)
    nz = absy > 0.0
    xlog = (jnp.log2(jnp.where(nz, absy, 1.0)) - beta) / alpha
    return jnp.where(nz, jnp.sign(y) * jnp.exp2(xlog), 0.0)


def _matmul_kernel(aa_ref, ab_ref, ba_ref, bb_ref, oa_ref, ob_ref,
                   a_ref, b_ref, o_ref, *, layout, epilogue, fmt):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = _dequant(a_ref[...], aa_ref[0, 0], ab_ref[0, 0])
    b = _dequant(b_ref[...], ba_ref[0, 0], bb_ref[0, 0])
    o_ref[...] += jax.lax.dot_general(a, b, GEMM_CONTRACT[layout],
                                      precision=MXU_PRECISION,
                                      preferred_element_type=jnp.float32)
    if epilogue:
        @pl.when(k == pl.num_programs(2) - 1)
        def _epilogue():
            # Eq. 5 on the finished accumulator tile, in VMEM: the output
            # never crosses HBM untruncated.
            o_ref[...] = _truncate_body(o_ref[...], oa_ref[0, 0],
                                        ob_ref[0, 0], fmt)


def _operand_specs(layout, bm, bk, bn):
    """BlockSpecs realizing the layout as pure index-map swaps."""
    if layout == "nn":
        a_spec = pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk))
        b_spec = pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))
    elif layout == "nt":
        a_spec = pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk))
        b_spec = pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk))
    else:  # tn
        a_spec = pl.BlockSpec((bk, bm), lambda i, j, kk: (kk, i))
        b_spec = pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))
    return a_spec, b_spec


# ---------------------------------------------------------------------------
# batched variant: third (leading) data axis, broadcast/reduce via index maps
# ---------------------------------------------------------------------------

def _batched_matmul_kernel(aa_ref, ab_ref, ba_ref, bb_ref, oa_ref, ob_ref,
                           a_ref, b_ref, o_ref, *, layout, epilogue, fmt):
    gr = pl.program_id(3)
    k = pl.program_id(4)

    @pl.when(jnp.logical_and(gr == 0, k == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = _dequant(a_ref[...][0], aa_ref[0, 0], ab_ref[0, 0])
    b = _dequant(b_ref[...][0], ba_ref[0, 0], bb_ref[0, 0])
    o_ref[...] += jax.lax.dot_general(a, b, GEMM_CONTRACT[layout],
                                      precision=MXU_PRECISION,
                                      preferred_element_type=jnp.float32
                                      )[None]
    if epilogue:
        @pl.when(jnp.logical_and(gr == pl.num_programs(3) - 1,
                                 k == pl.num_programs(4) - 1))
        def _epilogue():
            o_ref[...] = _truncate_body(o_ref[...], oa_ref[0, 0],
                                        ob_ref[0, 0], fmt)


def _batched_operand_specs(layout, bm, bk, bn, go, ga, gb):
    """Batched BlockSpecs: the per-slice index maps of ``_operand_specs``
    plus a leading batch coordinate.  Grid axes are (g_out, i, j, g_red,
    kk); the combined batch step is ``g = g_red * go + g_out`` and each
    operand contributes its slice ``g % Gx`` (``Gx < G``: the
    trailing-aligned broadcast; block batch size is 1, so block index ==
    slice index)."""
    def amap(two_d):
        return lambda g, i, j, gr, kk: ((gr * go + g) % ga,) + two_d(i, kk)

    def bmap(two_d):
        return lambda g, i, j, gr, kk: ((gr * go + g) % gb,) + two_d(kk, j)

    if layout == "nn":
        a_spec = pl.BlockSpec((1, bm, bk), amap(lambda i, kk: (i, kk)))
        b_spec = pl.BlockSpec((1, bk, bn), bmap(lambda kk, j: (kk, j)))
    elif layout == "nt":
        a_spec = pl.BlockSpec((1, bm, bk), amap(lambda i, kk: (i, kk)))
        b_spec = pl.BlockSpec((1, bn, bk), bmap(lambda kk, j: (j, kk)))
    else:  # tn
        a_spec = pl.BlockSpec((1, bk, bm), amap(lambda i, kk: (kk, i)))
        b_spec = pl.BlockSpec((1, bk, bn), bmap(lambda kk, j: (kk, j)))
    return a_spec, b_spec


# ---------------------------------------------------------------------------
# block-size heuristic
# ---------------------------------------------------------------------------

# (platform, size-class) -> (bm, bk, bn).  Chosen by the sweep recorded in
# kernels/README.md ("GEMM block heuristic"); VMEM budget per entry =
# fp8 operand tiles (bm*bk + bk*bn bytes) + their f32 dequant images (x4)
# + the f32 accumulator (bm*bn*4), double-buffered on the operand side.
#   tpu/small : K often fits one step; modest tiles keep the grid >= core
#               count for pipelining.
#   tpu/large : widen K to 512 (1-byte payload tiles make deep-K cheap:
#               512*256 fp8 = 128 KiB/operand tile) to cut accumulator
#               revisits; ~3.5 MiB resident, safe with double buffering.
#   interpret : grid iterations are Python-speed, so prefer the fewest,
#               fattest tiles that divide the padded problem.
_BLOCK_TABLE = {
    ("tpu", "s"): (128, 256, 128),
    ("tpu", "m"): (256, 256, 256),
    ("tpu", "l"): (256, 512, 256),
    ("interpret", "s"): (256, 256, 256),
    ("interpret", "m"): (256, 512, 256),
    ("interpret", "l"): (512, 512, 512),
}


def pick_gemm_block(m: int, k: int, n: int, platform: str | None = None):
    """(bm, bk, bn) for a logical (M, K, N) GEMM on ``platform``.

    ``REPRO_GEMM_BLOCK=bm,bk,bn`` overrides the table globally (perf
    triage / sweeps without a code edit)."""
    env = os.environ.get("REPRO_GEMM_BLOCK")
    if env:
        try:
            bm, bk, bn = (int(v) for v in env.split(","))
        except ValueError:
            raise ValueError(
                f"REPRO_GEMM_BLOCK must be 'bm,bk,bn' ints, got {env!r}")
        return bm, bk, bn
    if platform is None:
        platform = "tpu" if jax.default_backend() == "tpu" else "interpret"
    size = max(m, k, n)
    cls = "s" if size <= 512 else ("m" if size <= 2048 else "l")
    return _BLOCK_TABLE[(platform, cls)]


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("layout", "fmt", "bm", "bk",
                                             "bn", "interpret"))
def s2fp8_matmul_pallas(a_payload, a_alpha, a_beta, b_payload, b_alpha, b_beta,
                        out_alpha=None, out_beta=None, *, layout: str = "nn",
                        fmt: str = "e5m2", bm=256, bk=256, bn=256,
                        interpret: bool | None = None):
    """C[M,N] = dequant(A) x dequant(B) under ``layout``; payloads are FP8.

    ``out_alpha/out_beta`` enable the fused Eq. 5 output-truncation
    epilogue (stats of the OUTPUT site; ``fmt`` is the epilogue's payload
    format).  ``interpret=None`` auto-detects (compiled on TPU, interpreter
    off-TPU).  Shapes must be block-divisible; ragged shapes are
    zero-padded one layer up in ``repro.kernels.dispatch.qmatmul_nd``.
    """
    interpret = auto_interpret() if interpret is None else interpret
    m, k, n = gemm_dims(layout, a_payload.shape, b_payload.shape)
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n, bm, bk, bn)
    grid = (m // bm, n // bn, k // bk)
    epilogue = out_alpha is not None
    oa = out_alpha if epilogue else 1.0
    ob = out_beta if epilogue else 0.0
    scalar = pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0))
    a_spec, b_spec = _operand_specs(layout, bm, bk, bn)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, layout=layout, epilogue=epilogue,
                          fmt=fmt),
        name=spans.GEMM,
        grid=grid,
        in_specs=[scalar] * 6 + [a_spec, b_spec],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(a_alpha, jnp.float32).reshape(1, 1),
      jnp.asarray(a_beta, jnp.float32).reshape(1, 1),
      jnp.asarray(b_alpha, jnp.float32).reshape(1, 1),
      jnp.asarray(b_beta, jnp.float32).reshape(1, 1),
      jnp.asarray(oa, jnp.float32).reshape(1, 1),
      jnp.asarray(ob, jnp.float32).reshape(1, 1),
      a_payload, b_payload)


@functools.partial(jax.jit, static_argnames=("layout", "out_batch", "fmt",
                                             "bm", "bk", "bn", "interpret"))
def s2fp8_matmul_batched_pallas(a_payload, a_alpha, a_beta,
                                b_payload, b_alpha, b_beta,
                                out_alpha=None, out_beta=None, *,
                                layout: str = "nn", out_batch=None,
                                fmt: str = "e5m2", bm=256, bk=256, bn=256,
                                interpret: bool | None = None):
    """Batched payload GEMM: ``C[Go,M,N]`` from ``A[Ga,.,.] x B[Gb,.,.]``.

    The combined batch is ``G = max(Ga, Gb)``; an operand's slice for
    combined step ``g`` is ``g % Gx`` (trailing-aligned broadcast — the
    ``becd,edf`` weight reuse), and ``out_batch < G`` accumulates the
    ``G // out_batch`` broadcast groups into one output slice (the
    broadcast operand's gradient).  Grid is (g_out, M/bm, N/bn, g_red,
    K/bk) with the two reduction axes innermost, so each output tile
    stays resident in VMEM across its whole reduction (revisit
    accumulation) and the Eq. 5 epilogue still runs on the finished tile
    before it ever crosses HBM.  Per-slice layout/epilogue semantics
    match :func:`s2fp8_matmul_pallas`; trailing dims must be
    block-divisible (padded one layer up in ``dispatch``)."""
    interpret = auto_interpret() if interpret is None else interpret
    ga, gb = a_payload.shape[0], b_payload.shape[0]
    g = max(ga, gb)
    assert g % ga == 0 and g % gb == 0, (ga, gb)
    go = g if out_batch is None else out_batch
    assert g % go == 0, (g, go)
    m, k, n = gemm_dims(layout, a_payload.shape[1:], b_payload.shape[1:])
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n, bm, bk, bn)
    grid = (go, m // bm, n // bn, g // go, k // bk)
    epilogue = out_alpha is not None
    oa = out_alpha if epilogue else 1.0
    ob = out_beta if epilogue else 0.0
    scalar = pl.BlockSpec((1, 1), lambda gi, i, j, gr, kk: (0, 0))
    a_spec, b_spec = _batched_operand_specs(layout, bm, bk, bn, go, ga, gb)
    return pl.pallas_call(
        functools.partial(_batched_matmul_kernel, layout=layout,
                          epilogue=epilogue, fmt=fmt),
        name=spans.GEMM_BATCHED,
        grid=grid,
        in_specs=[scalar] * 6 + [a_spec, b_spec],
        out_specs=pl.BlockSpec((1, bm, bn), lambda gi, i, j, gr, kk: (gi, i, j)),
        out_shape=jax.ShapeDtypeStruct((go, m, n), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(a_alpha, jnp.float32).reshape(1, 1),
      jnp.asarray(a_beta, jnp.float32).reshape(1, 1),
      jnp.asarray(b_alpha, jnp.float32).reshape(1, 1),
      jnp.asarray(b_beta, jnp.float32).reshape(1, 1),
      jnp.asarray(oa, jnp.float32).reshape(1, 1),
      jnp.asarray(ob, jnp.float32).reshape(1, 1),
      a_payload, b_payload)
