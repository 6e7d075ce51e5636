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
  * a tile plan from the GEMM's shape (``pick_gemm_block``): on the TPU
    the widest output tiles that divide the aligned dims within a VMEM
    budget (``gemm_vmem_bytes``), so dequantization amortizes over wide
    tiles and no MAC is padding; a ``REPRO_GEMM_BLOCK=bm,bk,bn`` env
    override — see kernels/README.md for the chip numbers.

Grid is (M/bm, N/bn, K/bk) with K innermost; the output tile lives in VMEM
across the K loop (constant index_map) and acts as the accumulator.  Inside
a grid step each operand tile is dequantized once into f32 VMEM scratch, and
the output tile accumulates one (_DOT_M, _DOT_N) block at a time, in loops,
so the kernel's code does not grow with its tiles.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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
# Mosaic unrolls a kernel's body over its whole tile, and a TPU program
# keeps its code in HBM: a (1024, 384, 1920) body dequantizing and
# multiplying whole tiles compiled to 4.5 MB of code, against 0.29 MB for
# (256, 512, 256).  So the body works through the tile in loops of fixed
# size.  The elementwise maps (dequant, Eq. 5) take a chunk of at most
# _CHUNK_ROWS x _CHUNK_LANES at a time, a few vector registers, so that
# their chains of temporaries stay in registers; _CHUNK_UNROLL chunks a
# loop iteration give the scheduler independent chains to interleave.
# Lowering a kernel costs time in proportion to the vector registers one
# iteration touches: four chunks an iteration ran 2% faster than two on a
# v5e, and lowered a MiniCPM-2B train step in 6 s more
_CHUNK_ROWS, _CHUNK_LANES, _CHUNK_UNROLL = 32, 512, 2
# and the most rows and columns of the output tile that one dot computes
_DOT_M, _DOT_N = 512, 512


def _dequant(y, alpha, beta):
    y = y.astype(jnp.float32)
    absy = jnp.abs(y)
    nz = absy > 0.0
    xlog = (jnp.log2(jnp.where(nz, absy, 1.0)) - beta) / alpha
    return jnp.where(nz, jnp.sign(y) * jnp.exp2(xlog), 0.0)


def _by_chunks(rows: int, cols: int, fn) -> None:
    """``fn(rows_slice, cols_slice)`` over a (rows, cols) tile, a chunk at
    a time, in a loop."""
    cr = _CHUNK_ROWS if rows % _CHUNK_ROWS == 0 else rows
    cc = _dot_block(cols, _CHUNK_LANES)
    nc = cols // cc
    n = rows // cr * nc
    unroll = next(u for u in range(_CHUNK_UNROLL, 0, -1) if n % u == 0)

    def body(i, carry):
        for u in range(unroll):
            t = i * unroll + u
            fn(pl.ds(pl.multiple_of(t // nc * cr, cr), cr),
               pl.ds(pl.multiple_of(t % nc * cc, cc), cc))
        return carry

    jax.lax.fori_loop(0, n // unroll, body, 0)


def _dequant_tile(dst, src, lead, alpha, beta):
    """Dequantize the payload tile ``src[lead]`` into f32 VMEM ``dst``, once
    per grid step: every dot of the step reads it from there."""
    def fill(rows, cols):
        dst[rows, cols] = _dequant(src[lead + (rows, cols)], alpha, beta)
    _by_chunks(*dst.shape, fill)


def _dot_block(dim: int, cap: int) -> int:
    """Rows or columns of the output tile one dot computes: the largest
    multiple of 128 up to ``cap`` that divides ``dim``, else all of it."""
    if dim <= cap:
        return dim
    return next((b for b in range(cap - cap % 128, 0, -128) if dim % b == 0),
                dim)


def _accumulate(o_ref, lead, a_deq, b_deq, layout):
    """``o_ref[lead] += A B`` from the dequantized tiles, a (_DOT_M, _DOT_N)
    block of the output at a time, each at ``MXU_PRECISION``."""
    bm, bn = o_ref.shape[-2:]
    tm, tn = _dot_block(bm, _DOT_M), _dot_block(bn, _DOT_N)
    nj = bn // tn

    def body(t, carry):
        rows = pl.ds(pl.multiple_of(t // nj * tm, tm), tm)
        cols = pl.ds(pl.multiple_of(t % nj * tn, tn), tn)
        a = a_deq[:, rows] if layout == "tn" else a_deq[rows, :]
        b = b_deq[cols, :] if layout == "nt" else b_deq[:, cols]
        o_ref[lead + (rows, cols)] += jax.lax.dot_general(
            a, b, GEMM_CONTRACT[layout], precision=MXU_PRECISION,
            preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, bm // tm * nj, body, 0)


def _truncate_tile(o_ref, lead, alpha, beta, fmt):
    """Eq. 5 on the finished accumulator tile ``o_ref[lead]``, in VMEM, a
    chunk at a time: the output never crosses HBM untruncated, and the
    map's f32 temporaries take one chunk's room, not the tile's.
    Compiled for a v5e at (768, 512, 1920), tn, a GEMM that dequantized
    and multiplied whole tiles asked for 21 MiB of VMEM without an
    epilogue and 49 MiB with the whole tile mapped at once."""
    def trunc(rows, cols):
        idx = lead + (rows, cols)
        o_ref[idx] = _truncate_body(o_ref[idx], alpha, beta, fmt)
    _by_chunks(*o_ref.shape[-2:], trunc)


def _matmul_kernel(aa_ref, ab_ref, ba_ref, bb_ref, oa_ref, ob_ref,
                   a_ref, b_ref, o_ref, a_deq, b_deq, *, layout, epilogue,
                   fmt):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    _dequant_tile(a_deq, a_ref, (), aa_ref[0, 0], ab_ref[0, 0])
    _dequant_tile(b_deq, b_ref, (), ba_ref[0, 0], bb_ref[0, 0])
    _accumulate(o_ref, (), a_deq, b_deq, layout)
    if epilogue:
        @pl.when(k == pl.num_programs(2) - 1)
        def _epilogue():
            _truncate_tile(o_ref, (), oa_ref[0, 0], ob_ref[0, 0], fmt)


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


def _dequant_scratch(a_spec, b_spec):
    """f32 VMEM for the dequantized operand tiles (a batched block's
    leading 1 dropped)."""
    return [pltpu.VMEM(spec.block_shape[-2:], jnp.float32)
            for spec in (a_spec, b_spec)]


# ---------------------------------------------------------------------------
# batched variant: third (leading) data axis, broadcast/reduce via index maps
# ---------------------------------------------------------------------------

def _batched_matmul_kernel(aa_ref, ab_ref, ba_ref, bb_ref, oa_ref, ob_ref,
                           a_ref, b_ref, o_ref, a_deq, b_deq, *, layout,
                           epilogue, fmt):
    gr = pl.program_id(3)
    k = pl.program_id(4)

    @pl.when(jnp.logical_and(gr == 0, k == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    _dequant_tile(a_deq, a_ref, (0,), aa_ref[0, 0], ab_ref[0, 0])
    _dequant_tile(b_deq, b_ref, (0,), ba_ref[0, 0], bb_ref[0, 0])
    _accumulate(o_ref, (0,), a_deq, b_deq, layout)
    if epilogue:
        @pl.when(jnp.logical_and(gr == pl.num_programs(3) - 1,
                                 k == pl.num_programs(4) - 1))
        def _epilogue():
            _truncate_tile(o_ref, (0,), oa_ref[0, 0], ob_ref[0, 0], fmt)


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
# tile plan
# ---------------------------------------------------------------------------

# Every (i, j, k) grid step dequantizes its A and B tiles, so A is
# dequantized N/bn times and B M/bm times: 1/bm + 1/bn elements of
# per-element log2/exp2 work per MAC, spent again for every output tile, on
# top of a fixed per-step overhead.  On the TPU the plan therefore takes the
# widest output tiles that divide the (8/128-aligned) GEMM within a VMEM
# budget, and a K tile that divides K, so no MAC is padding.
_TPU_MAX_BK = 512
_TPU_MAX_BM = 1024
_TPU_MAX_BN = 2048
# a dim with no 128-multiple divisor of at least this pads to a block
_TPU_MIN_DIVISOR = 256
_TPU_PAD_BLOCKS = (512, 384, 256)
_MIB = 1 << 20
# the most gemm_vmem_bytes a plan may take: on a v5e, tiles past it ran at
# 82-87% of the tiles below it (kernels/README.md)
_TPU_VMEM_BUDGET = 28 * _MIB
# the compiler's default scoped-VMEM limit on a v5e, and the most a GEMM
# may ask for (a v5e core has 128 MiB)
_VMEM_FLOOR = 16 * _MIB
_VMEM_CEIL = 48 * _MIB

# interpret mode: grid iterations are Python-speed, so the fewest, fattest
# tiles that divide the padded problem
_INTERPRET_BLOCKS = {"s": (256, 256, 256), "m": (256, 512, 256),
                     "l": (512, 512, 512)}


def gemm_vmem_bytes(bm: int, bk: int, bn: int) -> int:
    """VMEM one grid step of the payload GEMM holds, in bytes: the FP8
    operand tiles double-buffered, 2 (bm bk + bk bn); their f32 dequant
    images, 4 (bm bk + bk bn); the bf16 parts of the HIGHEST split, ~6
    (bm bk + bk bn), a bound now that the dots take blocks of the tiles;
    and the f32 output tile double-buffered, 8 bm bn."""
    return 12 * (bm * bk + bk * bn) + 8 * bm * bn


def gemm_vmem_limit(bm: int, bk: int, bn: int) -> int:
    """``vmem_limit_bytes`` for a (bm, bk, bn) grid: the formula with
    headroom for what it leaves out, never below the compiler's default
    nor above ``_VMEM_CEIL``."""
    need = gemm_vmem_bytes(bm, bk, bn)
    return min(_VMEM_CEIL, max(_VMEM_FLOOR, need + need // 2))


def _tpu_tiles(dim: int, cap: int) -> tuple:
    """Blocks that may tile ``dim``: the whole ``dim`` if it is at most
    ``cap``; else the multiples of 128 from ``_TPU_MIN_DIVISOR`` to
    ``cap`` that divide it; else the one block of ``_TPU_PAD_BLOCKS`` that
    pads ``dim`` least (the larger on a tie), so padding stays under 512."""
    if dim <= cap:
        return (dim,)
    fits = tuple(b for b in range(_TPU_MIN_DIVISOR, cap + 1, 128)
                 if dim % b == 0)
    return fits or (min(_TPU_PAD_BLOCKS, key=lambda b: -(-dim // b) * b),)


def _tpu_plan(m: int, k: int, n: int):
    """The largest K block, then the output tiles with the least dequant
    work per MAC, 1/bm + 1/bn (the larger tile on a tie), whose
    :func:`gemm_vmem_bytes` fits ``_TPU_VMEM_BUDGET``."""
    bk = max(_tpu_tiles(k, _TPU_MAX_BK))
    pairs = [(bm, bn) for bm in _tpu_tiles(m, _TPU_MAX_BM)
             for bn in _tpu_tiles(n, _TPU_MAX_BN)]
    fit = [p for p in pairs
           if gemm_vmem_bytes(p[0], bk, p[1]) <= _TPU_VMEM_BUDGET]
    bm, bn = min(fit or pairs,
                 key=lambda p: (1 / p[0] + 1 / p[1], -p[0] * p[1]))
    return bm, bk, bn


def pick_gemm_block(m: int, k: int, n: int, platform: str | None = None):
    """(bm, bk, bn) for a GEMM of aligned dims (M, K, N) on ``platform``.

    TPU: :func:`_tpu_plan`, with bk up to 512, bm up to 1024 and bn up to
    2048; a decode-size M keeps its whole 8-row tile.
    ``REPRO_GEMM_BLOCK=bm,bk,bn`` overrides both platforms (perf triage /
    sweeps without a code edit)."""
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
    if platform == "tpu":
        return _tpu_plan(m, k, n)
    size = max(m, k, n)
    return _INTERPRET_BLOCKS["s" if size <= 512 else
                             ("m" if size <= 2048 else "l")]


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
        scratch_shapes=_dequant_scratch(a_spec, b_spec),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=gemm_vmem_limit(bm, bk, bn)),
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
        scratch_shapes=_dequant_scratch(a_spec, b_spec),
        out_shape=jax.ShapeDtypeStruct((go, m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=gemm_vmem_limit(bm, bk, bn)),
        interpret=interpret,
    )(jnp.asarray(a_alpha, jnp.float32).reshape(1, 1),
      jnp.asarray(a_beta, jnp.float32).reshape(1, 1),
      jnp.asarray(b_alpha, jnp.float32).reshape(1, 1),
      jnp.asarray(b_beta, jnp.float32).reshape(1, 1),
      jnp.asarray(oa, jnp.float32).reshape(1, 1),
      jnp.asarray(ob, jnp.float32).reshape(1, 1),
      a_payload, b_payload)
