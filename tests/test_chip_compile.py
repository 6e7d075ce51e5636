"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers a kernel at gemma3-1b widths (d_model 1152,
d_ff 6912, head_dim 256, 4 query heads over 1 KV head, window 512) for a
``v5e:2x2`` topology that is described, not attached, and asserts that the
TPU compiler accepted it and emitted the Mosaic kernel (``tpu_custom_call``).
This catches what interpret mode cannot: block shapes that break the
(8, 128) tiling rule, scalar stores to VMEM, VMEM over-use.  One test
compiles a whole tiny S2FP8 train step and reads the names its kernels get
in the compiled program, which are the names a TPU trace shows.

The topology is described inside a module fixture, never at import, so that
every pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU compiler.
"""
from __future__ import annotations

import collections
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dispatch
from repro.kernels import flash_attention as fa
from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.s2fp8_matmul import pick_gemm_block

D_MODEL, D_FF, HD, KVH, G, WINDOW = 1152, 6912, 256, 1, 4, 512
TOKENS = 2048                 # batch 2 x seq 1024
F8 = jnp.float8_e5m2


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)`` -> a ShapeDtypeStruct placed on chip 0."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(text):
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("layout,a_shape,b_shape", [
    ("nn", (TOKENS, D_MODEL), (D_MODEL, D_FF)),      # x @ W
    ("nt", (TOKENS, D_FF), (D_MODEL, D_FF)),         # dx = g @ W^T
    ("tn", (TOKENS, D_MODEL), (TOKENS, D_FF)),       # dW = x^T @ g
])
def test_qmatmul_compiles(spec, layout, a_shape, b_shape):
    from repro.kernels.ref import gemm_dims
    m, k, n = gemm_dims(layout, a_shape, b_shape)
    bm, bk, bn = pick_gemm_block(m, k, n, platform="tpu")
    s = spec(())
    fn = functools.partial(dispatch.qmatmul_nd, layout=layout, bm=bm, bk=bk,
                           bn=bn, interpret=False)
    text = _compile_text(
        lambda a, aa, ab, b, ba, bb, oa, ob: fn(
            a, aa, ab, b, ba, bb, epilogue_stats=(oa, ob)),
        spec(a_shape, F8), s, s, spec(b_shape, F8), s, s, s, s)
    _assert_kernel(text)


@pytest.mark.parametrize("layout", ["nn", "nt", "tn"])
def test_qmatmul_batched_compiles(spec, layout):
    from repro.kernels.ref import gemm_dims
    a_shape = {"nn": (8, 1024, 256), "nt": (8, 1024, 256),
               "tn": (8, 256, 1024)}[layout]
    b_shape = {"nn": (8, 256, 1024), "nt": (8, 1024, 256),
               "tn": (8, 256, 1024)}[layout]
    m, k, n = gemm_dims(layout, a_shape[1:], b_shape[1:])
    bm, bk, bn = pick_gemm_block(m, k, n, platform="tpu")
    s = spec(())
    fn = functools.partial(dispatch.qmatmul_batched_nd, layout=layout,
                           bm=bm, bk=bk, bn=bn, interpret=False)
    text = _compile_text(fn, spec(a_shape, F8), s, s, spec(b_shape, F8), s, s)
    _assert_kernel(text)


@pytest.mark.parametrize("op", ["truncate", "truncate_fused", "quant",
                                "quant_stats", "dequant"])
def test_quant_kernels_compile(spec, op):
    x = spec((TOKENS, D_MODEL))
    s = spec(())
    if op == "truncate":
        text = _compile_text(lambda x, a, b: dispatch.truncate_nd(
            x, stats=(a, b), interpret=False), x, s, s)
    elif op == "truncate_fused":
        text = _compile_text(lambda x: dispatch.truncate_nd(
            x, fused_stats=True, interpret=False), x)
    elif op == "quant":
        text = _compile_text(lambda x, a, b: dispatch.quant_nd(
            x, stats=(a, b), interpret=False)[0], x, s, s)
    elif op == "quant_stats":
        text = _compile_text(lambda x: dispatch.quant_nd(
            x, interpret=False), x)
    else:
        text = _compile_text(lambda p, a, b: dispatch.dequant_nd(
            p, a, b, interpret=False), spec((TOKENS, D_MODEL), F8), s, s)
    _assert_kernel(text)


def _qflash_args(spec, hd, seq=1024, batch=2):
    bh, bkv = batch * KVH * G, batch * KVH
    s = spec(())
    return (spec((bh, seq, hd), F8), spec((bkv, seq, hd), F8),
            spec((bkv, seq, hd), F8), s, bh, seq)


@pytest.mark.parametrize("hd", [HD, 64])
@pytest.mark.parametrize("window", [None, WINDOW])
def test_qflash_fwd_compiles(spec, hd, window):
    q, k, v, s, bh, seq = _qflash_args(spec, hd)

    def fwd(q, k, v, a, b):
        return fa.qflash_fwd_pallas(q, k, v, (a, b), (a, b), (a, b), g=G,
                                    causal=True, window=window,
                                    out_stats=(a, b), interpret=False)
    _assert_kernel(_compile_text(fwd, q, k, v, s, s))


@pytest.mark.parametrize("hd", [HD, 64])
@pytest.mark.parametrize("window", [None, WINDOW])
def test_qflash_bwd_compiles(spec, hd, window):
    q, k, v, s, bh, seq = _qflash_args(spec, hd)
    row = spec((bh, seq, 1))

    def bwd(q, k, v, g, a, b, lse, delta):
        return fa.qflash_bwd_pallas(q, k, v, g, (a, b), (a, b), (a, b),
                                    (a, b), lse, delta, g=G, causal=True,
                                    window=window, interpret=False)
    _assert_kernel(_compile_text(bwd, q, k, v, q, s, s, row, row))


def test_paged_decode_compiles(spec):
    # PayloadLMServer defaults: 8 slots, max_len 256, block 16
    slots, max_len, block = 8, 256, 16
    max_blocks = max_len // block
    n_blocks = slots * max_blocks + 1
    s = spec(())
    text = _compile_text(
        functools.partial(paged_decode_attention, interpret=False),
        spec((slots, KVH, G, HD)), spec((n_blocks, KVH, block, HD), F8),
        spec((n_blocks, KVH, block, HD), F8), s, s, s, s,
        spec((slots, max_blocks), jnp.int32), spec((slots,), jnp.int32))
    _assert_kernel(text)


def test_train_step_kernels_carry_registry_names(spec, monkeypatch):
    """A whole S2FP8 train step, its layers under ``jax.checkpoint`` and
    its StatsBank refreshes under ``lax.cond``, compiled for the chip:
    every Mosaic call's HLO instruction name is a name of
    ``repro.obs.spans``, not that of what wraps the call (``checkpoint``,
    ``branch_0_fun``, ``jvp_jit_...``)."""
    from repro.configs import get_reduced_config
    from repro.core import statsbank
    from repro.core.policy import make_policy
    from repro.kernels import s2fp8_matmul, s2fp8_quant
    from repro.models import transformer as tlm
    from repro.obs import spans
    from repro.optim import optimizers, schedules
    from repro.training.trainer import make_train_step

    # default_backend() says CPU here: compile the kernels with the chip's
    # blocks instead of interpreting them, and keep the traces made so out
    # of the jit caches that later tests of this process use
    for mod in (s2fp8_quant, s2fp8_matmul, fa):
        monkeypatch.setattr(mod, "auto_interpret", lambda: False)
    monkeypatch.setattr(dispatch, "pick_gemm_block",
                        functools.partial(pick_gemm_block, platform="tpu"))
    jax.clear_caches()
    try:
        cfg = get_reduced_config("minicpm_2b").replace(
            n_layers=2, remat=True, vocab=256)
        pol = make_policy("s2fp8", backend="pallas", gemm_mode="payload")
        assert pol.uses_payload_gemm

        def loss_fn(p, batch, pol_):
            return tlm.loss_fn(p, batch["tokens"], batch["labels"], cfg,
                               pol_)

        def on_chip(tree):
            return jax.tree_util.tree_map(
                lambda x: spec(x.shape, x.dtype), tree)

        opt = optimizers.adamw()
        stats = statsbank.StatsConfig(refresh_every=4)
        params = jax.eval_shape(lambda k: tlm.init_lm(cfg, k),
                                jax.random.PRNGKey(0))
        batch = {k: spec((2, 128), jnp.int32) for k in ("tokens", "labels")}
        bank = statsbank.init_bank(loss_fn, params, batch, pol, stats)
        step = make_train_step(loss_fn, opt, schedules.constant(1e-3), pol,
                               stats=stats)
        text = _compile_text(step, on_chip(params),
                             on_chip(jax.eval_shape(opt.init, params)),
                             on_chip(bank), batch, spec((), jnp.int32))
    finally:
        jax.clear_caches()
    names = collections.Counter(
        line.strip().split(" = ", 1)[0].lstrip("%").rsplit(".", 1)[0]
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line)
    assert set(names) <= set(spans.KERNELS), names
    assert {spans.GEMM, spans.QUANT_APPLY, spans.TRUNCATE_APPLY,
            spans.QFLASH_FWD, spans.QFLASH_BWD_DQ,
            spans.QFLASH_BWD_DKDV} <= set(names), names
