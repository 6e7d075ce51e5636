"""Decoder-only LM over heterogeneous block patterns, scan-over-layers.

The layer pattern (configs) is grouped into maximal homogeneous *segments*;
each segment's params are stacked on a leading layer axis and executed with
``lax.scan`` (+ optional ``jax.checkpoint`` remat).  HLO size is O(#segments),
not O(#layers) — a 96-layer dense model compiles as one scanned block, which
is what keeps the 512-device dry-runs tractable and matches production remat.

Three entry points:
  forward_train(params, tokens, labels)      -> (loss, aux-dict)
  prefill(params, tokens, caches)            -> (last-token logits, caches)
  decode_step(params, token, caches, index)  -> (logits, caches)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import statsbank
from repro.core.policy import Policy
from repro.models import blocks
from repro.obs import spans
from repro.parallel.sharding import shard


def segments_of(cfg: ArchConfig) -> List[Tuple[str, int]]:
    """Group the layer pattern into maximal (block_type, run_length) runs."""
    runs: List[Tuple[str, int]] = []
    for t in cfg.resolved_pattern:
        if runs and runs[-1][0] == t:
            runs[-1] = (t, runs[-1][1] + 1)
        else:
            runs.append((t, 1))
    return runs


def _stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def init_lm(cfg: ArchConfig, key) -> Dict[str, Any]:
    keys = jax.random.split(key, len(cfg.resolved_pattern) + 3)
    params: Dict[str, Any] = {
        "embed": jax.random.normal(keys[0], (cfg.vocab, cfg.d_model),
                                   jnp.float32) * 0.02,
        "final_norm": blocks.init_norm(cfg, cfg.d_model),
        "segments": [],
    }
    if not cfg.tie_embeddings:
        params["head"] = jax.random.normal(
            keys[1], (cfg.d_model, cfg.vocab), jnp.float32) / (cfg.d_model ** 0.5)
    li = 0
    for btype, length in segments_of(cfg):
        layer_ps = [blocks.init_block(btype, cfg, keys[3 + li + i])
                    for i in range(length)]
        params["segments"].append(_stack(layer_ps))
        li += length
    return params


def embed_tokens(params, tokens, cfg: ArchConfig, pol: Policy):
    table = params["embed"]
    if pol.mode in ("s2fp8", "s2fp8_e4m3", "fp8", "fp8_ls"):
        with statsbank.scope("embed"):
            table = pol.truncate(table)
    x = jnp.take(table, tokens, axis=0)
    return shard(x.astype(cfg.activation_dtype), "batch", None, None)


def lm_head(params, x, cfg: ArchConfig, pol: Policy):
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    with statsbank.scope("head"), jax.named_scope(spans.LM_HEAD):
        logits = pol.dot(x, w.astype(x.dtype))
    return shard(logits, "batch", None, "vocab")


def _segment_scan(btype, seg_params, x, cfg, pol, positions, caches,
                  cache_index, mode, seg_name: str = "seg",
                  cache_fmt: Optional[str] = None):
    """Scan one homogeneous segment.  caches: stacked per-layer pytree or None.

    When a StatsBank session is active (jitted train step with delayed
    stats), the segment's per-layer site states ride through the scan
    ``xs`` alongside the stacked layer params, so every layer truncates
    with its own carried (alpha, beta); their refreshed values flow back
    out through the scan transpose as the bank argument's cotangent.
    """
    n_layers = jax.tree_util.tree_leaves(seg_params)[0].shape[0]
    sites = statsbank.segment_sites(seg_name, n_layers)

    def body(carry, xs):
        x, aux_sum = carry
        if caches is None:
            layer_p, layer_sites = xs
            with statsbank.segment_ctx(seg_name, layer_sites):
                y, _, aux = blocks.block_apply(btype, layer_p, x, cfg, pol,
                                               positions, None, cache_index,
                                               mode, cache_fmt)
            return (y, aux_sum + aux), None
        layer_p, layer_sites, layer_c = xs
        with statsbank.segment_ctx(seg_name, layer_sites):
            y, c_new, aux = blocks.block_apply(btype, layer_p, x, cfg, pol,
                                               positions, layer_c,
                                               cache_index, mode, cache_fmt)
        return (y, aux_sum + aux), c_new

    if cfg.remat and mode == "train":
        body = jax.checkpoint(body, prevent_cse=False)

    xs = (seg_params, sites) if caches is None else (seg_params, sites, caches)
    (x, aux), new_caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
    return x, aux, new_caches


def forward(params, tokens, cfg: ArchConfig, pol: Policy, *,
            caches=None, cache_index=0, mode: str = "train",
            cache_fmt: Optional[str] = None):
    """Shared forward.  Returns (hidden, total_aux, new_caches).

    ``cache_index`` may be a traced scalar (single shared position) or a
    [B] vector of per-slot positions (serving); ``cache_fmt`` is the static
    paged-cache storage format (see serving/paged_cache.py), threaded down
    to the block cache read/write paths.
    """
    x = embed_tokens(params, tokens, cfg, pol)
    s = tokens.shape[1]
    if mode == "decode":
        ci = jnp.asarray(cache_index, jnp.int32)
        if ci.ndim == 1:
            positions = jnp.broadcast_to(ci[:, None], (ci.shape[0], s))
        else:
            positions = jnp.full((s,), ci, jnp.int32)
    else:
        positions = jnp.arange(s, dtype=jnp.int32)

    total_aux = jnp.zeros((), jnp.float32)
    new_caches = []
    for i, (btype, _) in enumerate(segments_of(cfg)):
        seg_c = None if caches is None else caches[i]
        x, aux, seg_c_new = _segment_scan(
            btype, params["segments"][i], x, cfg, pol, positions,
            seg_c, cache_index, mode, seg_name=f"seg{i}:{btype}",
            cache_fmt=cache_fmt)
        total_aux = total_aux + aux
        new_caches.append(seg_c_new)
    x = blocks.apply_norm(params["final_norm"], x, cfg)
    return x, total_aux, (new_caches if caches is not None else None)


def loss_fn(params, tokens, labels, cfg: ArchConfig, pol: Policy):
    """Next-token cross entropy (labels = tokens shifted by the data layer)."""
    x, aux, _ = forward(params, tokens, cfg, pol, mode="train")
    logits = lm_head(params, x, cfg, pol).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = jnp.mean(logz - gold)
    # z-loss stabilizer (production default; tiny, keeps logz bounded)
    zloss = 1e-4 * jnp.mean(logz ** 2)
    return nll + zloss + aux, {"nll": nll, "aux": aux}


def init_caches(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    caches = []
    for btype, length in segments_of(cfg):
        one = blocks.init_cache(btype, cfg, batch, max_len, dtype)
        caches.append(jax.tree_util.tree_map(
            lambda leaf: jnp.zeros((length,) + leaf.shape, leaf.dtype), one))
    return caches


def prefill(params, tokens, cfg: ArchConfig, pol: Policy, caches, *,
            last_index=None):
    """Process a full prompt, fill caches, return last-position logits.

    ``last_index``: optional [B] int32 of each row's true last-token index
    (right-padded batched admission); default reads position -1.
    """
    x, _, new_caches = forward(params, tokens, cfg, pol,
                               caches=caches, mode="prefill")
    if last_index is None:
        x_last = x[:, -1:]
    else:
        x_last = x[jnp.arange(x.shape[0]), last_index][:, None]
    logits = lm_head(params, x_last, cfg, pol)
    return logits, new_caches


def decode_step(params, token, cfg: ArchConfig, pol: Policy, caches,
                cache_index, *, cache_fmt: Optional[str] = None):
    """One decode step.  token: [B, 1] int32; cache_index: traced scalar or
    per-slot [B] position vector (serving)."""
    x, _, new_caches = forward(params, token, cfg, pol, caches=caches,
                               cache_index=cache_index, mode="decode",
                               cache_fmt=cache_fmt)
    logits = lm_head(params, x, cfg, pol)
    return logits, new_caches
