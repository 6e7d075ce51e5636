"""Serving launcher: batched LM serving with the slot engines.

Dense engine (any block pattern):

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3_1b --reduced \
        --requests 8 --policy s2fp8

Payload engine (paged S2FP8 KV cache, frozen export-time stats; global
attention patterns only):

    PYTHONPATH=src python -m repro.launch.serve --arch minicpm_2b --reduced \
        --engine payload --cache-fmt e5m2 --requests 16 --slots 8
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config, get_reduced_config
from repro.core.policy import make_policy
from repro.launch import api
from repro.launch.compile_cache import enable_compile_cache
from repro.obs.sinks import make_sink
from repro.serving import bank as sbank
from repro.serving import paged_cache
from repro.serving.engine import LMServer, PayloadLMServer, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--policy", default="s2fp8")
    ap.add_argument("--engine", choices=("dense", "payload"), default="dense")
    ap.add_argument("--cache-fmt", default="e5m2",
                    choices=paged_cache.CACHE_FMTS)
    ap.add_argument("--block", type=int, default=16,
                    help="paged cache block size (payload engine)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--export-passes", type=int, default=2,
                    help="stats-bank probe passes at export (payload engine)")
    ap.add_argument("--metrics", default=None,
                    help="per-tick metrics sink spec (obs.sinks.make_sink)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if cfg.enc_dec:
        raise SystemExit("serve launcher covers decoder LMs; whisper uses "
                         "encdec.serve_prefill/serve_decode (see examples)")
    pol = make_policy(args.policy)
    key = jax.random.PRNGKey(args.seed)
    params = api.init_params(cfg, key)

    if args.engine == "payload":
        print(f"[serve] exporting frozen serving bank "
              f"({args.export_passes} probe passes)...")
        bank = sbank.export_serving_bank(
            params, cfg, pol, prompt_len=min(args.prompt_len, 32),
            passes=args.export_passes, seed=args.seed)
        sink = make_sink(args.metrics) if args.metrics else None
        server = PayloadLMServer(
            cfg, params, pol, bank=bank, slots=args.slots,
            max_len=args.max_len, block=args.block,
            cache_fmt=args.cache_fmt, sink=sink)
        pool_b, stats_b = server.cache_bytes()
        print(f"[serve] paged cache: {pool_b/1e6:.2f} MB pool + "
              f"{stats_b} B frozen stats ({args.cache_fmt}, "
              f"block={args.block}, {server.n_blocks} blocks)")
    else:
        server = LMServer(cfg, params, pol, slots=args.slots,
                          max_len=args.max_len)

    rng = np.random.default_rng(args.seed)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, args.prompt_len,
                                        dtype=np.int32),
                    max_new_tokens=args.new_tokens)
            for _ in range(args.requests)]
    for r in reqs:
        server.submit(r)
    t0 = time.perf_counter()
    ticks = server.run_to_completion()
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.out) for r in reqs)
    print(f"[serve] {args.requests} requests, {total_tokens} tokens, "
          f"{ticks} ticks, {dt:.2f}s ({total_tokens/dt:.1f} tok/s), "
          f"{len(server.prefill_shapes)} compiled prefill shapes")
    if args.engine == "payload":
        print(f"[serve] preemptions: {server.preemptions}")
    for i, r in enumerate(reqs[:3]):
        print(f"  req{i}: {r.out[:8]}...")


if __name__ == "__main__":
    main()
