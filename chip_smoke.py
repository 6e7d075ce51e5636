#!/usr/bin/env python3
"""Smoke run of S2FP8 training on a TPU: one chip, or one four-chip host.

    python3 chip_smoke.py            # kernel-vs-oracle checks, then 8 training
                                     # steps of gemma3-1b on one chip
    python3 chip_smoke.py --chips 4  # only the data-parallel comparison: 4x1
                                     # mesh, s2fp8 grad sync + fsdp_q params vs
                                     # f32 sync + replicated params

The model is gemma3-1b at its published widths (d_model 1152, 4 query heads
over 1 KV head, head_dim 256, d_ff 6912, vocab 262144, tied embeddings,
window 512), cut to one 5:1 local:global period (6 layers), with random
weights and synthetic Markov batches from ``--seed``.  Training goes through
the launcher (``repro.launch.train.train``) under policy s2fp8 with the
numerics backend and GEMM mode left on ``auto``, which must resolve to the
compiled Pallas kernels and payload GEMMs, and a StatsBank that refreshes
every 4 steps.

Batch 2 x 1024 tokens: compiled for a described v5e, this step needs 12.95 GB
of the chip's 16 GB (5.56 GB of f32 params and AdamW moments, 7.40 GB of
temporaries, ``compiled.memory_analysis()``); batch 4 would not fit.

The script fails (non-zero exit, no result line) when JAX finds no TPU, when
a kernel disagrees with its oracle, when the compiled step holds no Pallas
kernel, or on a non-finite loss.  Its last stdout line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Everything runs in this one process, which holds the chip(s) throughout.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "gemma3_1b"
LAYERS = 6                   # one 5 x local + 1 x dense period
BATCH, SEQ = 2, 1024         # one chip
MESH_BATCH = 8               # four chips: 2 sequences per chip
STEPS = 8
REFRESH_EVERY = 4
# loss agreement of the compressed-sync / fsdp_q run with the f32 /
# replicated run: the bound of the 8-way CPU tests (tests/test_mesh_train.py)
LOSS_GAP = 0.15


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


FAILED = []


def check(ok, what: str) -> None:
    """Record a failed check; the run goes on so that one run reports every
    failure, and ends non-zero without a result line if any was recorded."""
    if not ok:
        FAILED.append(what)
        print(f"[smoke] FAILED: {what}", file=sys.stderr, flush=True)


def compile_clock():
    """(seconds XLA spent compiling or reading the persistent cache,
    persistent-cache hits) so far, from the program's recorder."""
    from repro.obs import spans
    rec = spans.RECORDER
    return rec.total(spans.COMPILE), rec.counters.get(spans.CACHE_HITS, 0)


def gemm_plan_counters():
    """The payload GEMMs' plan counters so far (``gemm/*`` of the
    program's recorder): MACs, padded MACs, dequantized and operand
    elements."""
    from repro.obs import spans
    c = spans.RECORDER.counters
    return [c.get(name, 0) for name in (
        spans.GEMM_MACS, spans.GEMM_PADDED_MACS, spans.GEMM_DEQUANT_ELEMS,
        spans.GEMM_OPERAND_ELEMS)]


def smoke_config():
    from repro.configs.base import get_config
    cfg = get_config(ARCH)
    return cfg.replace(n_layers=LAYERS, pattern=cfg.pattern[:LAYERS])


# ---------------------------------------------------------------------------
# phase 1: kernels against their oracles, at the model's widths
# ---------------------------------------------------------------------------

# On a v5e an engine's f32 forward image of Eq. 5 lies up to ~5e-4 off the
# float64 one, XLA and Mosaic alike: their log2 is ~160 ulps off, which
# alpha (~4.7) amplifies; ``eq5_map_errors`` prints these errors.  So an
# image that close to a midpoint may land on either neighbour.  2^-8
# leaves a 7x margin and stays 30x below the e5m2 grid's relative spacing
# (>= 2^-3): an engine that skips the FP8 rounding or rounds to the wrong
# grid point still fails.
EQ5_RTOL = 2.0 ** -8


def _mosaic(fn, x, *scalars):
    """``fn(x, *scalars)`` evaluated elementwise by a Pallas (Mosaic)
    kernel over row blocks of the 2-D ``x``, the scalars read from (1, 1)
    blocks as the quant kernels read (alpha, beta)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    bm = 256
    scalar = pl.BlockSpec((1, 1), lambda i: (0, 0))
    rows = pl.BlockSpec((bm, x.shape[1]), lambda i: (i, 0))

    def kernel(*refs):
        *s_refs, x_ref, o_ref = refs
        o_ref[...] = fn(x_ref[...], *(r[0, 0] for r in s_refs))

    return pl.pallas_call(
        kernel, grid=(x.shape[0] // bm,),
        in_specs=[scalar] * len(scalars) + [rows], out_specs=rows,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
    )(*(jnp.asarray(s, jnp.float32).reshape(1, 1) for s in scalars), x)


def eq5_map_errors(x, alpha, beta, key) -> None:
    """Eq. 5's f32 maps as XLA and Mosaic evaluate them on this device,
    against float64: log2 on ``|x|`` (error in ulps of the result), exp2
    over the forward map's exponent range (relative error), and the
    forward map ``sign(x) 2^(alpha log2|x| + beta)`` itself (relative
    error).  The forward error these imply is
    ``ln2 (alpha e_log2 + the f32 roundings of the product and the sum)
    + e_exp2``; the measured one must stay inside ``EQ5_RTOL``, the
    tolerance the payload checks below rest on."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def fwd(v, a, b):
        nz = v != 0
        ylog = a * jnp.log2(jnp.where(nz, jnp.abs(v), 1.0)) + b
        return jnp.where(nz, jnp.sign(v) * jnp.exp2(ylog), 0.0)

    t = jax.random.uniform(key, x.shape, minval=-20.0, maxval=16.0)
    a, b = float(alpha), float(beta)
    x64, t64 = np.asarray(x, np.float64), np.asarray(t, np.float64)
    nz = x64 != 0
    l2 = np.log2(np.abs(np.where(nz, x64, 1.0)))
    ylog = a * l2 + b
    y = np.where(nz, np.sign(x64) * np.exp2(ylog), 0.0)
    e2 = np.exp2(t64)
    f32 = np.float32
    # the f32 roundings of alpha * log2|x| and of the sum with beta
    arith = 0.5 * (np.spacing(np.abs(a * l2).astype(f32))
                   + np.spacing(np.abs(ylog).astype(f32)))
    for engine, run in (("XLA", lambda fn, *v: jax.jit(fn)(*v)),
                        ("Mosaic", _mosaic)):
        got_l2 = np.asarray(run(lambda v: jnp.log2(jnp.abs(v)), x),
                            np.float64)
        got_e2 = np.asarray(run(jnp.exp2, t), np.float64)
        got_y = np.asarray(run(fwd, x, alpha, beta), np.float64)
        err_l2 = np.abs(got_l2 - l2)[nz]
        ulps_l2 = (err_l2 / np.spacing(np.abs(l2[nz]).astype(f32))).max()
        rel_e2 = (np.abs(got_e2 - e2) / e2).max()
        rel_y = (np.abs(got_y - y)[nz] / np.abs(y[nz])).max()
        implied = (np.log(2) * (a * err_l2 + arith[nz])).max() + rel_e2
        log(f"{engine} on this device vs float64: log2 max {ulps_l2:.4g} "
            f"ulp ({err_l2.max():.3g} abs), exp2 max {rel_e2:.3g} "
            f"relative ({rel_e2 / 2.0 ** -24:.4g} x 2^-24); Eq. 5 forward "
            f"map max {rel_y:.3g} relative, implied by those <= "
            f"{implied:.3g} (alpha {a:.6g}, beta {b:.6g})")
        check(rel_y <= EQ5_RTOL,
              f"{engine} Eq. 5 forward map outside EQ5_RTOL of float64")


def eq5_check(what: str, x, payload, out, alpha, beta) -> None:
    """An engine's payload and truncated values against Eq. 5 in float64.

    The payload must be the float64 forward image rounded to e5m2, or,
    where that image lies within ``EQ5_RTOL`` of a rounding midpoint, the
    neighbour across it: the map is monotone, so the rounded endpoints of
    that interval bracket every admissible payload.  The truncated value
    must be the float64 inverse map of one of those grid points within
    ``EQ5_RTOL`` (quantize and truncate are separate programs, and each
    may take either side of a near tie).
    """
    import ml_dtypes
    import numpy as np
    from repro.core import fp8
    f8 = ml_dtypes.float8_e5m2
    a, b = float(alpha), float(beta)

    def to_grid(y):
        return y.astype(np.float32).astype(f8).astype(np.float64)

    def inverse(p):
        nz = p != 0
        return np.where(nz, np.sign(p) * np.exp2(
            (np.log2(np.where(nz, np.abs(p), 1.0)) - b) / a), 0.0)

    x = np.asarray(x, np.float64)
    nz = x != 0
    y = np.where(nz, np.sign(x) * np.exp2(
        a * np.log2(np.where(nz, np.abs(x), 1.0)) + b), 0.0)
    y = np.clip(y, -fp8.E5M2_MAX, fp8.E5M2_MAX)
    ends = [to_grid(y * (1 + t)) for t in (-EQ5_RTOL, EQ5_RTOL)]
    lo, hi = np.minimum(*ends), np.maximum(*ends)
    p = np.asarray(payload).astype(np.float64)
    out = np.asarray(out, np.float64)
    rel = np.minimum(*(np.abs(out - v) / np.where(v != 0, np.abs(v), 1.0)
                       for v in (inverse(lo), inverse(hi))))
    exact = to_grid(y)
    flip = p != exact
    # how far the flipped elements' float64 images sit from the midpoint
    # the engine rounded across: a lower bound on its forward-map error
    near = np.abs(y - (p + exact) / 2)[flip] / np.abs(y[flip])
    log(f"{what} {x.shape}: payload = float64 Eq. 5 in "
        f"{1 - flip.mean():.6g} of elements (flips up to "
        f"{near.max(initial=0.0):.3g} relative from their midpoint), max "
        f"value error {rel.max():.3g} relative")
    check(((lo <= p) & (p <= hi)).all(),
          f"{what}: payload off the float64 Eq. 5 grid point")
    check((rel <= EQ5_RTOL).all(),
          f"{what}: value off the float64 inverse map")


def oracle_checks(cfg, batch: int, seq: int, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import backend as nbackend
    from repro.core import s2fp8
    from repro.kernels import ref
    from repro.kernels.flash_attention import (flash_fwd_reference,
                                               qflash_fwd_pallas)

    pal, refb = nbackend.get_backend("pallas"), nbackend.get_backend("ref")
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))
    tokens, d, f = batch * seq, cfg.d_model, cfg.d_ff

    # XLA-path FP8 rounding (core/fp8.py) against the host's FP8 cast, over
    # magnitudes from below the e5m2 subnormals to past its overflow
    import ml_dtypes
    from repro.core import fp8
    v = np.asarray(jax.random.normal(next(keys), (1 << 20,)) * jnp.exp2(
        jax.random.uniform(next(keys), (1 << 20,), minval=-20, maxval=20)))
    want = v.astype(ml_dtypes.float8_e5m2).astype(np.float32)
    got = np.asarray(jax.jit(fp8.truncate_e5m2)(v))
    log(f"fp8.truncate_e5m2 == FP8 cast in {np.mean(got == want):.6g} of "
        f"{v.size} values")
    check(np.array_equal(got, want), "fp8.truncate_e5m2 != the FP8 cast")

    x = jax.random.normal(next(keys), (tokens, d)) * 1e-3
    st = s2fp8.compute_stats_jit(x)
    eq5_map_errors(x, *st, next(keys))
    outs, pays = {}, {}
    for name, be in (("pallas", pal), ("ref", refb)):
        q = be.quantize(x, stats=st)
        outs[name] = np.asarray(be.truncate(x, stats=st))
        pays[name] = np.asarray(q.payload)
        eq5_check(f"{name} truncate", x, pays[name], outs[name], *st)
        same = np.asarray(be.dequantize(q)) == outs[name]
        log(f"{name} truncate == dequantize(quantize) in {same.mean():.6g} "
            f"of elements")
        if name == "pallas":
            # one engine, one program for the maps: Eq. 5 is bitwise the
            # storage round trip
            check(same.all(), "pallas truncate != its dequantize(quantize)")
    # XLA and Mosaic evaluate log2/exp2 with different code on a TPU, so
    # the two engines agree to a few ulps here, bitwise only on the CPU
    log(f"pallas vs ref truncate: bitwise equal in "
        f"{np.mean(outs['pallas'] == outs['ref']):.6g}, payload bytes equal "
        f"in {np.mean(pays['pallas'] == pays['ref']):.6g} of elements")

    for layout, ash, bsh in (("nn", (tokens, d), (d, f)),
                             ("nt", (tokens, f), (d, f)),
                             ("tn", (tokens, d), (tokens, f))):
        qa = pal.quantize(jax.random.normal(next(keys), ash))
        qb = pal.quantize(jax.random.normal(next(keys), bsh) * 0.02)
        got = np.asarray(pal.qmatmul(qa, qb, layout=layout))
        want = np.asarray(ref.s2fp8_matmul_ref(
            qa.payload, qa.alpha, qa.beta, qb.payload, qb.alpha, qb.beta,
            layout=layout))
        bound = ref.dot_error_bound(
            ref.GEMM_SPECS[layout],
            ref.s2fp8_dequant_ref(qa.payload, qa.alpha, qa.beta),
            ref.s2fp8_dequant_ref(qb.payload, qb.alpha, qb.beta))
        err = np.abs(got - want)
        log(f"payload GEMM {layout} {ash}x{bsh}: max |err|/bound "
            f"{np.max(err / np.maximum(bound, 1e-30)):.3g}")
        check((err <= bound).all(),
              f"payload GEMM {layout} outside the f32 accumulation bound")

    g = cfg.n_heads // cfg.kv_heads
    hd = cfg.head_dim
    q = jax.random.normal(next(keys), (batch, cfg.kv_heads, g, seq, hd))
    k = jax.random.normal(next(keys), (batch, cfg.kv_heads, seq, hd))
    v = jax.random.normal(next(keys), (batch, cfg.kv_heads, seq, hd))
    qq, qk, qv = pal.quantize(q), pal.quantize(k), pal.quantize(v)
    for window in (cfg.window, None):
        out, lse = qflash_fwd_pallas(
            qq.payload.reshape(-1, seq, hd), qk.payload.reshape(-1, seq, hd),
            qv.payload.reshape(-1, seq, hd), (qq.alpha, qq.beta),
            (qk.alpha, qk.beta), (qv.alpha, qv.beta), g=g, causal=True,
            window=window)
        with jax.default_matmul_precision("highest"):
            r_out, r_lse = flash_fwd_reference(
                pal.dequantize(qq), pal.dequantize(qk), pal.dequantize(qv),
                causal=True, window=window)
        for name, a, b in (("out", out, r_out), ("lse", lse, r_lse)):
            a = np.asarray(a).reshape(b.shape)
            b = np.asarray(b)
            log(f"qflash fwd window={window} {name}: max |err| "
                f"{np.max(np.abs(a - b)):.3g}")
            # the raw-f32 tolerance of tests/test_qflash.py
            check(np.allclose(a, b, rtol=2e-5, atol=2e-6),
                  f"qflash {name} window={window} off its reference")


# ---------------------------------------------------------------------------
# phase 2: training through the launcher
# ---------------------------------------------------------------------------

def train_args(batch: int, seq: int, steps: int, seed: int, extra=()):
    from repro.launch.train import build_parser
    return build_parser().parse_args([
        "--arch", ARCH, "--policy", "s2fp8", "--backend", "auto",
        "--gemm-mode", "auto", "--steps", str(steps), "--batch", str(batch),
        "--seq", str(seq), "--seed", str(seed),
        "--stats-refresh-every", str(REFRESH_EVERY), *extra])


def losses_of(loop, steps: int) -> list:
    losses = [h["loss"] for h in loop.history]
    for i, h in enumerate(loop.history):
        log(f"step {i}: loss {h['loss']:.6f} wall {h['step_s']:.4f} s"
            f" (data {h['data_s']:.4f} s before it) refresh "
            f"{int(h.get('stats_refreshed', 0.0))}")
    check(len(losses) == steps, f"{len(losses)} of {steps} steps ran")
    check(all(math.isfinite(v) for v in losses), f"non-finite loss {losses}")
    return losses


def train_one_chip(cfg, batch: int, seq: int, steps: int, seed: int
                   ) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core import backend as nbackend
    from repro.kernels import auto_interpret
    from repro.launch.train import train

    args = train_args(batch, seq, steps, seed, ("--mesh", "none"))
    before = compile_clock()[0]
    plan_before = gemm_plan_counters()
    t0 = time.perf_counter()
    loop = train(cfg, args)
    wall = time.perf_counter() - t0
    losses_of(loop, steps)
    secs, hits = compile_clock()
    # the GEMM plan of the traced step (counted per trace, not per step)
    macs, padded, dequant, operand = (
        after - b for after, b in zip(gemm_plan_counters(), plan_before))
    log(f"train phase: {wall:.2f} s wall, compile {secs - before:.2f}"
        f" s, persistent-cache hits {hits}; GEMM plan: padded MACs "
        f"{padded / max(macs, 1):.4%}, each operand element dequantized "
        f"{dequant / max(operand, 1):.2f} times")

    # the step the loop ran, compiled again for inspection (a cache read
    # when the persistent cache holds it)
    step_args = [loop.params, loop.opt_state, loop.stats_bank,
                 loop.data_fn(0), jnp.int32(0)]
    t0 = time.perf_counter()
    compiled = loop.train_step.lower(*step_args).compile()
    log(f"step re-lowered and compiled in {time.perf_counter() - t0:.2f} s")
    text = compiled.as_text()
    n_kernels = text.count("tpu_custom_call")
    log(f"compiled train step: {n_kernels} tpu_custom_call sites")
    check(n_kernels > 0, "compiled train step holds no Pallas TPU kernel")
    ma = compiled.memory_analysis()
    if ma is not None:
        log(f"step memory: arguments {ma.argument_size_in_bytes} B, "
            f"temporaries {ma.temp_size_in_bytes} B")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}")
    check(nbackend.get_backend("auto").name == "pallas"
          and not auto_interpret(), "kernels did not resolve to compiled "
                                    "Pallas on this device")


# ---------------------------------------------------------------------------
# four chips: compressed sync + quantized FSDP vs the f32 / replicated step
# ---------------------------------------------------------------------------

def train_four_chips(cfg, seq: int, steps: int, seed: int) -> None:
    from repro.launch.train import train
    curves = {}
    for name, extra in (("f32/replicated", ("--grad-sync", "f32",
                                            "--shard-params", "replicated")),
                        ("s2fp8/fsdp_q", ("--grad-sync", "s2fp8",
                                          "--shard-params", "fsdp_q"))):
        args = train_args(MESH_BATCH, seq, steps, seed,
                          ("--mesh", "4x1", *extra))
        before = compile_clock()[0]
        loop = train(cfg, args)
        log(f"{name}: compile {compile_clock()[0] - before:.2f} s")
        curves[name] = losses_of(loop, steps)
    ref_l, cmp_l = curves["f32/replicated"], curves["s2fp8/fsdp_q"]
    gaps = [abs(c - r) / abs(r) for c, r in zip(cmp_l, ref_l)]
    log("loss f32/replicated " + json.dumps(ref_l))
    log("loss s2fp8/fsdp_q   " + json.dumps(cmp_l))
    log(f"max relative loss gap {max(gaps):.4g} (bound {LOSS_GAP})")
    check(max(gaps) < LOSS_GAP, "s2fp8/fsdp_q losses diverge from "
                                "f32/replicated")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"[smoke] no TPU found: JAX sees "
                         f"{devices[0].platform} devices only")
    if len(devices) < args.chips:
        raise SystemExit(f"[smoke] --chips {args.chips} but "
                         f"{len(devices)} TPU device(s)")
    kind = devices[0].device_kind
    log(f"device {kind} x {len(devices)}")

    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    from repro.obs import spans
    spans.install_compile_listener()
    cfg = smoke_config()
    log(f"{cfg.name} cut to {cfg.n_layers} layers {cfg.pattern}: "
        f"{cfg.n_params() / 1e6:.1f} M params")
    t0 = time.perf_counter()
    if args.chips == 4:
        log(f"batch {MESH_BATCH} x seq {SEQ} over a 4x1 data mesh")
        train_four_chips(cfg, SEQ, STEPS, args.seed)
    else:
        log(f"batch {BATCH} x seq {SEQ}")
        oracle_checks(cfg, BATCH, SEQ, args.seed)
        train_one_chip(cfg, BATCH, SEQ, STEPS, args.seed)
    secs, hits = compile_clock()
    log(f"total compile {secs:.2f} s, persistent-cache hits {hits}, wall "
        f"{time.perf_counter() - t0:.2f} s")
    if FAILED:
        raise SystemExit(f"[smoke] {len(FAILED)} check(s) failed: {FAILED}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
