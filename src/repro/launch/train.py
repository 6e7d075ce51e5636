"""Training launcher.

Single host (this container):
    PYTHONPATH=src python -m repro.launch.train --arch minicpm_2b \
        --reduced --steps 50 --policy s2fp8 --ckpt-dir /tmp/ckpt --resume auto

Production pod: the same entry point under `jax.distributed.initialize()`
(one process per host); the mesh flag switches to the 16x16 / 2x16x16
production meshes and params/opt-state are sharded by the same rule tables
the dry-run proves out (launch/dryrun.py).
"""
from __future__ import annotations

import argparse
import contextlib

import jax
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.base import get_config, get_reduced_config
from repro.core import backend as nbackend
from repro.core import policy as policy_mod
from repro.core import statsbank
from repro.core.policy import make_policy
from repro.checkpoint.manager import CheckpointManager
from repro.data import synthetic
from repro.launch import api
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import (axis_sizes, make_host_mesh,
                               make_mesh_from_spec, make_production_mesh)
from repro import obs
from repro.optim import optimizers, schedules
from repro.parallel import sharding as shd
from repro.training import chaos as chaos_mod
from repro.training import guard as guard_mod
from repro.training import trainer as trainer_mod
from repro.training.trainer import TrainLoop, make_train_step


def build_parser() -> argparse.ArgumentParser:
    """The launcher's options.  ``--arch``/``--reduced`` pick the config in
    :func:`main`; everything else is read by :func:`train`."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-scale smoke/convergence runs)")
    ap.add_argument("--policy", default="s2fp8",
                    choices=["fp32", "bf16", "fp8", "fp8_ls", "s2fp8"])
    ap.add_argument("--backend", default=None,
                    choices=("auto",) + nbackend.available_backends(),
                    help="numerics backend for s2fp8 truncations "
                         "(default: the arch config's, usually 'auto')")
    ap.add_argument("--gemm-mode", default="auto",
                    choices=policy_mod.GEMM_MODES,
                    help="s2fp8 GEMM execution: 'payload' = qdot_train "
                         "(FP8 operand streaming, fused epilogue, NT/TN "
                         "payload backward), 'fig4' = the composed "
                         "truncation chain; 'auto' = payload on the "
                         "pallas engines")
    ap.add_argument("--loss-scale", type=float, default=100.0)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", default="none", choices=["none", "auto"])
    ap.add_argument("--mesh", default="host",
                    help="'host' (all local devices on the data axis), "
                         "'single'/'multi' (production 16x16 / 2x16x16), "
                         "a 'DxT' / 'PxDxT' spec (e.g. '8x1'), or 'none' "
                         "for the meshless single-device step")
    ap.add_argument("--grad-sync", default="f32", choices=["f32", "s2fp8"],
                    help="cross-shard gradient sync under the mesh: plain "
                         "f32 psum, or the S2FP8-compressed reduce-scatter"
                         "/all-gather schedule (core/collectives.py) for "
                         "every compressible leaf")
    ap.add_argument("--grad-sync-min-size", type=int, default=1 << 16,
                    help="element-count floor below which a gradient leaf "
                         "takes the exact f32 path even under s2fp8 sync "
                         "(stats overhead dominates small leaves; also the "
                         "floor for the FSDP compressed scatter leg)")
    ap.add_argument("--shard-params", default="replicated",
                    choices=trainer_mod.PARAM_SHARDING_MODES,
                    help="param/optimizer placement under the mesh: "
                         "'replicated' (every device holds full copies), "
                         "'fsdp' (ZeRO-3: leaves shard dim 0 over the "
                         "fsdp axis, f32 all-gather just-in-time, grads "
                         "reduce-scatter back), or 'fsdp_q' (gather "
                         "S2FP8 *payloads* — 1 byte/elt on the wire — "
                         "straight into the banked GEMMs; requires "
                         "--stats-refresh-every and a payload-GEMM "
                         "policy)")
    ap.add_argument("--track-stats", action="store_true")
    ap.add_argument("--stats-refresh-every", type=int, default=0,
                    help="enable the jit-carried StatsBank: refresh the "
                         "per-site (alpha, beta) reduction every K steps "
                         "(0 = off, exact stats every truncation)")
    ap.add_argument("--stats-ema", type=float, default=0.0,
                    help="EMA decay on the raw (mu, m) moments at each "
                         "StatsBank refresh (0 = replace)")
    ap.add_argument("--metrics-sink", default=None,
                    help="route loop records (step spans, watchdog / "
                         "checkpoint events, per-site FP8 health) to a "
                         "sink: jsonl:<path>, csv:<path>, console")
    ap.add_argument("--telemetry", action="store_true",
                    help="carry per-site FP8 health metrics in the "
                         "StatsBank (requires --stats-refresh-every) and "
                         "drain them to --metrics-sink each refresh")
    ap.add_argument("--guard", action="store_true",
                    help="arm the in-step StepGuard (training/guard.py): "
                         "non-finite loss/grad + grad-norm-spike "
                         "sentinels reject bad updates in-trace and the "
                         "loop escalates skip -> forced refresh -> "
                         "snapshot rollback -> checkpoint restore")
    ap.add_argument("--guard-spike-factor", type=float, default=10.0,
                    help="trip when grad_norm exceeds this multiple of "
                         "its accepted-step EMA")
    ap.add_argument("--guard-warmup", type=int, default=8,
                    help="accepted steps before the spike sentinel arms")
    ap.add_argument("--guard-sat-threshold", type=float, default=0.0,
                    help="trip when any StatsBank site's sat_frac "
                         "telemetry exceeds this fraction (0 = off; "
                         "needs --telemetry)")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="push (params, opt, bank, guard) onto an "
                         "in-memory snapshot ring every K clean steps — "
                         "the escalation ladder's rollback target "
                         "(0 = ring off)")
    ap.add_argument("--snapshot-ring", type=int, default=4,
                    help="snapshot ring depth")
    ap.add_argument("--snapshot-compress", action="store_true",
                    help="S2FP8-compress big snapshot leaves (~4x less "
                         "host memory; rollback no longer bitwise)")
    ap.add_argument("--watchdog-escalate-after", type=int, default=0,
                    help="N consecutive watchdog trips trigger a "
                         "proactive snapshot + watchdog_escalated event "
                         "(0 = trips stay log-only)")
    ap.add_argument("--chaos", default=None,
                    help="deterministic fault injection spec "
                         "(training/chaos.py), e.g. 'nan_grad@5x3,"
                         "slow_step@12:0.5'; injectors: nan_grad, "
                         "inf_loss, reject, saturating_bank, "
                         "corrupt_ckpt, slow_step, corrupt_batch. "
                         "Implies --guard.")
    return ap


def train(cfg, args) -> TrainLoop:
    """Train ``cfg`` for ``args.steps`` steps under the options parsed by
    :func:`build_parser` and return the finished :class:`TrainLoop`
    (``loop.history`` holds the per-step metrics, ``step_s`` among them).
    """
    backend_name = args.backend or getattr(cfg, "numerics_backend", "auto")
    pol = make_policy(args.policy, loss_scale=args.loss_scale,
                      backend=backend_name, gemm_mode=args.gemm_mode)
    print(f"[train] numerics backend: {backend_name} "
          f"-> {pol.backend_obj.name} ({jax.default_backend()}), "
          f"gemm: {'payload' if pol.uses_payload_gemm else 'fig4'}")
    key = jax.random.PRNGKey(args.seed)

    if args.mesh == "none":
        mesh = None
    elif args.mesh == "host":
        mesh = make_host_mesh()
    elif args.mesh in ("single", "multi"):
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
    else:
        mesh = make_mesh_from_spec(args.mesh)
    sizes = axis_sizes(mesh) if mesh is not None else {}

    loss_fn = api.make_loss_fn(cfg)
    opt = optimizers.adamw(weight_decay=0.01)
    sched = schedules.make_schedule(
        cfg.schedule if cfg.schedule == "wsd" else "cosine",
        args.lr, total_steps=args.steps, warmup=max(args.steps // 20, 1))
    stats_cfg = None
    if args.stats_refresh_every > 0:
        stats_cfg = statsbank.StatsConfig(
            refresh_every=args.stats_refresh_every,
            ema_decay=args.stats_ema,
            telemetry=args.telemetry)
    if args.telemetry and stats_cfg is None:
        raise SystemExit("--telemetry requires --stats-refresh-every > 0 "
                         "(health metrics ride the StatsBank refresh)")
    # no sink spec: loop records fall back to the console (TrainLoop's
    # default), telemetry (if on) prints through an explicit ConsoleSink
    sink = obs.make_sink(args.metrics_sink) if args.metrics_sink else \
        (obs.ConsoleSink() if args.telemetry else None)
    telemetry = (obs.Telemetry(sink, every=args.stats_refresh_every)
                 if args.telemetry else None)
    chaos_plan = chaos_mod.ChaosPlan.parse(args.chaos) if args.chaos else None
    use_guard = args.guard or chaos_plan is not None
    if args.guard_sat_threshold > 0 and not args.telemetry:
        raise SystemExit("--guard-sat-threshold reads the StatsBank's "
                         "sat_frac telemetry leaves: add --telemetry "
                         "(and --stats-refresh-every)")
    guard_cfg = None
    if use_guard:
        guard_cfg = guard_mod.GuardConfig(
            spike_factor=args.guard_spike_factor,
            warmup=args.guard_warmup,
            sat_threshold=args.guard_sat_threshold)
        print(f"[train] step guard armed: spike x{guard_cfg.spike_factor} "
              f"(warmup {guard_cfg.warmup}), sat_threshold "
              f"{guard_cfg.sat_threshold}"
              + (f", chaos: {args.chaos}" if chaos_plan else ""))
    if args.shard_params != "replicated":
        if mesh is None:
            raise SystemExit("--shard-params needs a mesh (--mesh != none)")
        if args.shard_params == "fsdp_q" and stats_cfg is None:
            raise SystemExit("--shard-params fsdp_q streams payloads into "
                             "the banked GEMMs: add --stats-refresh-every "
                             "(and an s2fp8 payload-GEMM policy)")
    step_fn = make_train_step(loss_fn, opt, sched, pol,
                              track_stats=args.track_stats,
                              stats=stats_cfg, mesh=mesh,
                              grad_sync_mode=args.grad_sync,
                              grad_sync_min_size=args.grad_sync_min_size,
                              telemetry=telemetry, guard=guard_cfg,
                              param_sharding=args.shard_params)
    if mesh is not None:
        n_shards = 1
        for a in ("pod", "data"):
            n_shards *= sizes.get(a, 1)
        print(f"[train] mesh {dict(sizes)}: {n_shards}-way data-parallel "
              f"step, grad sync {args.grad_sync}, params "
              f"{args.shard_params}"
              + (f" ({shd.fsdp_axis_size(mesh)}-way over "
                 f"'{shd.fsdp_axis_entry(mesh)}')"
                 if args.shard_params != "replicated" else ""))
        if args.batch % n_shards != 0:
            print(f"[train] WARNING: --batch {args.batch} does not divide "
                  f"the {n_shards}-way data axis — the divisibility guard "
                  f"will REPLICATE the batch (every device computes the "
                  f"full batch; no data-parallel speedup)")
        if sizes.get("model", 1) > 1:
            print(f"[train] WARNING: the shard_map train step parallelizes "
                  f"the batch and (with --shard-params) the param store "
                  f"over the data axis only — the {sizes['model']}-way "
                  f"model axis runs duplicate compute (TP inside the step "
                  f"is a ROADMAP item); size the mesh as Nx1 to use every "
                  f"device for data")

    table = synthetic.make_markov_table(args.seed, cfg.vocab) \
        if not cfg.enc_dec else None

    def data_fn(step):
        if cfg.enc_dec:
            b = synthetic.seq2seq_batch(args.seed, step, args.batch,
                                        args.seq, args.seq, cfg.vocab)
            return {"enc_inputs": b["enc_tokens"], "dec_tokens": b["dec_tokens"],
                    "dec_labels": b["dec_labels"]}
        return synthetic.lm_batch(args.seed, step, args.batch, args.seq,
                                  cfg.vocab, table)

    mesh_ctx = mesh if mesh is not None else contextlib.nullcontext()
    rules_ctx = (shd.use_rules(shd.TRAIN_RULES, sizes) if mesh is not None
                 else contextlib.nullcontext())
    with mesh_ctx, rules_ctx:
        params = api.init_params(cfg, key)
        opt_state = opt.init(params)
        bank = None
        if stats_cfg is not None:
            bank = statsbank.init_bank(loss_fn, params, data_fn(0), pol,
                                       stats_cfg)
            print(f"[train] statsbank: {len(bank)} sites, refresh every "
                  f"{stats_cfg.refresh_every} steps, ema {stats_cfg.ema_decay}")
        if mesh is not None:
            # lay the carry out as the step returns it, so that step 0 and
            # every later step run one compiled program
            specs = shd.train_step_specs(
                data_fn(0), mesh, with_stats=bank is not None,
                param_sharding=args.shard_params, params=params,
                opt_state=opt_state)[0]

            def place(tree, spec):
                return jax.device_put(tree, jax.tree_util.tree_map(
                    lambda p: NamedSharding(mesh, p), spec,
                    is_leaf=lambda p: isinstance(p, PartitionSpec)))

            params, opt_state = place(params, specs[0]), place(opt_state,
                                                               specs[1])
            if bank is not None:
                bank = place(bank, specs[2])
        ckpt = None
        if args.ckpt_dir:
            ckpt = CheckpointManager(
                args.ckpt_dir,
                event_fn=(sink.emit if sink is not None else None))
        loop = TrainLoop(step_fn, params, opt_state,
                         chaos_mod.wrap_data_fn(data_fn, chaos_plan),
                         ckpt_manager=ckpt, ckpt_every=args.ckpt_every,
                         stats_bank=bank, sink=sink,
                         guard_state=(guard_mod.init_state() if use_guard
                                      else None),
                         chaos=chaos_plan,
                         snapshot_every=args.snapshot_every,
                         snapshot_ring=args.snapshot_ring,
                         snapshot_compress=args.snapshot_compress,
                         watchdog_escalate_after=args.watchdog_escalate_after)
        if args.resume == "auto" and ckpt is not None:
            loop.maybe_resume()
        loop.run(args.steps)
    if sink is not None:
        sink.close()
    return loop


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    cfg = get_reduced_config(args.arch) if args.reduced else get_config(args.arch)
    history = train(cfg, args).history
    final = history[-1] if history else {}
    print(f"[train] done: final loss {final.get('loss'):.4f}")


if __name__ == "__main__":
    main()
