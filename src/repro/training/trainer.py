"""Train-step factory: numerics policy + loss scaling + master-FP32 update,
single-device or mesh-native.

Implements the paper's Figure 4 training procedure for any model whose loss
is a closure over a Policy, plus the FP8+LS baselines (Eq. 6: scale the loss
by lambda, unscale the grads) and S2FP8 statistics tracking (Fig. 5).

``make_train_step`` returns a pure function
    (params, opt_state, batch, step) -> (params, opt_state, metrics)
suitable for jax.jit.  With ``mesh=...`` the SAME step body runs under
``shard_map``: the batch shards over the mesh's data axes
(parallel/sharding.py rules), gradients synchronize through
``core/collectives.grad_sync_axis`` — a plain f32 psum or the
S2FP8-compressed reduce-scatter/all-gather schedule (``grad_sync_mode``) —
and StatsBank refreshes all-reduce their (sum, max, count) partials so
bank statistics are GLOBAL.  ``mesh=None`` degrades exactly to the
single-device step (no collectives traced, bit-identical programs).

The distributed-mean convention: the local loss is scaled by
``1 / n_data_shards`` INSIDE the differentiated function, so per-shard
gradients are contributions to the global batch mean and the sync is a
pure SUM.  Folding the normalization into the loss (instead of pmean-ing
the grads) keeps every per-element cotangent numerically identical to the
single-device run — the property the bitwise parity suite in
tests/test_mesh_train.py pins down.  ``loss_fn`` must therefore return a
batch-MEAN loss (every loss in models/ does).
"""
from __future__ import annotations

import contextlib
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import io_callback

from repro.core import collectives
from repro.core import s2fp8
from repro.core import statsbank
from repro.core.policy import Policy
from repro.obs import spans
from repro.obs import telemetry as obs_telemetry
from repro.optim import optimizers as optim_mod
from repro.optim.optimizers import Optimizer, global_norm
from repro.parallel import sharding as shd
from repro.training import chaos as chaos_mod
from repro.training import fault
from repro.training import guard as guard_mod

GRAD_SYNC_MODES = ("f32", "s2fp8")
PARAM_SHARDING_MODES = ("replicated", "fsdp", "fsdp_q")


def make_train_step(loss_fn: Callable, optimizer: Optimizer,
                    schedule: Callable, policy: Policy,
                    track_stats: bool = False,
                    grad_sync: Optional[Callable] = None,
                    stats: Optional[statsbank.StatsConfig] = None,
                    mesh=None, grad_sync_mode: str = "f32",
                    grad_sync_min_size: int = 1 << 16,
                    grad_sync_backend: Optional[str] = None,
                    telemetry: Optional[obs_telemetry.Telemetry] = None,
                    guard: Optional[guard_mod.GuardConfig] = None,
                    param_sharding: str = "replicated"):
    """loss_fn(params, batch, policy) -> (loss, metrics_dict).

    * fp8_ls mode: loss scaled by policy.loss_scale before grad, grads
      unscaled after (paper Eq. 6).
    * grad_sync: optional cross-replica synchronizer for the meshless
      step (legacy hook; under ``mesh=...`` synchronization is built in
      and this must be None).
    * track_stats: returns (mu, m, alpha, beta) of a probe gradient tensor
      (paper Fig. 5 evolution plots).
    * stats: a ``statsbank.StatsConfig`` enables the jit-carried StatsBank
      — the returned step grows a ``stats_state`` carry::

          (params, opt_state, stats_state, batch, step)
              -> (params, opt_state, stats_state, metrics)

      Every Policy truncation reuses its bank entry; the Eq. 3–4 stats
      reduction runs under ``lax.cond`` only on ``refresh_every`` steps
      (and the bootstrap step).  The bank is an extra differentiated
      argument whose gradient IS the refreshed bank (statsbank docstring),
      so the carry is pure data flow — jit/pjit/scan/remat safe.  Build
      the initial carry with ``statsbank.init_bank(loss_fn, params,
      batch, policy, cfg)``.
    * mesh: a ``jax.sharding.Mesh`` makes the step mesh-native: the body
      runs under ``shard_map`` with the batch sharded over the mesh's
      batch axes (``parallel/sharding.mesh_batch_specs``), params /
      optimizer state / bank replicated, gradients SUM-synced across the
      data shards, loss/metrics psum'd to global means, and — with
      ``stats`` — the bank's refresh reductions made global via
      ``statsbank.for_mesh``.  A 1-device mesh reproduces the meshless
      step bitwise; ``mesh=None`` builds the meshless step itself.
    * grad_sync_mode: ``"f32"`` — plain f32 psum per gradient leaf;
      ``"s2fp8"`` — S2FP8-compressed all-reduce (bf16 reduce-scatter +
      1-byte payload all-gather) for every leaf
      ``collectives.leaf_sync_route`` deems compressible, plain psum for
      the rest (small / integer / 0-d / non-divisible leaves).
      ``grad_sync_min_size`` is the compression floor (elements);
      ``grad_sync_backend`` picks the encode/decode numerics engine.
    * telemetry: a ``repro.obs.Telemetry`` drains the bank's per-site
      health metrics host-side via ``io_callback`` each step (requires
      ``stats`` with ``telemetry=True`` for non-empty metrics).  The
      drain is a pure elementwise extraction — it adds ZERO reduce
      primitives, preserving the steady-state jaxpr invariant.  Under a
      mesh it runs on the replicated post-shard_map bank, so each step
      emits exactly once.
    * guard: a ``training/guard.GuardConfig`` arms the in-step StepGuard —
      the step grows a ``guard_state`` carry (after the bank when both are
      on)::

          (params, opt_state[, stats_state], guard_state, batch, step)
              -> (params, opt_state[, stats_state], guard_state, metrics)

      Non-finite loss/grad, grad-norm-spike-vs-EMA, and (with a
      telemetry bank and ``sat_threshold > 0``) bank-saturation sentinels
      evaluate on scalars the step already computes; a bad verdict
      rejects the update in-trace via ``lax.cond`` (pre-step trees pass
      through bit-identically, no recompile) and raises ``guard_*``
      metric flags the TrainLoop escalation ladder acts on.  The
      saturation probe FUSES into the bank's existing bookkeeping ``min``
      (one ``[2, N]`` reduce), so the steady-state jaxpr reduction budget
      is unchanged: fp32 baseline + 1 outside ``lax.cond``.  Build the
      carry with ``guard.init_state()``.

    * param_sharding: how param and optimizer leaves live on the mesh.
      ``"replicated"`` (default) — every device holds full copies, as
      before.  ``"fsdp"`` — eligible leaves (float, rank >= 1, dim 0
      divisible by the fsdp axis size; ``sharding.fsdp_leaf_eligible``)
      shard dim 0 over the rule table's fsdp axis, ZeRO-3 style: the step
      all-gathers each leaf just-in-time INSIDE the differentiated loss
      (f32 wire), the gather's custom_vjp reduce-scatters the gradient
      back to the owner shard (psum over the other batch axes first; the
      compressed ``grad_sync_mode="s2fp8"`` path becomes just its bf16
      reduce-scatter leg, routed per leaf by ``leaf_sync_route`` on the
      FULL leaf shape), and the optimizer update runs shard-local —
      ``clip_by_global_norm`` sees the mixed global norm through the
      ``optim.optimizers.fsdp_grads`` scope.  ``"fsdp_q"`` — additionally
      streams payload-eligible leaves (2-D, consumed by ``Policy.dot``)
      as S2FP8 *payloads*: quantize-at-owner with leaf-global bank stats,
      1-byte all-gather straight into the payload GEMM B slot (no
      f32/bf16 copy of the leaf materializes; jaxpr-asserted in
      tests/test_mesh_train.py), other consumption of a wrapped leaf
      falls back to the f32 gather via ``FSDPPayloadParam.__jax_array__``.
      Non-replicated modes need ``mesh`` with an fsdp-carrying axis;
      ``fsdp_q`` additionally needs ``stats`` and a payload-GEMM policy.
      Updated params/opt leaves come OUT of the step sharded
      (``sharding.fsdp_param_specs``); checkpoints still gather to full
      host arrays, so save/restore stays topology-agnostic.

    A ``batch["_chaos"]`` entry (attached by ``training/chaos.py``'s
    data_fn wrapper) is popped off the batch inside the step and drives
    the in-trace fault injectors (NaN grads / Inf loss / forced reject)
    as pure data — every schedule runs the identical compiled program.

    The numerics backend (ref jnp vs fused Pallas kernels) rides on the
    policy: ``policy.backend`` is validated at Policy construction and
    resolved through core/backend.py inside each truncation.
    """
    scale = policy.loss_scale if policy.mode == "fp8_ls" else 1.0
    if stats is not None and policy.mode not in ("s2fp8", "s2fp8_e4m3"):
        raise ValueError(
            f"StatsBank requires an s2fp8-mode policy, got {policy.mode!r}")
    if telemetry is not None and stats is None:
        raise ValueError("telemetry requires a StatsBank (stats=...)")
    if grad_sync_mode not in GRAD_SYNC_MODES:
        raise ValueError(f"grad_sync_mode must be one of {GRAD_SYNC_MODES}, "
                         f"got {grad_sync_mode!r}")
    if mesh is not None and grad_sync is not None:
        raise ValueError("mesh=... builds its own gradient sync; the "
                         "legacy grad_sync callable must be None")

    if param_sharding not in PARAM_SHARDING_MODES:
        raise ValueError(f"param_sharding must be one of "
                         f"{PARAM_SHARDING_MODES}, got {param_sharding!r}")

    batch_axes = shd.mesh_batch_axes(mesh) if mesh is not None else ()
    axis_name = (None if not batch_axes
                 else batch_axes[0] if len(batch_axes) == 1 else batch_axes)
    n_shards = shd.mesh_batch_size(mesh) if mesh is not None else 1
    axis_sizes = ({a: mesh.shape[a] for a in batch_axes}
                  if mesh is not None else {})
    if stats is not None and mesh is not None:
        # mesh=None leaves the config untouched: a caller wrapping the
        # meshless step in their own pmap/shard_map may have set
        # axis_name themselves (the legacy grad_sync-hook path)
        stats = statsbank.for_mesh(stats, mesh)

    fsdp_axis = shd.fsdp_axis_entry(mesh) if mesh is not None else None
    gather_f32 = pay_info = None
    if param_sharding != "replicated":
        if mesh is None or fsdp_axis is None:
            raise ValueError(f"param_sharding={param_sharding!r} needs a "
                             f"mesh whose axes carry the rule table's "
                             f"'fsdp' logical axis")
        if param_sharding == "fsdp_q":
            if stats is None:
                raise ValueError("param_sharding='fsdp_q' quantizes at "
                                 "the owner with leaf-global bank stats — "
                                 "pass stats=StatsConfig(...)")
            if not policy.uses_payload_gemm:
                raise ValueError("param_sharding='fsdp_q' streams payload "
                                 "operands; the policy must route GEMMs "
                                 "through qdot_train (s2fp8 mode with "
                                 "gemm_mode='payload' or a pallas backend)")
        fsdp_n = mesh.shape[fsdp_axis]
        lead_axes = tuple(a for a in batch_axes if a != fsdp_axis)
        # one FSDPInfo + ONE custom_vjp gather per step factory, so the
        # custom_vjp identity (and the _qdot_banked cache key) is stable
        # across retraces
        base_info = collectives.FSDPInfo(
            fsdp_axis, fsdp_n, lead_axes, grad_sync_mode,
            grad_sync_min_size, grad_sync_backend)
        gather_f32 = collectives.make_param_gather(base_info)
        pay_info = base_info._replace(gather_f32=gather_f32)

    def _scale_loss(loss):
        # lambda-scaling (Eq. 6) and the DP mean-normalization both fold
        # INTO the differentiated function: per-shard grads come out as
        # contributions to the global batch mean, so the sync is a pure
        # sum and per-element cotangents match the single-device run.
        if scale != 1.0:
            loss = loss * scale
        if n_shards > 1:
            loss = loss / float(n_shards)
        return loss

    def _sync(grads, skip=None):
        if axis_name is not None:
            return collectives.grad_sync_axis(
                grads, axis_name, axis_sizes, mode=grad_sync_mode,
                min_size=grad_sync_min_size, backend=grad_sync_backend,
                skip=skip)
        if grad_sync is not None:
            return grad_sync(grads)
        return grads

    def _global(x):
        # scalar metrics are per-shard contributions (already 1/n-scaled):
        # psum them to the global mean; identity off-mesh.
        return jax.lax.psum(x, axis_name) if axis_name is not None else x

    def _drain_telemetry(bank, step):
        # ship the bank's telemetry leaves to the host sink; a pure
        # elementwise extraction (no reductions), ordered so records hit
        # the sink in step order.  Empty for telemetry-off banks.  Under
        # a mesh the callback must be PINNED to one device: the bank is
        # replicated, and an unplaced io_callback in a multi-device
        # program trips XLA's sharding propagation (and would otherwise
        # fire once per device).
        if telemetry is None:
            return
        state = obs_telemetry.telemetry_state(bank, step)
        if state:
            if mesh is None:
                io_callback(telemetry.drain, None, state, step,
                            ordered=True)
            else:
                # ordered effects are single-device only; records carry
                # their step, so cross-step ordering is recoverable
                io_callback(telemetry.drain, None, state, step,
                            sharding=jax.sharding.SingleDeviceSharding(
                                mesh.devices.flat[0]))

    def _make_reduce_metrics(int_div: int):
        # every metric leaf must leave the shard_map replicated (out_specs
        # P() with check_vma=False would silently report shard 0's local
        # value otherwise): float leaves psum to the global MEAN of the
        # per-shard means, integer leaves (counts) psum to the global SUM
        # — divided back by the shard count when the batch took the
        # replicated fallback (every shard counted the full batch).
        def _reduce_metrics(metrics):
            if axis_name is None:
                return metrics

            def red(v):
                v = jnp.asarray(v)
                if jnp.issubdtype(v.dtype, jnp.floating):
                    return _global(v / float(n_shards) if n_shards > 1
                                   else v)
                if jnp.issubdtype(v.dtype, jnp.integer):
                    s = _global(v)
                    return s // int_div if int_div > 1 else s
                if v.dtype == jnp.bool_:
                    # flags (diverged/overflow markers) reduce as ANY:
                    # a True on one shard must survive to the host
                    return _global(v.astype(jnp.int32)) > 0
                return v
            return jax.tree_util.tree_map(red, dict(metrics))

        return _reduce_metrics

    def _build_step(int_div: int = 1, elig=None, pay=None):
        reduce_metrics = _make_reduce_metrics(int_div)

        def _gather_params(p):
            # FSDP just-in-time gather, INSIDE the differentiated loss:
            # eligible leaves enter as dim-0 shards and leave either
            # through the f32 custom_vjp gather (grads reduce-scatter
            # back in its backward) or wrapped as FSDPPayloadParam (the
            # payload handoff Policy.dot/qdot_train consume — 1-byte
            # all-gather, same sharded-grad contract).
            if elig is None:
                return p

            def g(leaf, e, q):
                if not e:
                    return leaf
                if q:
                    return collectives.FSDPPayloadParam(leaf, pay_info)
                return gather_f32(leaf)

            return jax.tree_util.tree_map(g, p, elig, pay)

        def scaled_loss(params, batch):
            loss, metrics = loss_fn(_gather_params(params), batch, policy)
            return _scale_loss(loss), metrics

        def _core(params, opt_state, stats_state, guard_state, batch, step):
            # the chaos schedule (if armed) rides the batch as int32
            # scalars — popped here so loss_fn never sees it and every
            # schedule traces to the same program
            batch, chaos_fields = chaos_mod.split_batch(batch)
            if stats_state is None:
                (loss, metrics), grads = jax.value_and_grad(
                    scaled_loss, has_aux=True)(params, batch)
                new_bank = None
            else:
                def banked_loss(p, bank):
                    with statsbank.bind(bank, step, stats):
                        loss, metrics = loss_fn(_gather_params(p), batch,
                                                policy)
                    return _scale_loss(loss), metrics

                (loss, metrics), (grads, bank_cot) = jax.value_and_grad(
                    banked_loss, argnums=(0, 1), has_aux=True)(params,
                                                               stats_state)
                new_bank = statsbank.merge_updates(stats_state, bank_cot)
            if scale != 1.0:
                grads = jax.tree_util.tree_map(lambda g: g / scale, grads)
                loss = loss / scale
            # FSDP leaves exit value_and_grad already reduce-scattered to
            # the owner shard (the gather custom_vjp's backward) — the
            # replicated sync skips them
            grads = _sync(grads, skip=elig)
            metrics = reduce_metrics(metrics)
            loss = _global(loss)
            # in-trace fault injection points: data-driven `where`s on the
            # post-sync globals, so a fired injector perturbs exactly what
            # the guard must catch and nothing else
            loss = chaos_mod.inject_loss(chaos_fields, loss, step)
            grads = chaos_mod.inject_grads(chaos_fields, grads, step)

            sat_margin = None
            if stats_state is not None:
                # sites also refresh on bootstrap (last < 0), not just on
                # cadence; one O(n_sites) min over the concatenated
                # bookkeeping scalars — the single non-cond reduction the
                # bank step adds (asserted in tests/test_statsbank.py::
                # test_zero_stats_reductions_outside_cond).  With the
                # guard's saturation sentinel armed the probe widens to a
                # [2, N] stack (guard.bank_probe) — still ONE reduce_min.
                # The bank is replicated under the mesh (refreshes
                # all-reduce their partials), so no psum is needed here.
                thresh = guard.sat_threshold if guard is not None else 0.0
                cold_min, sat_margin = guard_mod.bank_probe(
                    stats_state, new_bank, thresh)
                metrics["stats_refreshed"] = jnp.maximum(
                    (step % stats.refresh_every == 0).astype(jnp.float32),
                    (cold_min < 0).astype(jnp.float32))

            lr = schedule(step)
            # the candidate update is computed UNconditionally (its clip
            # reductions stay outside lax.cond, matching the fp32
            # baseline's count); the guard's cond below is a pure select.
            # Under FSDP the update runs shard-local (ZeRO-3: opt state
            # only for owned shards) inside the fsdp_grads scope, so the
            # optimizer's clip — and the grad_norm metric below — psum
            # sharded-leaf sum-of-squares over the fsdp axis.
            norm_scope = (optim_mod.fsdp_grads(fsdp_axis, elig)
                          if elig is not None else contextlib.nullcontext())
            with norm_scope, jax.named_scope(spans.OPTIMIZER):
                new_params, new_opt = optimizer.update(grads, opt_state,
                                                       params, lr)
                # grads are post-sync (replicated-global under a mesh, or
                # owner shards under FSDP — the scope makes the norm
                # global either way), so no axis_name is needed here.
                grad_norm = global_norm(grads)
            out = dict(metrics)
            out["loss"] = loss
            out["grad_norm"] = grad_norm
            out["lr"] = lr
            if track_stats:
                probe = jax.tree_util.tree_leaves(grads)[-1]
                out["probe_stats"] = s2fp8.tensor_stats(probe)

            new_guard = None
            if guard is not None:
                flags, new_guard = guard_mod.evaluate(
                    guard, guard_state, loss, out["grad_norm"], sat_margin,
                    chaos_mod.forced_reject(chaos_fields, step))
                new_params, new_opt = guard_mod.reject_update(
                    flags["ok"], (new_params, new_opt),
                    (params, opt_state))
                if new_bank is not None:
                    new_bank = guard_mod.reject_update(
                        flags["ok_bank"], new_bank, stats_state)
                out.update(guard_mod.flag_metrics(flags))
            if new_bank is not None and mesh is None:
                # mesh path drains AFTER shard_map (replicated bank, one
                # callback) — see sharded_step
                _drain_telemetry(new_bank, step)
            return new_params, new_opt, new_bank, new_guard, out

        if stats is None and guard is None:
            def train_step(params, opt_state, batch, step):
                p, o, _, _, out = _core(params, opt_state, None, None,
                                        batch, step)
                return p, o, out
            return train_step
        if stats is None:
            def train_step_guarded(params, opt_state, guard_state, batch,
                                   step):
                p, o, _, g, out = _core(params, opt_state, None,
                                        guard_state, batch, step)
                return p, o, g, out
            return train_step_guarded
        if guard is None:
            def train_step_with_stats(params, opt_state, stats_state,
                                      batch, step):
                p, o, b, _, out = _core(params, opt_state, stats_state,
                                        None, batch, step)
                return p, o, b, out
            return train_step_with_stats

        def train_step_with_stats_guarded(params, opt_state, stats_state,
                                          guard_state, batch, step):
            p, o, b, g, out = _core(params, opt_state, stats_state,
                                    guard_state, batch, step)
            return p, o, b, g, out
        return train_step_with_stats_guarded

    if mesh is None:
        return _build_step()

    bodies = {}

    def sharded_step(*args):
        # specs resolve against the CONCRETE batch (divisibility guard
        # needs leaf shapes), so the shard_map is built per call — free
        # under jit, which retraces per input structure anyway.  When the
        # batch takes the replicated fallback, integer count metrics are
        # divided back by the shard count (every shard counted the full
        # batch).
        batch = args[-2]
        int_div = 1 if shd.batch_is_sharded(batch, mesh) else n_shards
        if param_sharding == "replicated":
            elig = pay = None
            key = int_div
        else:
            # eligibility resolves on the GLOBAL leaves out here — inside
            # the shard_map body dim 0 is already divided and the
            # predicate would be ambiguous.  The same predicate drives
            # train_step_specs, so specs and gathers stay in lockstep.
            elig = jax.tree_util.tree_map(
                lambda p: shd.fsdp_leaf_eligible(p.shape, p.dtype, fsdp_n),
                args[0])
            pay = jax.tree_util.tree_map(
                lambda p, e: bool(e and param_sharding == "fsdp_q"
                                  and p.ndim == 2), args[0], elig)
            key = (int_div, tuple(jax.tree_util.tree_leaves(elig)),
                   tuple(jax.tree_util.tree_leaves(pay)))
        if key not in bodies:
            step_fn = _build_step(int_div, elig, pay)

            def local_body(*a, _step_fn=step_fn):
                # inside shard_map every tensor is a local shard and the
                # mesh axes are manual: the models' logical-axis
                # annotations (sharding.shard) must not emit GSPMD
                # constraints here.
                with shd.suspend_rules():
                    return _step_fn(*a)

            bodies[key] = local_body
        in_specs, out_specs = shd.train_step_specs(
            batch, mesh, with_stats=stats is not None,
            with_guard=guard is not None, param_sharding=param_sharding,
            params=args[0], opt_state=args[1])
        out = jax.shard_map(bodies[key], mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)(*args)
        if stats is not None:
            _drain_telemetry(out[2], args[-1])
        return out

    return sharded_step


def make_eval_step(loss_fn: Callable, policy: Policy):
    def eval_step(params, batch):
        _, metrics = loss_fn(params, batch, policy)
        return metrics
    return eval_step


class TrainLoop:
    """Host-side loop: prefetch, checkpoint-every-k, auto-resume, watchdog,
    and the resilience escalation ladder.

    Single-host here (1 or N local devices — the mesh-native step from
    ``make_train_step(mesh=...)`` drops in unchanged; jit lays the batch
    out per the step's shard_map specs).

    ``stats_bank``: the StatsBank carry for a step built with
    ``make_train_step(..., stats=...)``.  It is checkpointed alongside
    (params, opt_state) and restored by ``maybe_resume`` — a resumed run
    truncates with warm stats instead of silently bootstrapping cold.
    Checkpoints gather sharded leaves to host (checkpoint/manager.py), so
    a carry saved from an N-device mesh restores on any device count.

    ``guard_state``: the StepGuard carry for a step built with
    ``make_train_step(..., guard=...)``.  When the step's ``guard_ok``
    metric reports a trip (the update was already rejected IN-TRACE), the
    loop walks the escalation ladder, one rung per CONSECUTIVE trip:

        1. skip        — the rejection is the whole intervention
        2. force a StatsBank refresh (``statsbank.force_refresh``: every
           site bootstrap-refreshes next step, EMA re-seeded)
        3. roll back   — restore the newest :class:`guard.SnapshotRing`
           entry and rewind the step counter (deterministic data makes the
           replay exact; chaos injections are single-fire, so a replayed
           fault step runs clean)
        4. restore the newest VALID checkpoint (the manager quarantines
           corrupt ones on the way)

    Inapplicable rungs collapse (no bank -> 2 skipped; empty ring -> 3
    falls through to 4; no checkpoint -> keep skipping).  A clean step
    resets the rung.  Every intervention is emitted through ``sink`` as a
    structured event: ``guard_tripped``, ``stats_refresh_forced``,
    ``rollback``, ``checkpoint_restore`` (plus the manager's
    ``checkpoint_quarantined``).  ``max_interventions`` bounds a
    persistently-faulting run (RuntimeError instead of a silent loop).

    ``snapshot_every=k`` pushes (params, opt[, bank][, guard]) onto an
    in-memory :class:`guard.SnapshotRing` after every k-th clean step
    (``snapshot_compress=True`` routes big leaves through the S2FP8
    codec; lossy — leave off when replays must be bitwise).

    ``chaos``: a ``training/chaos.ChaosPlan`` — the loop calls its
    host-side hooks (bank mutation before the step, straggler sleep
    inside the timed span, checkpoint corruption after a save); the
    in-trace schedule must additionally ride the batch via
    ``chaos.wrap_data_fn``.

    ``watchdog_escalate_after=N``: N consecutive watchdog trips push a
    proactive snapshot and emit ``watchdog_escalated`` (0 disables; trips
    stay log-only).

    ``sink``: a ``repro.obs.MetricsSink`` receiving the loop's records —
    per-step ``"train_step"`` lines with the durations of the step's
    ``train/data``, ``train/step`` (dispatch through ``block_until_ready``)
    and ``train/ckpt`` spans and whether the step refreshed the StatsBank,
    ``"event"`` records (watchdog trips, checkpoint saves, ladder
    interventions), and at the end of ``run`` the span, counter and
    compile records of ``repro.obs.spans.RECORDER``.  Defaults to a
    ``ConsoleSink`` over ``run``'s ``print_fn``, which reproduces the
    historical log lines.

    Each step runs inside a ``jax.profiler.StepTraceAnnotation`` named
    ``train``, so a profiler trace shows the loop's steps, and their
    spans, on the device trace's clock.  The jitted step's function is
    registered with the recorder, which then tells its trace, lowering
    and compile apart from every other function's.
    """

    def __init__(self, train_step, params, opt_state, data_fn,
                 ckpt_manager=None, ckpt_every: int = 0,
                 log_every: int = 10, watchdog_factor: float = 3.0,
                 stats_bank=None, sink=None, guard_state=None,
                 chaos=None, snapshot_every: int = 0,
                 snapshot_ring: int = 4, snapshot_compress: bool = False,
                 watchdog_escalate_after: int = 0,
                 max_interventions: int = 32):
        donate = tuple(range(2 + (stats_bank is not None)
                             + (guard_state is not None)))
        self.train_step = jax.jit(train_step, donate_argnums=donate)
        spans.install_compile_listener()
        spans.RECORDER.register_step(train_step)
        self.params = params
        self.opt_state = opt_state
        self.stats_bank = stats_bank
        self.guard_state = guard_state
        self.data_fn = data_fn
        self.ckpt = ckpt_manager
        self.ckpt_every = ckpt_every
        self.log_every = log_every
        self.watchdog_factor = watchdog_factor
        self.watchdog_escalate_after = watchdog_escalate_after
        self.chaos = chaos
        self.snapshot_every = snapshot_every
        self.ring = (guard_mod.SnapshotRing(snapshot_ring,
                                            compress=snapshot_compress)
                     if snapshot_every else None)
        self.max_interventions = max_interventions
        self.sink = sink
        self.start_step = 0
        self.history = []

    # -- state tree plumbing ------------------------------------------------
    def _state_tree(self):
        """(params, opt[, bank][, guard]) — the checkpoint/snapshot unit."""
        tree = [self.params, self.opt_state]
        if self.stats_bank is not None:
            tree.append(self.stats_bank)
        if self.guard_state is not None:
            tree.append(self.guard_state)
        return tuple(tree)

    def _load_state(self, tree):
        tree = list(tree)
        self.params, self.opt_state = tree[0], tree[1]
        i = 2
        if self.stats_bank is not None:
            self.stats_bank = tree[i]
            i += 1
        if self.guard_state is not None:
            self.guard_state = tree[i]

    def _ckpt_tree(self):
        return self._state_tree()

    def _step_once(self, batch, step):
        args = [self.params, self.opt_state]
        if self.stats_bank is not None:
            args.append(self.stats_bank)
        if self.guard_state is not None:
            args.append(self.guard_state)
        out = self.train_step(*args, batch, jnp.int32(step))
        out = list(out)
        self.params, self.opt_state = out[0], out[1]
        i = 2
        if self.stats_bank is not None:
            self.stats_bank = out[i]
            i += 1
        if self.guard_state is not None:
            self.guard_state = out[i]
            i += 1
        return out[i]                        # metrics

    def maybe_resume(self):
        if self.ckpt is None:
            return
        try:
            # step=None walks newest -> oldest, quarantining corrupt dirs
            restored, latest = self.ckpt.restore(self._ckpt_tree())
        except FileNotFoundError:
            return
        self._load_state(restored)
        self.start_step = latest
        print(f"[trainer] resumed from step {latest}")

    # -- escalation ladder ---------------------------------------------------
    def _escalate(self, step: int, trips: int, sink) -> int:
        """One rung per consecutive trip; returns the next step to run
        (<= step means a rewind happened)."""
        if trips == 1:
            return step + 1                 # the in-trace rejection IS rung 1
        if trips == 2 and self.stats_bank is not None:
            self.stats_bank = statsbank.force_refresh(self.stats_bank)
            sink.emit({"kind": "event", "event": "stats_refresh_forced",
                       "step": step})
            return step + 1
        snap = self.ring.latest() if self.ring is not None else None
        if snap is not None:
            snap_step, tree = snap
            self._load_state(tree)
            sink.emit({"kind": "event", "event": "rollback", "step": step,
                       "to_step": snap_step,
                       "compressed": self.ring.compress})
            return snap_step
        if self.ckpt is not None:
            try:
                restored, s = self.ckpt.restore(self._ckpt_tree())
            except FileNotFoundError:
                return step + 1
            self._load_state(restored)
            sink.emit({"kind": "event", "event": "checkpoint_restore",
                       "step": step, "to_step": s})
            return s
        return step + 1

    def run(self, steps: int, print_fn=print):
        from repro.obs.sinks import ConsoleSink
        sink = self.sink if self.sink is not None else ConsoleSink(print_fn)
        watchdog = fault.Watchdog(self.watchdog_factor)
        wd_consecutive = 0
        trips = 0                # consecutive guard trips = ladder rung
        interventions = 0
        step = self.start_step
        while step < steps:
            with jax.profiler.StepTraceAnnotation(spans.STEP_TRACE,
                                                  step_num=step):
                with spans.span(spans.TRAIN_DATA) as data_span:
                    # the data may be made by a jitted program: wait for
                    # it here, so that its time counts as data_s and not
                    # as step time
                    batch = jax.block_until_ready(self.data_fn(step))
                data_s = data_span.seconds
                if self.chaos is not None:
                    mutated = self.chaos.mutate_bank(step, self.stats_bank)
                    if mutated is not None:
                        self.stats_bank = mutated
                with spans.span(spans.TRAIN_STEP) as step_span:
                    if self.chaos is not None:
                        # straggler injection lands INSIDE the timed span
                        # so the watchdog sees it
                        self.chaos.maybe_sleep(step)
                    metrics = self._step_once(batch, step)
                    # device-sync the span: the step dispatches
                    # asynchronously, so wall-clock without the barrier
                    # measures dispatch, not compute
                    jax.block_until_ready((self.params, metrics))
                dt = step_span.seconds
                metrics = {k: (float(v) if hasattr(v, "item")
                               and getattr(v, 'ndim', 1) == 0 else v)
                           for k, v in metrics.items()}
                metrics["step_s"], metrics["data_s"] = dt, data_s
                # straggler watchdog: flag steps > factor x trailing median
                event = watchdog.observe(step, dt)
                if event is not None:
                    sink.emit({"kind": "event", "event": "watchdog",
                               "step": step, **event})
                    wd_consecutive += 1
                    if self.watchdog_escalate_after and \
                            wd_consecutive >= self.watchdog_escalate_after:
                        if self.ring is not None:
                            self.ring.push(step + 1, self._state_tree())
                        sink.emit({"kind": "event",
                                   "event": "watchdog_escalated",
                                   "step": step, "trips": wd_consecutive,
                                   "snapshot": self.ring is not None})
                        wd_consecutive = 0
                else:
                    wd_consecutive = 0
                self.history.append(metrics)
                tripped = (self.guard_state is not None
                           and metrics.get("guard_ok", 1.0) < 0.5)
                if tripped:
                    trips += 1
                    interventions += 1
                    cause = ",".join(c for c in ("nonfinite", "spike", "sat",
                                                 "forced")
                                     if metrics.get(f"guard_{c}", 0.0) >= 0.5)
                    sink.emit({"kind": "event", "event": "guard_tripped",
                               "step": step, "trip": trips,
                               "cause": cause or "unknown",
                               "loss": metrics.get("loss"),
                               "grad_norm": metrics.get("grad_norm")})
                    if interventions > self.max_interventions:
                        sink.flush()
                        raise RuntimeError(
                            f"StepGuard: {interventions} interventions "
                            f"without recovery (last trip at step {step}, "
                            f"cause {cause or 'unknown'}) — giving up")
                    next_step = self._escalate(step, trips, sink)
                    if next_step <= step:
                        trips = 0               # rewound: the ladder restarts
                    step = next_step
                    continue
                trips = 0
                if self.ring is not None and self.snapshot_every and \
                        (step + 1) % self.snapshot_every == 0:
                    # the state ENTERING step+1 — last-good by construction
                    # (this step just passed the guard)
                    self.ring.push(step + 1, self._state_tree())
                saved = bool(self.ckpt is not None and self.ckpt_every
                             and (step + 1) % self.ckpt_every == 0)
                ckpt_s = 0.0
                if saved:
                    with spans.span(spans.TRAIN_CKPT) as ckpt_span:
                        self.ckpt.save(step + 1, self._ckpt_tree(),
                                       blocking=False)
                    ckpt_s = ckpt_span.seconds
                    sink.emit({"kind": "event", "event": "checkpoint_saved",
                               "step": step + 1, "blocking_s": ckpt_s,
                               "write_s": getattr(self.ckpt,
                                                  "last_write_seconds", 0.0)})
                if self.chaos is not None and self.ckpt is not None:
                    damage = self.chaos.corrupt_checkpoint(step, self.ckpt)
                    if damage is not None:
                        sink.emit({"kind": "event",
                                   "event": "chaos_corrupt_ckpt",
                                   "step": step, **damage})
                if self.log_every and step % self.log_every == 0:
                    sink.emit({"kind": "train_step", "step": step,
                               "loss": metrics["loss"], "lr": metrics["lr"],
                               "grad_norm": metrics.get("grad_norm"),
                               "data_ms": data_s * 1e3, "step_ms": dt * 1e3,
                               "ckpt_ms": ckpt_s * 1e3,
                               "refreshed": metrics.get("stats_refreshed",
                                                        0.0) >= 0.5})
                step += 1
        if self.ckpt is not None:
            self.ckpt.wait()
        for record in spans.RECORDER.records():
            sink.emit(record)
        sink.flush()
        return self.history
