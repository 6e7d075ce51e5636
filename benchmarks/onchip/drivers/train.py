"""Driver for training cells: the program's jitted train step, timed.

Set-up builds one object, the program's ``TrainLoop`` around the step that
``repro.training.trainer.make_train_step`` returns (the factory the
training launcher uses), with the weights made by the reference's
``init_params`` from the seed and the batches by ``generator.py``.  On more
than one chip the job sets the layout as the launcher's options do: the
step is built on a ("data", "model") mesh of n x 1 over the cell's chips,
with the job's ``shard_params`` and ``grad_sync`` (default ``replicated``
and ``f32``), and the weights, the optimizer's state and every batch are
made where the step takes them; ``batch`` is the global batch.  Every
step, checked or timed, goes through one call, ``dispatch``: the next batch
and the loop's jitted ``train_step`` on the state the last step returned,
not waited for.  The first ``check_steps`` steps are each waited for; they
warm the step (the first is a StatsBank refresh) and give the readings that
are compared with the reference.  The window then keeps about ``AHEAD_S``
seconds of steps, and at most ``AHEAD_MAX``, dispatched ahead of the one
it waits for, so that a stall of the host does not leave the chip idle;
when its time is up it dispatches nothing more, waits for every step it
sent, and only then reads the clock.  The steps' metrics are read to the
host after the window.

End-to-end metrics: ``train_tokens_per_s`` (all tokens of all steps
dispatched in the window over the window's wall time, the wait for the
last of them included), ``peak_hbm_gb`` (``peak_bytes_in_use`` plus
``peak_bytes_reserved`` after the window, largest over the cell's chips:
the allocator's largest free block is what the limit leaves after both)
and ``setup_s`` (process start to the first timed step).
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import math
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import compare            # noqa: E402
import generator          # noqa: E402
import peaks as peaks_mod  # noqa: E402
import trace_reduce       # noqa: E402

# seconds of steps the window keeps dispatched ahead of the one it waits
# for, and at most this many steps: the TPU runtime holds 8 steps in
# flight and makes a ninth dispatch wait, which would leave the host's
# timestamps (``step_s``) a step or two behind the steps they belong to
AHEAD_S = 5.0
AHEAD_MAX = 8


def _field(obj, name: str):
    for part in name.split("."):
        obj = getattr(obj, part)
    return obj


def _replace(obj, changes: dict):
    """The dataclass ``obj`` with ``changes``, where a dotted name sets a
    field of a nested group."""
    top, nested = {}, collections.defaultdict(dict)
    for name, value in changes.items():
        head, _, rest = name.partition(".")
        if rest:
            nested[head][rest] = value
        else:
            top[head] = value
    for head, sub in nested.items():
        top[head] = _replace(getattr(obj, head), sub)
    return dataclasses.replace(obj, **top)


def program_config(cell):
    """The program's registered architecture, cut as the cell's
    configuration says.  The configuration's reference names the fields
    it needs and their values (``program_fields``); one that differs,
    other than the cuts, is an error."""
    from repro.configs.base import get_config
    name = cell.config["arch"]
    want = cell.reference.program_fields(cell.sizes)
    arch = _replace(get_config(name), {k: want[k] for k in cell.cut})
    wrong = {k: (_field(arch, k), v) for k, v in want.items()
             if _field(arch, k) != v}
    if wrong:
        raise ValueError(f"{name}: the program's configuration differs "
                         f"from the file's: {wrong}")
    return arch


def _check_tree(params, arch) -> None:
    import jax
    from repro.launch import api
    want = jax.eval_shape(lambda k: api.init_params(arch, k),
                          jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), params)
    want = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), want)
    if got != want:
        raise ValueError("the reference's weight tree does not match the "
                         "program's parameter tree")


def _layout(job, devices):
    """The mesh and ``make_train_step``'s layout arguments for the cell's
    chips, as the training launcher passes them; no mesh on one chip."""
    import jax
    from jax.sharding import AxisType
    if len(devices) == 1:
        return None, {}
    mesh = jax.make_mesh((len(devices), 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2, devices=devices)
    return mesh, {"mesh": mesh,
                  "grad_sync_mode": job.get("grad_sync", "f32"),
                  "param_sharding": job.get("shard_params", "replicated")}


def _shardings(mesh, layout, batch, params, opt_state, with_stats):
    """Where the step on ``mesh`` takes its weights, optimizer state,
    StatsBank (None without one) and batch, from their shapes
    (``parallel.sharding.train_step_specs``)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.parallel import sharding as shd
    specs = list(shd.train_step_specs(
        batch, mesh, with_stats=with_stats,
        param_sharding=layout["param_sharding"], params=params,
        opt_state=opt_state)[0][:-1])
    if not with_stats:
        specs.insert(2, None)
    return [None if spec is None else jax.tree_util.tree_map(
        lambda p: NamedSharding(mesh, p), spec,
        is_leaf=lambda p: isinstance(p, PartitionSpec)) for spec in specs]


def _held_bytes(device) -> int:
    """The most HBM the process has held on ``device``: the allocator's
    peak (arguments, outputs and other arrays) plus the runtime's peak
    reservation for the compiled programs' temporaries, which
    ``peak_bytes_in_use`` leaves out.  A CPU device (the harness's own
    tests) reports no memory stats."""
    stats = device.memory_stats() or {}
    return (stats.get("peak_bytes_in_use", 0)
            + stats.get("peak_bytes_reserved", 0))


def _norms_fn():
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda t: jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))


def run(cell, *, seed, seconds, trace, devices, clock, process_age,
        trace_dir, wrap_step, log):
    import jax
    import jax.numpy as jnp
    from repro.core import statsbank
    from repro.core.policy import make_policy
    from repro.launch import api
    from repro.obs.sinks import NullSink
    from repro.optim import optimizers, schedules
    from repro.training.trainer import TrainLoop, make_train_step

    job, sizes, ref = cell.job, cell.sizes, cell.reference
    o = job["optimizer"]
    batch, seq, n_check = job["batch"], job["seq"], job["check_steps"]
    arch = program_config(cell)
    mesh, layout = _layout(job, devices)

    # -- the program's step, built as the training launcher builds it -----
    pol = make_policy(job["policy"], loss_scale=job["loss_scale"],
                      backend=job["backend"], gemm_mode=job["gemm_mode"])
    loss_fn = api.make_loss_fn(arch)
    opt = optimizers.adamw(b1=o["b1"], b2=o["b2"], eps=o["eps"],
                           weight_decay=o["weight_decay"],
                           clip_norm=o["clip_norm"])
    sched = schedules.make_schedule("constant", o["lr"], total_steps=1)
    every = job["stats_refresh_every"]
    stats_cfg = statsbank.StatsConfig(refresh_every=every) if every else None
    step_fn = make_train_step(loss_fn, opt, sched, pol, stats=stats_cfg,
                              **layout)
    if wrap_step is not None:
        step_fn = wrap_step(step_fn)
    log(f"policy {pol.mode}: engine {pol.backend_obj.name}, payload GEMMs "
        f"{pol.uses_payload_gemm}; {arch.n_layers} layers, vocab "
        f"{arch.vocab}, {arch.n_params() / 1e6:.1f} M params; batch "
        f"{batch} x {seq}; mesh {mesh and dict(mesh.shape)}, params "
        f"{layout.get('param_sharding')}, grad sync "
        f"{layout.get('grad_sync_mode')}")

    key = generator.seed_key(seed, stream=0)
    # where the step takes its weights, optimizer state, bank and batch
    where = [None] * 4
    if mesh is not None:
        shapes = jax.eval_shape(lambda k: ref.init_params(sizes, k), key)
        rows = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
        where = _shardings(mesh, layout, {"tokens": rows, "labels": rows},
                           shapes, jax.eval_shape(opt.init, shapes),
                           stats_cfg is not None)
    params = ref.init_params(sizes, key, where[0])
    _check_tree(params, arch)
    make_batch = generator.make_batch_fn(seed, sizes["vocab"], batch, seq,
                                         job["tokens"], where[3])

    def data_fn(step):
        with jax.profiler.TraceAnnotation("bench/data"):
            return make_batch(step)

    bank = (statsbank.init_bank(loss_fn, params, data_fn(0), pol, stats_cfg)
            if stats_cfg is not None else None)
    if mesh is None:
        opt_state = opt.init(params)
    else:
        # made where the step returns them, so that every step runs one
        # compiled program (made eagerly, AdamW's moments would first be
        # whole on one chip)
        opt_state = jax.jit(opt.init, out_shardings=where[1])(params)
        if bank is not None:
            bank = jax.device_put(bank, where[2])
    loop = TrainLoop(step_fn, params, opt_state, data_fn,
                     stats_bank=bank, sink=NullSink(), log_every=0)
    del params, opt_state, bank
    # the step's state, in the order the loop's jitted step takes it; the
    # step donates it, so the loop's own references go stale after step 0
    state = [loop.params, loop.opt_state]
    if loop.stats_bank is not None:
        state.append(loop.stats_bank)

    def dispatch(step):
        """The window's call: one step on the state the last one returned,
        dispatched and not waited for; returns the step's metrics."""
        batch = data_fn(step)
        with jax.profiler.TraceAnnotation("bench/train_step"):
            out = loop.train_step(*state, batch, jnp.int32(step))
        state[:] = out[:-1]
        return out[-1]

    def to_host(metrics):
        return {k: float(v) if getattr(v, "ndim", 1) == 0 else v
                for k, v in jax.device_get(metrics).items()}

    # -- the checked steps, which also warm the step up ---------------------
    norms = _norms_fn()
    history = []
    for step in range(n_check):
        t = time.perf_counter()
        metrics = jax.block_until_ready(dispatch(step))
        history.append(dict(to_host(metrics),
                            step_s=time.perf_counter() - t))
        if step == 0:
            m_norms = compare.flatten(jax.device_get(norms(state[1].m)))
    change = compare.flatten(jax.device_get(
        ref.change_norms(state[0], sizes, key)))
    first = history[0]
    clip = min(1.0, o["clip_norm"] / (first["grad_norm"] + 1e-9)) \
        if o["clip_norm"] else 1.0
    prog = {"losses": [h["loss"] for h in history],
            "grad_norms": {k: v / (1 - o["b1"]) / clip
                           for k, v in m_norms.items()},
            "change_norms": change}
    compile_s, hits, events = clock.total(), clock.hits, clock.events
    setup_s = process_age()
    slowest = sorted(clock.secs.items(), key=lambda kv: -kv[1])[:3]
    ahead = max(1, min(AHEAD_MAX, round(AHEAD_S / history[-1]["step_s"])))
    log(f"set-up {setup_s:.3f} s: compile {compile_s:.3f} s over {events} "
        f"programs ({', '.join(f'{k} {v:.3f} s' for k, v in slowest)}), "
        f"{hits} persistent-cache hits; losses {prog['losses']}; the "
        f"window keeps {ahead} steps dispatched ahead")

    # -- the window ------------------------------------------------------------
    # ``done`` is when the host saw each step end; with the chip kept fed,
    # the gaps between them are the steps' device times
    if trace:
        jax.profiler.start_trace(str(trace_dir))
    pending, sent, done = collections.deque(), [], []

    def wait_oldest():
        with jax.profiler.TraceAnnotation("bench/wait"):
            jax.block_until_ready(pending.popleft())
        done.append(time.perf_counter())

    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench/window"):
        while time.perf_counter() - t0 < seconds:
            if len(pending) >= ahead:
                wait_oldest()
            pending.append(dispatch(n_check + len(sent)))
            sent.append(pending[-1])
        while pending:
            wait_oldest()
    wall = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    n = len(sent)
    window = [dict(to_host(m), step_s=b - a)
              for m, a, b in zip(sent, [t0] + done, done)]
    del sent, pending
    times = sorted(h["step_s"] for h in window)
    med = times[len(times) // 2]
    slow = [(i, round(h["step_s"], 4)) for i, h in enumerate(window)
            if h["step_s"] > 1.5 * med]
    log(f"window: {n} steps in {wall:.4f} s (step_s min {times[0]:.4f}, "
        f"median {med:.4f}, max {times[-1]:.4f}; steps over 1.5x the "
        f"median (index, step_s): {slow}); compiles inside it "
        f"{clock.events - events}")
    log(f"memory_stats after the window: {devices[0].memory_stats()}")
    peak = max(_held_bytes(d) for d in devices)
    tokens_per_s = n * batch * seq / wall
    out = {
        "attempted": n,
        "failed": sum(not math.isfinite(h["loss"]) for h in window),
        "memory_peak_bytes": peak,
        "end_to_end": {"train_tokens_per_s": tokens_per_s,
                       "peak_hbm_gb": peak / 1e9, "setup_s": setup_s},
    }
    if trace:
        red = trace_reduce.reduce_file(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ma = loop.train_step.lower(*state, data_fn(0),
                                   jnp.int32(0)).compile().memory_analysis()
        if ma is not None:
            log(f"compiled step memory: arguments "
                f"{ma.argument_size_in_bytes} B, outputs "
                f"{ma.output_size_in_bytes} B, aliased "
                f"{ma.alias_size_in_bytes} B, temporaries "
                f"{ma.temp_size_in_bytes} B; held at peak {peak} B")
        out["layer_ctx"] = {
            "trace": red, "sizes": sizes, "job": job, "chips": len(devices),
            "peaks": peaks_mod.peaks_for(devices[0].device_kind),
            "steps": n, "history": window,
            "counts": ref.count_module(), "log": log}

    # -- free the program's state, then the reference ---------------------
    for leaf in jax.tree_util.tree_leaves(state):
        leaf.delete()
    del loop, state
    gc.collect()
    t_ref = time.perf_counter()
    ref_out = ref.train(sizes, o, key, [make_batch(i) for i in
                                        range(n_check)], devices=devices)
    ref_out["grad_norms"] = compare.flatten(ref_out["grad_norms"])
    ref_out["change_norms"] = compare.flatten(ref_out["change_norms"])
    log(f"reference: {time.perf_counter() - t_ref:.3f} s, losses "
        f"{ref_out['losses']}")
    read = compare.readings(prog, ref_out)
    for name, (value, where) in read.items():
        if name not in cell.limits:
            log(f"reading {name} = {value!r} (not compared; worst at "
                f"{where})")
    out["correct"], out["checks"] = compare.judge(read, cell.limits)
    return out
