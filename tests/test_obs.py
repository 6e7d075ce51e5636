"""Numerics telemetry: per-site FP8 health metrics, sinks, s2fp8-doctor.

Covers the ISSUE-7 acceptance criteria:
  * health metrics ride the StatsBank refresh ``lax.cond`` — a
    telemetry-on banked train step runs the SAME number of reductions
    outside cond branches as the fp32 baseline + 1 (jaxpr-asserted; the
    zero-steady-state-reduction invariant is untouched);
  * the trainer drains TelemetryState host-side through ``io_callback``
    into pluggable sinks, covering every direction of a payload-GEMM
    node with correct staleness;
  * the TrainLoop watchdog trips on a deliberately slow step and the
    event lands in the sink;
  * a telemetry-enabled bank checkpoint round-trips bit-exactly
    (compress=True included — telemetry leaves are 0/1-D, kept raw);
  * 8-device mesh telemetry equals the 1-device run bitwise on the
    order-exact toy (subprocess, slow lane);
  * the doctor flags a saturating site (sat_frac > 0, e4m3 -> e5m2
    recommendation) and reports a healthy probe clean.
"""
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mesh_toy
from repro import obs
from repro.checkpoint.manager import CheckpointManager
from repro.core import statsbank
from repro.core.policy import make_policy
from repro.obs import doctor as obs_doctor
from repro.obs import metrics as obs_metrics
from repro.obs import sinks as obs_sinks
from repro.obs import spans
from repro.optim import optimizers, schedules
from repro.training import fault
from repro.training.trainer import TrainLoop, make_train_step

jax.config.update("jax_platform_name", "cpu")

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")
_TESTS = os.path.dirname(__file__)


# ---------------------------------------------------------------------------
# metric math: health_update via refresh_state
# ---------------------------------------------------------------------------

def test_refresh_computes_health_metrics():
    x = jax.random.normal(jax.random.PRNGKey(0), (4096,)) * 1e-3
    st = statsbank.init_site_state(telemetry=True)
    assert obs_metrics.has_telemetry(st)
    st1 = statsbank.refresh_state(x, st, jnp.float32(0.0), backend="ref",
                                  fmt="e5m2")
    # bootstrap refresh measures with the FRESH stats: no saturation, no
    # drift (nothing carried), healthy SNR; a few percent of low-tail
    # flush is intrinsic S2FP8 behavior on Gaussian data
    assert float(st1["sat_frac"]) == 0.0
    assert float(st1["drift_mu"]) == 0.0
    assert float(st1["drift_m"]) == 0.0
    assert float(st1["qsnr_db"]) > 10.0
    assert 0.0 <= float(st1["uflow_frac"]) < obs_doctor.UFLOW_THRESH
    assert float(st1["qmse"]) >= 0.0
    # second refresh fed a 2^12x hotter tensor: the metrics measure with
    # the CARRIED pair (what recent steps actually truncated with), so
    # saturation and moment drift must show — while the refreshed
    # (alpha, beta) themselves are the fresh, non-saturating ones
    st2 = statsbank.refresh_state(x * jnp.float32(2.0 ** 12), st1,
                                  jnp.float32(1.0), backend="ref",
                                  fmt="e5m2")
    assert float(st2["sat_frac"]) > 0.0
    assert float(st2["drift_mu"]) > 0.0
    assert float(st2["last"]) == 1.0


def test_ensure_and_strip_telemetry_roundtrip():
    plain = {"s": {"fwd": statsbank.init_site_state(),
                   "bwd": statsbank.init_site_state(length=3)}}
    wide = obs_metrics.ensure_telemetry(plain)
    for d in ("fwd", "bwd"):
        assert obs_metrics.has_telemetry(wide["s"][d])
    assert wide["s"]["bwd"]["sat_frac"].shape == (3,)
    # idempotent, and strip restores the five-leaf layout exactly
    assert obs_metrics.ensure_telemetry(wide)["s"]["fwd"].keys() == \
        wide["s"]["fwd"].keys()
    back = obs_metrics.strip_telemetry(wide)
    assert sorted(back["s"]["fwd"]) == sorted(statsbank.STATE_FIELDS)


def test_resolve_fmt():
    assert obs_metrics.resolve_fmt("e4m3", 15.0) == "e4m3"
    assert obs_metrics.resolve_fmt(None, 8.0) == "e4m3"
    assert obs_metrics.resolve_fmt(None, 15.0) == "e5m2"
    assert obs_metrics.resolve_fmt(None, 12.345) == "e5m2"


# ---------------------------------------------------------------------------
# acceptance: telemetry adds ZERO reductions outside lax.cond
# ---------------------------------------------------------------------------

def test_telemetry_zero_steady_state_reductions():
    params = mesh_toy.make_params()
    batch = mesh_toy.make_batch(0)
    opt = optimizers.adamw()
    sched = schedules.constant(1e-3)
    ost = opt.init(params)
    pol = make_policy("s2fp8_e4m3", gemm_mode="payload")

    def banked_jaxpr(cfg_s):
        bank = statsbank.init_bank(mesh_toy.loss_fn, params, batch, pol,
                                   cfg_s)
        return jax.make_jaxpr(
            make_train_step(mesh_toy.loss_fn, opt, sched, pol,
                            stats=cfg_s))(params, ost, bank, batch,
                                          jnp.int32(0))

    jx_fp32 = jax.make_jaxpr(
        make_train_step(mesh_toy.loss_fn, opt, sched,
                        make_policy("fp32")))(params, ost, batch,
                                              jnp.int32(0))
    jx_bank = banked_jaxpr(statsbank.StatsConfig(refresh_every=4))
    jx_tele = banked_jaxpr(statsbank.StatsConfig(refresh_every=4,
                                                 telemetry=True))

    n_fp32 = statsbank.count_reductions(jx_fp32, include_cond=False)
    n_bank = statsbank.count_reductions(jx_bank, include_cond=False)
    n_tele = statsbank.count_reductions(jx_tele, include_cond=False)
    # telemetry on == telemetry off outside cond branches: the fp32
    # baseline plus the single O(n_sites) bookkeeping min, nothing more
    assert n_tele == n_fp32 + 1, (n_tele, n_fp32)
    assert n_tele == n_bank, (n_tele, n_bank)
    # ... and the metric reductions DO exist, inside the cond branches
    n_bank_all = statsbank.count_reductions(jx_bank, include_cond=True)
    n_tele_all = statsbank.count_reductions(jx_tele, include_cond=True)
    assert n_tele_all > n_bank_all, (n_tele_all, n_bank_all)


# ---------------------------------------------------------------------------
# trainer drain: io_callback -> Telemetry -> MemorySink
# ---------------------------------------------------------------------------

def test_train_step_drains_telemetry_to_sink():
    sink = obs_sinks.MemorySink()
    pol = make_policy("s2fp8_e4m3", gemm_mode="payload")
    params = mesh_toy.make_params()
    opt = optimizers.adamw()
    cfg = statsbank.StatsConfig(refresh_every=2, telemetry=True)
    bank = statsbank.init_bank(mesh_toy.loss_fn, params,
                               mesh_toy.make_batch(0), pol, cfg)
    step = jax.jit(make_train_step(mesh_toy.loss_fn, opt,
                                   schedules.constant(1e-3), pol,
                                   stats=cfg,
                                   telemetry=obs.Telemetry(sink, every=1)))
    p, st = params, opt.init(params)
    for s in range(4):
        p, st, bank, m = step(p, st, bank, mesh_toy.make_batch(s),
                              jnp.int32(s))
    jax.block_until_ready((p, m))
    jax.effects_barrier()

    recs = sink.by_kind("site_health")
    assert recs, "telemetry drain emitted nothing"
    # every direction of the toy's single payload-GEMM node drains
    site = recs[0]["site"]
    assert {r["dir"] for r in recs if r["site"] == site} == \
        set(statsbank.GEMM_DIRS)
    # staleness tracks steps-since-refresh: refresh_every=2 => the step-3
    # snapshot is 1 step past the step-2 refresh
    last = [r for r in recs if r["step"] == 3]
    assert last and all(r["staleness"] == 1.0 for r in last), last
    for r in recs:
        assert set(obs_metrics.TELE_FIELDS) <= set(r), sorted(r)


def test_telemetry_requires_stats():
    opt = optimizers.adamw()
    with pytest.raises(ValueError, match="telemetry requires"):
        make_train_step(mesh_toy.loss_fn, opt, schedules.constant(1e-3),
                        make_policy("s2fp8"),
                        telemetry=obs.Telemetry(obs_sinks.NullSink()))
    with pytest.raises(ValueError):
        obs.Telemetry(obs_sinks.NullSink(), every=0)


# ---------------------------------------------------------------------------
# watchdog: unit + TrainLoop trip through a deliberately slow step
# ---------------------------------------------------------------------------

def test_watchdog_unit():
    with pytest.raises(ValueError):
        fault.Watchdog(factor=0.0)
    wd = fault.Watchdog(factor=2.0, min_history=4)
    # spikes before min_history accumulate silently
    assert wd.observe(0, 10.0) is None
    for s in range(1, 5):
        assert wd.observe(s, 0.1) is None
    ev = wd.observe(5, 0.5)
    assert ev is not None
    assert ev["step"] == 5 and ev["dt_s"] == 0.5
    assert ev["median_s"] == pytest.approx(0.1)
    assert wd.events == [ev]
    # back to baseline: no trip
    assert wd.observe(6, 0.1) is None


def test_trainloop_watchdog_flags_slow_step():
    from jax.experimental import io_callback
    SLOW_STEP = 10

    def host_pause(step):
        if int(step) == SLOW_STEP:
            time.sleep(0.3)
        return np.float32(0.0)

    def train_step(params, opt_state, batch, step):
        # the pause's output feeds the loss so block_until_ready in the
        # loop's span timing cannot complete before the sleep does
        z = io_callback(host_pause, jax.ShapeDtypeStruct((), jnp.float32),
                        step, ordered=True)
        return params, opt_state, {"loss": jnp.float32(1.0) + z,
                                   "lr": jnp.float32(1e-3)}

    sink = obs_sinks.MemorySink()
    loop = TrainLoop(train_step, {"w": jnp.zeros((4,))},
                     {"m": jnp.zeros((4,))},
                     lambda s: {"x": jnp.zeros((2,))},
                     log_every=0, watchdog_factor=3.0, sink=sink)
    loop.run(SLOW_STEP + 2)
    trips = [r for r in sink.by_kind("event") if r["event"] == "watchdog"]
    assert trips, sink.records
    assert trips[0]["step"] == SLOW_STEP
    assert trips[0]["dt_s"] > 3.0 * trips[0]["median_s"]


def test_trainloop_emits_spans_and_checkpoint_events(tmp_path):
    def train_step(params, opt_state, batch, step):
        return params, opt_state, {"loss": jnp.float32(1.0),
                                   "lr": jnp.float32(1e-3)}

    sink = obs_sinks.MemorySink()
    ck = CheckpointManager(str(tmp_path))
    spans.RECORDER.reset()
    loop = TrainLoop(train_step, {"w": jnp.zeros((4,))},
                     {"m": jnp.zeros((4,))},
                     lambda s: {"x": jnp.zeros((2,))},
                     ckpt_manager=ck, ckpt_every=2, log_every=1, sink=sink)
    loop.run(4)
    steps = sink.by_kind("train_step")
    assert [r["step"] for r in steps] == [0, 1, 2, 3]
    for r in steps:
        for k in ("loss", "lr", "data_ms", "step_ms", "ckpt_ms",
                  "refreshed"):
            assert k in r, (k, r)
        assert "refresh_ms" not in r
        assert r["refreshed"] is False          # no StatsBank
        assert r["step_ms"] > 0.0 and r["data_ms"] > 0.0
    # the record's times are the durations of the step's spans
    rec = spans.RECORDER.spans
    for key, name in (("data_ms", spans.TRAIN_DATA),
                      ("step_ms", spans.TRAIN_STEP),
                      ("ckpt_ms", spans.TRAIN_CKPT)):
        assert sum(r[key] for r in steps) == pytest.approx(
            rec[name].total_ns * 1e-6)
    assert rec[spans.TRAIN_STEP].count == 4
    assert rec[spans.TRAIN_CKPT].count == 2     # after steps 1 and 3
    assert [r["ckpt_ms"] > 0.0 for r in steps] == [False, True, False, True]
    saves = [r for r in sink.by_kind("event")
             if r["event"] == "checkpoint_saved"]
    assert [r["step"] for r in saves] == [2, 4]
    assert all("write_s" in r for r in saves)
    assert all(r["blocking_s"] * 1e3 == pytest.approx(s["ckpt_ms"])
               for r, s in zip(saves, steps[1::2]))
    # the recorder's aggregates close the run
    assert {r["name"] for r in sink.by_kind("span")} >= {
        spans.TRAIN_DATA, spans.TRAIN_STEP, spans.TRAIN_CKPT}
    assert {(r["fun_name"], r["phase"]) for r in sink.by_kind("compile")} >= {
        ("train_step", spans.TRACE), ("train_step", spans.LOWER),
        ("train_step", spans.COMPILE)}


def test_train_launcher_console_marks_refresh_steps(capsys):
    from repro.configs import get_reduced_config
    from repro.launch import train as launch
    args = launch.build_parser().parse_args(
        ["--arch", "minicpm_2b", "--reduced", "--steps", "11", "--batch",
         "2", "--seq", "8", "--policy", "s2fp8", "--backend", "ref",
         "--stats-refresh-every", "4"])
    cfg = get_reduced_config("minicpm_2b").replace(n_layers=1, vocab=64)
    launch.train(cfg, args)
    lines = [line for line in capsys.readouterr().out.splitlines()
             if line.startswith("step ")]
    # TrainLoop logs every 10th step: step 0 refreshes, step 10 does not
    assert [line.split()[1] for line in lines] == ["0", "10"]
    assert lines[0].endswith(" refreshed"), lines
    assert not lines[1].endswith(" refreshed"), lines


# ---------------------------------------------------------------------------
# sinks
# ---------------------------------------------------------------------------

def test_jsonl_sink_writes_parseable_lines(tmp_path):
    path = str(tmp_path / "m.jsonl")
    s = obs_sinks.JsonlSink(path)
    s.emit({"kind": "train_step", "step": 0, "loss": np.float32(1.5)})
    s.emit({"kind": "site_health", "step": 0, "site": "a",
            "sat_frac": jnp.float32(0.25)})
    s.close()
    with open(path) as f:
        recs = [json.loads(l) for l in f]
    assert recs[0]["loss"] == 1.5 and isinstance(recs[0]["loss"], float)
    assert recs[1]["sat_frac"] == 0.25


def test_csv_sink_unions_headers(tmp_path):
    path = str(tmp_path / "m.csv")
    s = obs_sinks.CsvSink(path)
    s.emit({"kind": "train_step", "step": 0, "loss": 1.0})
    s.emit({"kind": "site_health", "step": 0, "site": "a", "sat_frac": 0.0})
    s.close()
    with open(path) as f:
        header = f.readline().strip().split(",")
    assert set(header) >= {"kind", "step", "loss", "site", "sat_frac"}


def test_console_sink_reproduces_legacy_lines():
    lines = []
    s = obs_sinks.ConsoleSink(lines.append)
    s.emit({"kind": "train_step", "step": 7, "loss": 1.2345, "lr": 3e-3,
            "step_ms": 12.0})
    s.emit({"kind": "event", "event": "watchdog", "step": 9, "dt_s": 1.0,
            "median_s": 0.1, "factor": 3.0})
    s.emit({"kind": "site_health", "step": 4, "site": "s", "dir": "a.fwd",
            "layer": None, "sat_frac": 0.5, "uflow_frac": 0.0,
            "qsnr_db": 20.0, "staleness": 2.0})
    assert lines[0] == "step     7 loss 1.2345 lr 3.00e-03 t 12ms"
    assert "straggler suspected" in lines[1]
    assert lines[2].startswith("[obs] step 4 s.a.fwd sat 0.500")


def test_make_sink_parses_specs(tmp_path):
    assert isinstance(obs.make_sink(None), obs_sinks.NullSink)
    assert isinstance(obs.make_sink("null"), obs_sinks.NullSink)
    assert isinstance(obs.make_sink("console"), obs_sinks.ConsoleSink)
    assert isinstance(obs.make_sink("memory"), obs_sinks.MemorySink)
    j = obs.make_sink(f"jsonl:{tmp_path}/a.jsonl")
    assert isinstance(j, obs_sinks.JsonlSink)
    j.close()
    assert isinstance(obs.make_sink(f"csv:{tmp_path}/a.csv"),
                      obs_sinks.CsvSink)
    with pytest.raises(ValueError, match="unknown metrics sink"):
        obs.make_sink("protobuf:/tmp/x")


def test_tee_sink_fans_out():
    a, b = obs_sinks.MemorySink(), obs_sinks.MemorySink()
    t = obs_sinks.TeeSink(a, b)
    t.emit({"kind": "event", "event": "x"})
    t.close()
    assert a.records == b.records and len(a.records) == 1


# ---------------------------------------------------------------------------
# telemetry bank checkpoint round-trip (compress=True keeps 0/1-D raw)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("compress", [False, True])
def test_telemetry_bank_checkpoint_roundtrip(tmp_path, compress):
    pol = make_policy("s2fp8_e4m3", gemm_mode="payload")
    params = mesh_toy.make_params()
    opt = optimizers.adamw()
    cfg = statsbank.StatsConfig(refresh_every=2, telemetry=True)
    bank = statsbank.init_bank(mesh_toy.loss_fn, params,
                               mesh_toy.make_batch(0), pol, cfg)
    step = jax.jit(make_train_step(mesh_toy.loss_fn, opt,
                                   schedules.constant(1e-3), pol,
                                   stats=cfg))
    p, st = params, opt.init(params)
    for s in range(3):
        p, st, bank, _ = step(p, st, bank, mesh_toy.make_batch(s),
                              jnp.int32(s))

    ck = CheckpointManager(str(tmp_path), compress=compress)
    ck.save(3, (p, st, bank))
    template = jax.tree_util.tree_map(jnp.zeros_like, (p, st, bank))
    (rp, rst, rbank), _ = ck.restore(template)
    for a, b in zip(jax.tree_util.tree_leaves(bank),
                    jax.tree_util.tree_leaves(rbank)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    site = next(iter(rbank))
    assert obs_metrics.has_telemetry(rbank[site][next(iter(rbank[site]))])


# ---------------------------------------------------------------------------
# doctor: saturating site flagged, healthy probe clean
# ---------------------------------------------------------------------------

def _toy_loss(p, b, pol):
    return jnp.sum(pol.dot(b, p["w"]) ** 2), {}


def test_doctor_flags_saturating_site():
    pol = make_policy("s2fp8_e4m3", backend="ref", gemm_mode="fig4")
    params = {"w": jax.random.normal(jax.random.PRNGKey(1), (16, 8),
                                     jnp.float32) * 0.1}
    batch = jax.random.normal(jax.random.PRNGKey(2), (8, 16), jnp.float32)
    cfg = statsbank.StatsConfig(refresh_every=16)
    bank = statsbank.init_bank(_toy_loss, params, batch, pol, cfg)

    # healthy probe: warm the cold bank on an in-range batch -> clean
    warm, loss = obs_doctor.probe_bank(_toy_loss, params, batch, pol,
                                       bank, cfg, step=0)
    rows = obs_doctor.site_report(warm, step=0, refresh_every=16)
    assert rows
    assert all(obs_doctor.is_clean(r) for r in rows), rows
    assert all(r["recommend"] == "e4m3" for r in rows)
    assert np.isfinite(loss)

    # probe the warm bank with a 2^12x hotter batch: the carried stats
    # must report saturation and the rec must flip e4m3 -> e5m2
    hot, _ = obs_doctor.probe_bank(_toy_loss, params,
                                   batch * jnp.float32(2.0 ** 12), pol,
                                   warm, cfg, step=1)
    rows = obs_doctor.site_report(hot, step=1, refresh_every=16)
    worst = rows[0]
    assert worst["sat_frac"] > 0.0, worst
    assert "SAT" in worst["flags"]
    assert worst["recommend"] == "e5m2"
    assert not obs_doctor.is_clean(worst)
    report = obs_doctor.format_report(rows, backend="ref", loss=1.0)
    assert "verdict: worst site" in report and "SAT" in report


def test_recommend_fmt_rule():
    base = {"sat_frac": 0.0, "uflow_frac": 0.0}
    assert obs_doctor.recommend_fmt(base)[0] == "e4m3"
    assert obs_doctor.recommend_fmt({**base, "sat_frac": 0.01})[0] == "e5m2"
    assert obs_doctor.recommend_fmt(
        {**base, "uflow_frac": obs_doctor.UFLOW_THRESH + 0.01})[0] == "e5m2"


def test_doctor_probes_checkpointless_cold_bank(tmp_path):
    # the CLI path with no checkpoint: cold bank -> COLD is informational,
    # report still clean; exercises the full run() wiring cheaply via the
    # library (the CLI smoke runs in CI as `s2fp8-doctor --smoke`)
    pol = make_policy("s2fp8_e4m3", backend="ref", gemm_mode="fig4")
    params = {"w": jnp.ones((4, 4), jnp.float32) * 0.5}
    batch = jnp.ones((4, 4), jnp.float32)
    cfg = statsbank.StatsConfig(refresh_every=8)
    bank = statsbank.init_bank(_toy_loss, params, batch, pol, cfg)
    probed, _ = obs_doctor.probe_bank(_toy_loss, params, batch, pol, bank,
                                      cfg, step=0)
    rows = obs_doctor.site_report(probed, step=0, refresh_every=8)
    assert rows and all(obs_doctor.is_clean(r) for r in rows)


# ---------------------------------------------------------------------------
# 8-device mesh telemetry == 1-device, bitwise (order-exact toy)
# ---------------------------------------------------------------------------

_TELE_MESH_SCRIPT = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
import numpy as np
import mesh_toy
from repro import obs
from repro.core import statsbank
from repro.core.policy import make_policy
from repro.obs import telemetry as obs_telemetry
from repro.optim import optimizers, schedules
from repro.training.trainer import make_train_step

mesh = jax.make_mesh((8, 1), ("data", "model"))
s8, p8, o8, b8, _ = mesh_toy.setup(mesh=mesh, telemetry=True)
s1, p1, o1, b1, _ = mesh_toy.setup(mesh=None, telemetry=True)
r8 = mesh_toy.run(s8, p8, o8, b8, 4)
r1 = mesh_toy.run(s1, p1, o1, b1, 4)
t8 = obs_telemetry.telemetry_state(r8[2], 4)
t1 = obs_telemetry.telemetry_state(r1[2], 4)
l8 = jax.tree_util.tree_leaves_with_path(t8)
l1 = jax.tree_util.tree_leaves_with_path(t1)
out = {"n_sites": len(t8),
       "same_structure": [str(p) for p, _ in l8] == [str(p) for p, _ in l1],
       "bitwise": all(np.array_equal(np.asarray(a), np.asarray(b),
                                     equal_nan=True)
                      for (_, a), (_, b) in zip(l8, l1))}

# io_callback drain through the sharded step: the callback is pinned to
# one device (regression: an unplaced callback in an 8-device program
# trips XLA sharding propagation) and each step emits exactly once
sink = obs.MemorySink()
pol = make_policy("s2fp8_e4m3", gemm_mode="payload")
params = mesh_toy.make_params()
opt = optimizers.adamw()
cfg = statsbank.StatsConfig(refresh_every=2, telemetry=True)
bank = statsbank.init_bank(mesh_toy.loss_fn, params, mesh_toy.make_batch(0),
                           pol, cfg)
step = jax.jit(make_train_step(mesh_toy.loss_fn, opt,
                               schedules.constant(1e-3), pol, stats=cfg,
                               mesh=mesh, telemetry=obs.Telemetry(sink)))
p, st = params, opt.init(params)
for s in range(3):
    p, st, bank, m = step(p, st, bank, mesh_toy.make_batch(s), jnp.int32(s))
jax.block_until_ready((p, m))
jax.effects_barrier()
recs = sink.by_kind("site_health")
per_key = {}
for r in recs:
    k = (r["step"], r["site"], r["dir"])
    per_key[k] = per_key.get(k, 0) + 1
out["drain_records"] = len(recs)
out["drain_steps"] = sorted({r["step"] for r in recs})
out["drain_once_per_step"] = all(v == 1 for v in per_key.values())
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_mesh_telemetry_matches_single_device_bitwise():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join([_SRC, _TESTS])
    proc = subprocess.run([sys.executable, "-c", _TELE_MESH_SCRIPT],
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT ")][0]
    out = json.loads(line[len("RESULT "):])
    assert out["n_sites"] >= 1
    assert out["same_structure"] is True
    assert out["bitwise"] is True, out
    # pinned io_callback drain: every step ships each (site, dir) record
    # exactly once despite the 8-device program
    assert out["drain_steps"] == [0, 1, 2]
    assert out["drain_records"] == 3 * 6
    assert out["drain_once_per_step"] is True
