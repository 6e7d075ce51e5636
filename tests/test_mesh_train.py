"""Mesh-native train step: parity, routing, jaxpr and checkpoint suite.

Covers the ISSUE 5 acceptance criteria:
  * the banked payload train step on a 1-device mesh matches the existing
    unsharded step BITWISE (toy fast lane + transformer slow lane);
  * an 8-way host mesh with f32 grad-sync matches the 1-device banked
    step bitwise (order-exact toy, tests/mesh_toy.py), incl. a sharded
    checkpoint saved on 8 devices restoring on a single device with
    bit-exact params and bank stats;
  * s2fp8 grad-sync: tolerance vs 1-device + convergence smoke
    (transformer, subprocess);
  * jaxpr asserts: steady-state sharded steps run ZERO stats reductions
    outside lax.cond, and the s2fp8 sync mode contains NO f32 psum of a
    large gradient leaf (the compressed reduce-scatter/all-gather legs
    replace it);
  * per-leaf sync routing (collectives.leaf_sync_route) and the
    psum-aware global-norm clip (1- vs N-device bitwise).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mesh_toy
from repro.core import collectives, statsbank
from repro.core.policy import make_policy
from repro.launch.mesh import make_mesh_from_spec
from repro.optim import optimizers, schedules
from repro.parallel import sharding as shd
from repro.training.trainer import make_train_step

jax.config.update("jax_platform_name", "cpu")

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")
_TESTS = os.path.dirname(__file__)


def _subprocess_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join([_SRC, _TESTS])
    return env


def _assert_trees_bitwise(a, b, msg=""):
    la = jax.tree_util.tree_leaves_with_path(a)
    lb = jax.tree_util.tree_leaves_with_path(b)
    assert len(la) == len(lb)
    for (pa, xa), (_, xb) in zip(la, lb):
        np.testing.assert_array_equal(
            np.asarray(xa), np.asarray(xb),
            err_msg=f"{msg} leaf {jax.tree_util.keystr(pa)}")


# ---------------------------------------------------------------------------
# per-leaf sync routing (the compressed_grad_sync fallback audit)
# ---------------------------------------------------------------------------

def test_leaf_sync_route_per_leaf():
    route = collectives.leaf_sync_route
    big = (1 << 16,)
    # the happy path: large float leaves compress
    assert route(big, jnp.float32, 8) == "compressed"
    assert route((256, 512), jnp.bfloat16, 8) == "compressed"
    # non-float leaves bypass compression (no log2 image; sums must be
    # exact)
    assert route(big, jnp.int32, 8) == "plain"
    assert route(big, jnp.bool_, 8) == "plain"
    # 0-d scalars bypass
    assert route((), jnp.float32, 8) == "plain"
    # below the floor: stats overhead dominates
    assert route((100,), jnp.float32, 8) == "plain"
    assert route(((1 << 16) - 8,), jnp.float32, 8) == "plain"
    # length not divisible by the axis: tiled scatter/gather need equal
    # shards
    assert route(((1 << 16) + 1,), jnp.float32, 8) == "plain"
    # floor is configurable
    assert route((128,), jnp.float32, 8, min_size=64) == "compressed"


def test_compressed_grad_sync_routes_int_leaves_plain():
    """An integer leaf large enough to compress must still take the exact
    psum path (end-to-end, 1-device mesh: the sync is then an identity
    mean and must return the leaf bit-exactly, which the lossy S2FP8
    round-trip would not)."""
    mesh = jax.make_mesh((1,), ("data",))
    g = {"counts": jnp.arange(1 << 16, dtype=jnp.int32),
         "big": jnp.linspace(-1.0, 1.0, 1 << 16, dtype=jnp.float32)}
    out = collectives.compressed_grad_sync(g, mesh, "data")
    assert out["counts"].dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out["counts"]),
                                  np.asarray(g["counts"]))
    # the float leaf DID take the compressed path: S2FP8 round-trip error
    assert not np.array_equal(np.asarray(out["big"]), np.asarray(g["big"]))


# ---------------------------------------------------------------------------
# spec resolution
# ---------------------------------------------------------------------------

class _StubMesh:
    def __init__(self, axes, sizes):
        self.axis_names = axes
        self.shape = sizes


def test_mesh_batch_axes_and_specs():
    host = _StubMesh(("data", "model"), {"data": 8, "model": 1})
    pod = _StubMesh(("pod", "data", "model"),
                    {"pod": 2, "data": 8, "model": 16})
    assert shd.mesh_batch_axes(host) == ("data",)
    assert shd.mesh_batch_axes(pod) == ("pod", "data")
    assert shd.mesh_batch_size(pod) == 16

    from jax.sharding import PartitionSpec as P
    batch = {"tokens": jax.ShapeDtypeStruct((16, 64), jnp.int32),
             "labels": jax.ShapeDtypeStruct((16,), jnp.int32),
             "scalar": jax.ShapeDtypeStruct((), jnp.float32)}
    specs = shd.mesh_batch_specs(batch, host)
    assert specs["tokens"] == P("data")
    assert specs["labels"] == P("data")
    assert specs["scalar"] == P()
    assert shd.mesh_batch_specs(batch, pod)["tokens"] == P(("pod", "data"))
    # the divisibility guard is ALL-OR-NOTHING: one ragged leaf replicates
    # the whole batch (per-leaf guarding would pair a sharded leaf's shard
    # with another leaf's full batch inside the body)
    ragged = dict(batch, odd=jax.ShapeDtypeStruct((6, 4), jnp.float32))
    specs_r = shd.mesh_batch_specs(ragged, host)
    assert all(s == P() for s in specs_r.values()), specs_r


def test_statsbank_for_mesh():
    cfg = statsbank.StatsConfig(refresh_every=4)
    assert statsbank.for_mesh(cfg, None).axis_name is None
    host = _StubMesh(("data", "model"), {"data": 8, "model": 1})
    assert statsbank.for_mesh(cfg, host).axis_name == "data"
    pod = _StubMesh(("pod", "data", "model"),
                    {"pod": 2, "data": 8, "model": 16})
    assert statsbank.for_mesh(cfg, pod).axis_name == ("pod", "data")
    nobatch = _StubMesh(("model",), {"model": 4})
    assert statsbank.for_mesh(cfg, nobatch).axis_name is None


def test_make_mesh_from_spec():
    mesh = make_mesh_from_spec("1x1")
    assert mesh.axis_names == ("data", "model")
    with pytest.raises(ValueError, match="mesh spec"):
        make_mesh_from_spec("abc")
    with pytest.raises(ValueError, match="factors"):
        make_mesh_from_spec("1x1x1x1")


def test_make_train_step_validations():
    pol = make_policy("fp32")
    opt = optimizers.adamw()
    sched = schedules.constant(1e-3)
    with pytest.raises(ValueError, match="grad_sync_mode"):
        make_train_step(mesh_toy.loss_fn, opt, sched, pol,
                        grad_sync_mode="bf16")
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="grad_sync"):
        make_train_step(mesh_toy.loss_fn, opt, sched, pol, mesh=mesh,
                        grad_sync=lambda g: g)


# ---------------------------------------------------------------------------
# 1-device mesh == unsharded, bitwise (fast toy lane)
# ---------------------------------------------------------------------------

def test_mesh1_toy_matches_unsharded_bitwise():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    sm, pm, om, bm, _ = mesh_toy.setup(mesh=mesh)
    s0, p0, o0, b0, _ = mesh_toy.setup(mesh=None)
    rm = mesh_toy.run(sm, pm, om, bm, 4)
    r0 = mesh_toy.run(s0, p0, o0, b0, 4)
    _assert_trees_bitwise(rm[:3], r0[:3], "mesh1-vs-unsharded")
    assert float(rm[3]["loss"]) == float(r0[3]["loss"])


@pytest.mark.slow
def test_mesh1_transformer_banked_payload_bitwise():
    """The real model: banked payload train step on a 1-device mesh vs
    the existing unsharded step, bit for bit (params, opt state, bank)."""
    from repro.configs import get_reduced_config
    from repro.data import synthetic
    from repro.models import transformer as tlm

    cfg = get_reduced_config("minicpm_2b").replace(
        n_layers=2, remat=False, vocab=64)
    pol = make_policy("s2fp8", gemm_mode="payload")
    params = tlm.init_lm(cfg, jax.random.PRNGKey(0))
    opt = optimizers.adamw()
    sched = schedules.constant(3e-3)
    table = synthetic.make_markov_table(0, cfg.vocab)

    def loss_fn(p, b, pol_):
        return tlm.loss_fn(p, b["tokens"], b["labels"], cfg, pol_)

    def data_fn(s):
        return synthetic.lm_batch(0, s, 8, 64, cfg.vocab, table)

    scfg = statsbank.StatsConfig(refresh_every=4)
    bank = statsbank.init_bank(loss_fn, params, data_fn(0), pol, scfg)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    step_plain = jax.jit(make_train_step(loss_fn, opt, sched, pol,
                                         stats=scfg))
    step_mesh = jax.jit(make_train_step(loss_fn, opt, sched, pol,
                                        stats=scfg, mesh=mesh))
    p1, s1, b1 = params, opt.init(params), bank
    p2, s2, b2 = params, opt.init(params), bank
    for s in range(3):
        batch = data_fn(s)
        p1, s1, b1, m1 = step_plain(p1, s1, b1, batch, jnp.int32(s))
        p2, s2, b2, m2 = step_mesh(p2, s2, b2, batch, jnp.int32(s))
    _assert_trees_bitwise((p1, s1, b1), (p2, s2, b2), "transformer-mesh1")
    assert float(m1["loss"]) == float(m2["loss"])


# ---------------------------------------------------------------------------
# jaxpr structure asserts
# ---------------------------------------------------------------------------

def _collect_eqns(jaxpr, out):
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        out.append(eqn)
        for pv in eqn.params.values():
            for sub in statsbank._extract_jaxprs(pv):
                _collect_eqns(sub, out)
    return out


def _toy_sharded_jaxpr(mesh, policy, stats_cfg, grad_sync_mode="f32",
                       min_size=1 << 16, param_sharding="replicated"):
    opt = optimizers.adamw()
    params = mesh_toy.make_params()
    args = [params, opt.init(params)]
    if stats_cfg is not None:
        args.append(statsbank.init_bank(mesh_toy.loss_fn, params,
                                        mesh_toy.make_batch(0), policy,
                                        stats_cfg))
    args += [mesh_toy.make_batch(0), jnp.int32(1)]
    step = make_train_step(mesh_toy.loss_fn, opt, schedules.constant(1e-3),
                           policy, stats=stats_cfg, mesh=mesh,
                           grad_sync_mode=grad_sync_mode,
                           grad_sync_min_size=min_size,
                           param_sharding=param_sharding)
    return jax.make_jaxpr(step)(*args)


def test_sharded_steady_state_runs_zero_stats_reductions():
    """The banked SHARDED step keeps every Eq. 3-4 reduction inside
    lax.cond: outside cond it runs exactly the reductions of the sharded
    fp32 baseline plus the one O(n_sites) bookkeeping min."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    pol = make_policy("s2fp8_e4m3", gemm_mode="payload")
    scfg = statsbank.StatsConfig(refresh_every=64)
    jx_bank = _toy_sharded_jaxpr(mesh, pol, scfg)
    jx_fp32 = _toy_sharded_jaxpr(mesh, make_policy("fp32"), None)
    n_bank = statsbank.count_reductions(jx_bank, include_cond=False)
    n_bank_all = statsbank.count_reductions(jx_bank, include_cond=True)
    n_fp32 = statsbank.count_reductions(jx_fp32, include_cond=False)
    assert n_bank == n_fp32 + 1, (n_bank, n_fp32)
    assert n_bank_all > n_bank, (n_bank_all, n_bank)


def test_s2fp8_sync_has_no_large_f32_allreduce():
    """Acceptance jaxpr assert: in s2fp8 grad-sync mode the program
    contains NO f32 psum of a compressible-size gradient leaf — the
    compressed reduce-scatter (bf16) + all-gather (1-byte payload) legs
    carry them instead.  f32 mode shows the large psum."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    pol = make_policy("s2fp8_e4m3", gemm_mode="payload")
    scfg = statsbank.StatsConfig(refresh_every=64)
    min_size = 64                        # toy grad leaf is 8x16 = 128

    def summarize(jx):
        eqns = _collect_eqns(jx, [])
        large_f32_psum = [e for e in eqns if e.primitive.name == "psum"
                          and any(np.prod(v.aval.shape) >= min_size
                                  and v.aval.dtype == jnp.float32
                                  for v in e.outvars)]
        names = {e.primitive.name for e in eqns}
        return large_f32_psum, names

    big_psums, names = summarize(_toy_sharded_jaxpr(
        mesh, pol, scfg, grad_sync_mode="s2fp8", min_size=min_size))
    assert not big_psums, [str(e) for e in big_psums]
    assert "reduce_scatter" in names and "all_gather" in names, names

    big_psums_f32, names_f32 = summarize(_toy_sharded_jaxpr(
        mesh, pol, scfg, grad_sync_mode="f32", min_size=min_size))
    assert big_psums_f32, "f32 mode should psum the large grad leaf"
    assert "reduce_scatter" not in names_f32, names_f32


# ---------------------------------------------------------------------------
# 8-way host mesh: f32 bitwise + sharded-checkpoint restore + psum clip
# ---------------------------------------------------------------------------

_MESH8_SCRIPT = r"""
import os, sys, json, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
import mesh_toy
from repro.checkpoint.manager import CheckpointManager
from repro.optim import optimizers

out = {}
mesh = jax.make_mesh((8, 1), ("data", "model"))

# --- 8-way f32 grad-sync vs 1-device, bitwise over 6 steps -----------------
s8, p8, o8, b8, _ = mesh_toy.setup(mesh=mesh, grad_sync_mode="f32")
s1, p1, o1, b1, _ = mesh_toy.setup(mesh=None)

pa, oa, ba = p8, o8, b8
ckdir = tempfile.mkdtemp()
ck = CheckpointManager(ckdir)
for s in range(6):
    pa, oa, ba, ma = s8(pa, oa, ba, mesh_toy.make_batch(s), jnp.int32(s))
    if s == 2:      # sharded save after 3 steps (leaves live on 8 devices)
        ck.save(3, (pa, oa, ba))
r1 = mesh_toy.run(s1, p1, o1, b1, 6)

def bitwise(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))

out["step8_vs_step1_bitwise"] = bitwise((pa, oa, ba), r1[:3])
out["loss_bitwise"] = float(ma["loss"]) == float(r1[3]["loss"])

# --- sharded checkpoint restores on ONE device, bit-exact, and continues ---
template = jax.tree_util.tree_map(np.zeros_like,
                                  jax.tree_util.tree_map(np.asarray,
                                                         (p8, o8, b8)))
(rp, ro, rb), start = ck.restore(template)
out["restore_step"] = start
# restored leaves equal the 1-device run's state after 3 steps, bit for bit
mid = mesh_toy.run(s1, p1, o1, b1, 3)
out["restored_bitwise_vs_1dev"] = bitwise((rp, ro, rb), mid[:3])
# continue UNSHARDED from the sharded checkpoint: must land on the same
# final state
cont = mesh_toy.run(s1, rp, ro, rb, 6, start=3)
out["resume_1dev_matches_8way_final"] = bitwise(cont[:3], (pa, oa, ba))

# --- psum-aware global-norm clip: 1- vs 8-device bitwise -------------------
# integer-valued grads => every sum of squares is exact => order-free
g = {"a": (jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16) - 60.0),
     "b": jnp.tile(jnp.arange(8, dtype=jnp.float32)[:, None], (1, 4)) - 3.0}
full_c, full_n = optimizers.clip_by_global_norm(g, 1.0)

def body(gl):
    c, nrm = optimizers.clip_by_global_norm(gl, 1.0, axis_name="data")
    return c, nrm[None]

sh_c, sh_n = jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                           out_specs=(P("data"), P("data")),
                           check_vma=False)(g)
out["clip_values_bitwise"] = bitwise(sh_c, full_c)
out["clip_norm_bitwise"] = bool((np.asarray(sh_n) == float(full_n)).all())
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_mesh8_f32_bitwise_ckpt_and_clip():
    proc = subprocess.run([sys.executable, "-c", _MESH8_SCRIPT],
                          env=_subprocess_env(), capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][0]
    out = json.loads(line[len("RESULT "):])
    assert out["restore_step"] == 3
    assert all(v is True or v == 3 for v in out.values()), out


# ---------------------------------------------------------------------------
# 8-way s2fp8 grad-sync: tolerance + convergence smoke (transformer)
# ---------------------------------------------------------------------------

_S2FP8_SYNC_SCRIPT = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
import numpy as np
from repro.configs import get_reduced_config
from repro.core import statsbank
from repro.core.policy import make_policy
from repro.data import synthetic
from repro.models import transformer as tlm
from repro.optim import optimizers, schedules
from repro.training.trainer import make_train_step

cfg = get_reduced_config("minicpm_2b").replace(n_layers=2, remat=False,
                                               vocab=64)
pol = make_policy("s2fp8", gemm_mode="payload")
params = tlm.init_lm(cfg, jax.random.PRNGKey(0))
opt = optimizers.adamw()
sched = schedules.constant(3e-3)
table = synthetic.make_markov_table(0, cfg.vocab)

def loss_fn(p, b, pol_):
    return tlm.loss_fn(p, b["tokens"], b["labels"], cfg, pol_)

def data_fn(s):
    return synthetic.lm_batch(0, s, 8, 64, cfg.vocab, table)

scfg = statsbank.StatsConfig(refresh_every=4)
bank = statsbank.init_bank(loss_fn, params, data_fn(0), pol, scfg)
mesh = jax.make_mesh((8, 1), ("data", "model"))

def run(step, n):
    p, o, b = params, opt.init(params), bank
    losses = []
    for s in range(n):
        p, o, b, m = step(p, o, b, data_fn(s), jnp.int32(s))
        losses.append(float(m["loss"]))
    return p, losses

# compressed sync on the 8-way mesh (floor lowered so the transformer's
# reduced-config leaves actually compress) vs the 1-device banked step
step_c = jax.jit(make_train_step(loss_fn, opt, sched, pol, stats=scfg,
                                 mesh=mesh, grad_sync_mode="s2fp8",
                                 grad_sync_min_size=1 << 10))
step_1 = jax.jit(make_train_step(loss_fn, opt, sched, pol, stats=scfg))
pc, losses_c = run(step_c, 12)
p1, losses_1 = run(step_1, 12)

rel = []
for a, b in zip(jax.tree_util.tree_leaves(pc), jax.tree_util.tree_leaves(p1)):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    denom = np.abs(b)
    nz = denom > 1e-12
    if nz.any():
        rel.append(np.median(np.abs(a - b)[nz] / denom[nz]))
out = {
    "median_param_rel": float(np.median(rel)),
    "max_leaf_median_rel": float(np.max(rel)),
    "loss_first": losses_c[0], "loss_last": losses_c[-1],
    "loss_gap_last": abs(losses_c[-1] - losses_1[-1]) / abs(losses_1[-1]),
}
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_mesh8_s2fp8_sync_tolerance_and_convergence():
    proc = subprocess.run([sys.executable, "-c", _S2FP8_SYNC_SCRIPT],
                          env=_subprocess_env(), capture_output=True,
                          text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][0]
    out = json.loads(line[len("RESULT "):])
    # compressed-sync run stays close to the f32 1-device run...
    assert out["median_param_rel"] < 0.05, out
    assert out["loss_gap_last"] < 0.15, out
    # ...and converges on its own
    assert out["loss_last"] < out["loss_first"] * 0.8, out


# ---------------------------------------------------------------------------
# Quantized FSDP (ISSUE 9): shard params/opt, stream S2FP8 payloads
# ---------------------------------------------------------------------------

def test_fsdp_leaf_eligibility_and_specs():
    from jax.sharding import PartitionSpec as P

    elig = shd.fsdp_leaf_eligible
    assert elig((8, 16), jnp.float32, 8)
    assert elig((8,), jnp.bfloat16, 4)
    assert not elig((8, 16), jnp.int32, 8)       # non-float stays replicated
    assert not elig((), jnp.float32, 8)          # scalars (opt step counter)
    assert not elig((6, 4), jnp.float32, 4)      # dim 0 not divisible
    assert elig((6, 4), jnp.float32, 1)

    host = _StubMesh(("data", "model"), {"data": 8, "model": 1})
    assert shd.fsdp_axis_entry(host) == "data"
    assert shd.fsdp_axis_size(host) == 8
    nofsdp = _StubMesh(("model",), {"model": 4})
    assert shd.fsdp_axis_entry(nofsdp) is None
    assert shd.fsdp_axis_size(nofsdp) == 1

    tree = {"w": jax.ShapeDtypeStruct((8, 16), jnp.float32),
            "bias": jax.ShapeDtypeStruct((6,), jnp.float32),
            "count": jax.ShapeDtypeStruct((), jnp.int32)}
    specs = shd.fsdp_param_specs(tree, host)
    assert specs["w"] == P("data")
    assert specs["bias"] == P()                  # 6 % 8 != 0: per-leaf guard
    assert specs["count"] == P()

    batch = {"x": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
    in_specs, out_specs = shd.train_step_specs(
        batch, host, with_stats=True, param_sharding="fsdp",
        params=tree, opt_state={"m": tree})
    assert in_specs[0]["w"] == P("data")
    assert in_specs[1]["m"]["w"] == P("data")
    assert out_specs[0]["w"] == P("data")        # params come OUT sharded
    assert out_specs[2] == P()                   # bank stays replicated
    with pytest.raises(ValueError, match="concrete params"):
        shd.train_step_specs(batch, host, param_sharding="fsdp")


def test_make_train_step_fsdp_validations():
    pol_q = make_policy("s2fp8_e4m3", gemm_mode="payload")
    opt = optimizers.adamw()
    sched = schedules.constant(1e-3)
    scfg = statsbank.StatsConfig(refresh_every=64)
    with pytest.raises(ValueError, match="param_sharding"):
        make_train_step(mesh_toy.loss_fn, opt, sched, pol_q, stats=scfg,
                        param_sharding="zero3")
    # sharded params need a mesh to shard over
    with pytest.raises(ValueError, match="mesh"):
        make_train_step(mesh_toy.loss_fn, opt, sched, pol_q, stats=scfg,
                        param_sharding="fsdp")
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    # fsdp_q streams payloads into the banked GEMMs: stats are mandatory
    with pytest.raises(ValueError, match="fsdp_q"):
        make_train_step(mesh_toy.loss_fn, opt, sched, pol_q, mesh=mesh,
                        param_sharding="fsdp_q")
    # ...and so is a payload-GEMM policy (fp32 can't even carry a bank)
    with pytest.raises(ValueError, match="s2fp8"):
        make_train_step(mesh_toy.loss_fn, opt, sched, make_policy("fp32"),
                        mesh=mesh, stats=scfg, param_sharding="fsdp_q")
    # plain fsdp (f32 gather) has no stats requirement
    make_train_step(mesh_toy.loss_fn, opt, sched, make_policy("fp32"),
                    mesh=mesh, param_sharding="fsdp")


def test_mesh1_toy_fsdp_modes_match_unsharded_bitwise():
    """Fast lane: both FSDP modes on a 1-device mesh reproduce the
    unsharded banked step bit for bit (gather/scatter are identities at
    axis size 1, and the payload round-trip is the same quantize the
    dense path runs)."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    s0, p0, o0, b0, _ = mesh_toy.setup(mesh=None)
    r0 = mesh_toy.run(s0, p0, o0, b0, 4)
    for mode in ("fsdp", "fsdp_q"):
        sm, pm, om, bm, _ = mesh_toy.setup(mesh=mesh, param_sharding=mode)
        rm = mesh_toy.run(sm, pm, om, bm, 4)
        _assert_trees_bitwise(rm[:3], r0[:3], f"{mode}-mesh1-vs-unsharded")
        assert float(rm[3]["loss"]) == float(r0[3]["loss"]), mode
    # fsdp composes with the compressed grad-sync route (trace + run)
    sc, pc, oc, bc, _ = mesh_toy.setup(mesh=mesh, grad_sync_mode="s2fp8",
                                       param_sharding="fsdp")
    rc = mesh_toy.run(sc, pc, oc, bc, 2)
    assert np.isfinite(float(rc[3]["loss"]))


def test_fsdp_steady_state_runs_zero_stats_reductions():
    """ISSUE 9 budget anchor: the fsdp_q banked step keeps the
    steady-state stats-reduction budget at the sharded fp32 baseline + 1
    — quantize-at-owner reuses the bank's cadence, and the payload
    all-gather / grad reduce-scatter legs are collectives, not
    reductions."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    pol = make_policy("s2fp8_e4m3", gemm_mode="payload")
    scfg = statsbank.StatsConfig(refresh_every=64)
    jx_q = _toy_sharded_jaxpr(mesh, pol, scfg, param_sharding="fsdp_q")
    jx_fp32 = _toy_sharded_jaxpr(mesh, make_policy("fp32"), None,
                                 param_sharding="fsdp")
    n_q = statsbank.count_reductions(jx_q, include_cond=False)
    n_fp32 = statsbank.count_reductions(jx_fp32, include_cond=False)
    assert n_q == n_fp32 + 1, (n_q, n_fp32)


def test_fsdp_q_gathers_payloads_only():
    """ISSUE 9 jaxpr anchor: in fsdp_q mode NO f32/bf16 all-gather of a
    payload-eligible param leaf exists — the only full-leaf-size gather
    moves 1-byte payloads.  Plain fsdp shows the f32 gather."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    pol = make_policy("s2fp8_e4m3", gemm_mode="payload")
    scfg = statsbank.StatsConfig(refresh_every=64)
    w = mesh_toy.make_params()["w"]
    leaf_size = int(np.prod(w.shape))            # 8*16 = 128

    def gathers(jx):
        eqns = _collect_eqns(jx, [])
        out = {"wide": [], "byte": []}
        for e in eqns:
            if e.primitive.name != "all_gather":
                continue
            for v in e.outvars:
                if int(np.prod(v.aval.shape)) < leaf_size:
                    continue
                if v.aval.dtype in (jnp.float32, jnp.bfloat16,
                                    jnp.float16):
                    out["wide"].append(e)
                elif v.aval.dtype.itemsize == 1:
                    out["byte"].append(e)
        return out

    g_q = gathers(_toy_sharded_jaxpr(mesh, pol, scfg,
                                     param_sharding="fsdp_q"))
    assert not g_q["wide"], [str(e) for e in g_q["wide"]]
    assert g_q["byte"], "fsdp_q must all-gather the 1-byte payload"

    g_f = gathers(_toy_sharded_jaxpr(mesh, pol, scfg, param_sharding="fsdp"))
    assert g_f["wide"], "plain fsdp should all-gather the f32 leaf"


def test_fsdp8_inline_bitwise_when_devices_allow():
    """Runs 8-way in the CI fsdp lane (XLA host-device override); on a
    single-device tier-1 run it degrades to the 1-device parity check."""
    n = len(jax.devices())
    n = 8 if n >= 8 else 1
    mesh = jax.make_mesh((n, 1), ("data", "model"))
    s0, p0, o0, b0, _ = mesh_toy.setup(mesh=None)
    r0 = mesh_toy.run(s0, p0, o0, b0, 4)
    for mode in ("fsdp", "fsdp_q"):
        sm, pm, om, bm, _ = mesh_toy.setup(mesh=mesh, param_sharding=mode)
        rm = mesh_toy.run(sm, pm, om, bm, 4)
        _assert_trees_bitwise(rm[:3], r0[:3], f"{mode}-mesh{n}")
        if n > 1:  # updates really ran shard-local (ZeRO-3)
            spec = rm[0]["w"].sharding.spec
            assert tuple(spec) == ("data",), spec


_FSDP8_SCRIPT = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
import numpy as np
import mesh_toy
from repro.checkpoint.manager import CheckpointManager

ckdir = os.environ["FSDP_CKDIR"]
out = {}
mesh = jax.make_mesh((8, 1), ("data", "model"))

def bitwise(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))

s1, p1, o1, b1, _ = mesh_toy.setup(mesh=None)
ref = mesh_toy.run(s1, p1, o1, b1, 6)

# --- 8-way FSDP (f32 gather): bitwise vs 1-device, sharded mid-run save ----
s8, p8, o8, b8, _ = mesh_toy.setup(mesh=mesh, param_sharding="fsdp")
pa, oa, ba = p8, o8, b8
ck = CheckpointManager(ckdir)
for s in range(6):
    pa, oa, ba, ma = s8(pa, oa, ba, mesh_toy.make_batch(s), jnp.int32(s))
    if s == 2:   # leaves live SHARDED over 8 devices at save time
        out["save_spec_is_fsdp"] = tuple(pa["w"].sharding.spec) == ("data",)
        ck.save(3, (pa, oa, ba))
out["fsdp8_bitwise"] = bitwise((pa, oa, ba), ref[:3])
out["fsdp8_loss_bitwise"] = float(ma["loss"]) == float(ref[3]["loss"])
out["out_spec_is_fsdp"] = tuple(pa["w"].sharding.spec) == ("data",)

# --- 8-way FSDP-Q (payload streaming): bitwise vs 1-device -----------------
sq, pq, oq, bq, _ = mesh_toy.setup(mesh=mesh, param_sharding="fsdp_q")
rq = mesh_toy.run(sq, pq, oq, bq, 6)
out["fsdp_q8_bitwise"] = bitwise(rq[:3], ref[:3])
out["fsdp_q8_loss_bitwise"] = float(rq[3]["loss"]) == float(ref[3]["loss"])
print("RESULT " + json.dumps(out))
"""

_FSDP_RESTORE_SCRIPT = r"""
import os, sys, json
n = int(os.environ["FSDP_DEVICES"])
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d" % n
import jax, jax.numpy as jnp
import numpy as np
import mesh_toy
from repro.checkpoint.manager import CheckpointManager

ckdir = os.environ["FSDP_CKDIR"]
out = {}

def bitwise(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))

s1, p1, o1, b1, _ = mesh_toy.setup(mesh=None)
ref6 = mesh_toy.run(s1, p1, o1, b1, 6)
ref3 = mesh_toy.run(s1, p1, o1, b1, 3)

template = jax.tree_util.tree_map(
    lambda x: np.zeros_like(np.asarray(x)), (p1, o1, b1))
(rp, ro, rb), start = CheckpointManager(ckdir).restore(template)
out["restore_step"] = start
# the 8-device sharded save restores bit-exact on this topology
out["restored_bitwise"] = bitwise((rp, ro, rb), ref3[:3])

# continue under THIS topology's FSDP mesh to step 6
if n > 1:
    mesh = jax.make_mesh((n, 1), ("data", "model"))
    sn, _, _, _, _ = mesh_toy.setup(mesh=mesh, param_sharding="fsdp")
else:
    sn = s1
cont = mesh_toy.run(sn, rp, ro, rb, 6, start=3)
out["resume_bitwise"] = bitwise(cont[:3], ref6[:3])
print("RESULT " + json.dumps(out))
"""


_FSDP_Q_TRANSFORMER_SCRIPT = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
import numpy as np
from repro.configs import get_reduced_config
from repro.core import statsbank
from repro.core.policy import make_policy
from repro.data import synthetic
from repro.models import transformer as tlm
from repro.optim import optimizers, schedules
from repro.training.trainer import make_train_step

cfg = get_reduced_config("minicpm_2b").replace(n_layers=2, remat=False,
                                               vocab=64)
pol = make_policy("s2fp8", gemm_mode="payload")
params = tlm.init_lm(cfg, jax.random.PRNGKey(0))
opt = optimizers.adamw()
sched = schedules.constant(3e-3)
table = synthetic.make_markov_table(0, cfg.vocab)

def loss_fn(p, b, pol_):
    return tlm.loss_fn(p, b["tokens"], b["labels"], cfg, pol_)

def data_fn(s):
    return synthetic.lm_batch(0, s, 8, 64, cfg.vocab, table)

scfg = statsbank.StatsConfig(refresh_every=4)
bank = statsbank.init_bank(loss_fn, params, data_fn(0), pol, scfg)
mesh = jax.make_mesh((8, 1), ("data", "model"))

def run(step, n):
    p, o, b = params, opt.init(params), bank
    losses = []
    for s in range(n):
        p, o, b, m = step(p, o, b, data_fn(s), jnp.int32(s))
        losses.append(float(m["loss"]))
    return p, losses

# the real model (tied embeddings -> .T fallback, scan-stacked ineligible
# leaves, flash attention) under 8-way fsdp_q vs the 1-device dense step:
# sharded reduce-scatter sums reorder, so tolerance not bitwise
step_q = jax.jit(make_train_step(loss_fn, opt, sched, pol, stats=scfg,
                                 mesh=mesh, param_sharding="fsdp_q"))
step_1 = jax.jit(make_train_step(loss_fn, opt, sched, pol, stats=scfg))
pq, losses_q = run(step_q, 10)
p1, losses_1 = run(step_1, 10)

# payload-eligible leaves really live sharded
emb = pq["embed"]
rel = []
for a, b in zip(jax.tree_util.tree_leaves(pq), jax.tree_util.tree_leaves(p1)):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    denom = np.abs(b)
    nz = denom > 1e-12
    if nz.any():
        rel.append(np.median(np.abs(a - b)[nz] / denom[nz]))
out = {
    "embed_sharded": tuple(emb.sharding.spec) == ("data",),
    "median_param_rel": float(np.median(rel)),
    "loss_first": losses_q[0], "loss_last": losses_q[-1],
    "loss_gap_last": abs(losses_q[-1] - losses_1[-1]) / abs(losses_1[-1]),
}
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_fsdp_q_transformer_tolerance_and_convergence():
    """The real model under 8-way fsdp_q: payload-eligible leaves stay
    sharded, the run tracks the 1-device dense step, and it converges."""
    proc = subprocess.run([sys.executable, "-c",
                           _FSDP_Q_TRANSFORMER_SCRIPT],
                          env=_subprocess_env(), capture_output=True,
                          text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][0]
    out = json.loads(line[len("RESULT "):])
    assert out["embed_sharded"] is True, out
    assert out["median_param_rel"] < 0.05, out
    assert out["loss_gap_last"] < 0.15, out
    # 10 steps on the reduced config: ~17% off the start (the 12-step
    # s2fp8-sync smoke reaches 20%; this lane's job is tracking, above)
    assert out["loss_last"] < out["loss_first"] * 0.9, out


@pytest.mark.slow
def test_fsdp8_save_restores_on_other_topologies(tmp_path):
    """ISSUE 9 acceptance: a sharded checkpoint written by an 8-device
    FSDP run restores bit-exact (params + opt + bank) on 1- and 4-device
    topologies and continues to the same final state."""
    env = _subprocess_env()
    env["FSDP_CKDIR"] = str(tmp_path)
    proc = subprocess.run([sys.executable, "-c", _FSDP8_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][0]
    out = json.loads(line[len("RESULT "):])
    assert all(v is True for v in out.values()), out

    for n in (1, 4):
        env["FSDP_DEVICES"] = str(n)
        proc = subprocess.run([sys.executable, "-c", _FSDP_RESTORE_SCRIPT],
                              env=env, capture_output=True, text=True,
                              timeout=900)
        assert proc.returncode == 0, f"n={n}: " + proc.stderr[-3000:]
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("RESULT ")][0]
        out = json.loads(line[len("RESULT "):])
        assert out["restore_step"] == 3, (n, out)
        assert out["restored_bitwise"] is True, (n, out)
        assert out["resume_bitwise"] is True, (n, out)
