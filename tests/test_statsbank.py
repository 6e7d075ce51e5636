"""StatsBank: jit-carried, sharded, checkpointable per-tensor statistics.

Covers the PR-2 acceptance criteria:
  * a jitted train step with StatsBank enabled performs ZERO stats
    reductions on non-refresh steps (jaxpr inspection: every reduction
    introduced by the numerics sits inside a ``lax.cond`` branch);
  * delayed-stats training converges within tolerance of exact-stats;
  * the bank survives a checkpoint save/restore cycle bit-exactly
    (including under compress=True) and TrainLoop resumes with warm stats;
  * global (shard_map) stats refresh matches the single-device bank
    bit-for-bit (subprocess test, power-of-two data so reductions are
    order-exact);
  * the DelayedStatsCache shim delegates to HostStatsBank and warns.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_reduced_config
from repro.core import backend as nbackend
from repro.core import collectives, s2fp8, statsbank
from repro.core.policy import make_policy
from repro.data import synthetic
from repro.models import transformer as tlm
from repro.optim import optimizers, schedules
from repro.training.trainer import TrainLoop, make_train_step

jax.config.update("jax_platform_name", "cpu")


def _tiny_setup(n_layers=2, remat=False, seed=0):
    cfg = get_reduced_config("minicpm_2b").replace(
        n_layers=n_layers, remat=remat, vocab=64)
    pol = make_policy("s2fp8")
    params = tlm.init_lm(cfg, jax.random.PRNGKey(seed))
    opt = optimizers.adamw()
    sched = schedules.constant(3e-3)
    table = synthetic.make_markov_table(seed, cfg.vocab)

    def loss_fn(p, batch, pol_):
        return tlm.loss_fn(p, batch["tokens"], batch["labels"], cfg, pol_)

    def data_fn(s):
        return synthetic.lm_batch(seed, s, 8, 64, cfg.vocab, table)

    return cfg, pol, params, opt, sched, loss_fn, data_fn


# ---------------------------------------------------------------------------
# discovery + bank structure
# ---------------------------------------------------------------------------

def test_init_bank_discovers_sites_and_stacks_segments():
    _, pol, params, _, _, loss_fn, data_fn = _tiny_setup()
    cfg_s = statsbank.StatsConfig(refresh_every=4)
    bank = statsbank.init_bank(loss_fn, params, data_fn(0), pol, cfg_s)
    # global sites are scalars; scanned-segment sites are [L]-stacked
    assert any(k.startswith("embed/") for k in bank)
    assert any(k.startswith("head/") for k in bank)
    seg_keys = [k for k in bank if k.startswith("seg0:dense/")]
    assert seg_keys, sorted(bank)
    for k in seg_keys:
        assert bank[k]["fwd"]["alpha"].shape == (2,), k
    assert bank["head/t0"]["bwd"]["last"].shape == ()
    # every entry bootstraps with identity stats and last = -1
    for entry in bank.values():
        for d in entry.values():
            assert float(jnp.min(d["last"])) == -1.0
            assert float(jnp.max(jnp.abs(d["alpha"] - 1.0))) == 0.0
    # named scopes from models/blocks.py show up in the keys
    assert any("/attn/" in k for k in seg_keys)
    assert any("/mlp/" in k for k in seg_keys)


def test_init_bank_rejects_numerics_free_policy():
    _, _, params, _, _, loss_fn, data_fn = _tiny_setup()
    with pytest.raises(ValueError, match="no truncation sites"):
        statsbank.init_bank(loss_fn, params, data_fn(0), make_policy("fp32"))


def test_make_train_step_validates_policy_mode():
    _, _, _, opt, sched, loss_fn, _ = _tiny_setup()
    with pytest.raises(ValueError, match="s2fp8"):
        make_train_step(loss_fn, opt, sched, make_policy("fp32"),
                        stats=statsbank.StatsConfig())


def test_stats_config_validation():
    with pytest.raises(ValueError):
        statsbank.StatsConfig(refresh_every=0)
    with pytest.raises(ValueError):
        statsbank.StatsConfig(ema_decay=1.0)


# ---------------------------------------------------------------------------
# delayed-stats numerics: in-jit bank vs exact stats over a convergence run
# ---------------------------------------------------------------------------

def test_bank_training_tracks_exact_stats():
    _, pol, params, opt, sched, loss_fn, data_fn = _tiny_setup()
    cfg_s = statsbank.StatsConfig(refresh_every=4)
    bank = statsbank.init_bank(loss_fn, params, data_fn(0), pol, cfg_s)

    bank_step = jax.jit(make_train_step(loss_fn, opt, sched, pol,
                                        stats=cfg_s))
    exact_step = jax.jit(make_train_step(loss_fn, opt, sched, pol))

    pb, sb = params, opt.init(params)
    pe, se = params, opt.init(params)
    lb, le = [], []
    for s in range(16):
        batch = data_fn(s)
        pb, sb, bank, mb = bank_step(pb, sb, bank, batch, jnp.int32(s))
        pe, se, me = exact_step(pe, se, batch, jnp.int32(s))
        lb.append(float(mb["loss"]))
        le.append(float(me["loss"]))
    assert all(np.isfinite(lb)), lb
    # step 0 bootstraps fresh stats (refresh-then-use): no identity-stats
    # flush-to-zero catastrophe on the first step
    assert abs(lb[0] - le[0]) / le[0] < 0.01, (lb[0], le[0])
    # training converges, and stays within tolerance of the exact run
    assert lb[-1] < lb[0] * 0.85, lb
    assert abs(lb[-1] - le[-1]) / le[-1] < 0.10, (lb[-1], le[-1])
    # bank refreshed on cadence: last-refresh of every site is step 12
    lasts = {float(jnp.max(e[d]["last"]))
             for e in bank.values() for d in e}
    assert lasts == {12.0}, lasts


def test_refresh_every_one_matches_exact_closely():
    """k=1 refreshes every step — the bank path degenerates to fresh stats
    and must sit on top of the exact-stats run.  (Tolerance, not bitwise:
    the two programs fuse the stats epilogue differently, and 1-ulp stat
    shifts move a handful of RNE roundings per step.)"""
    _, pol, params, opt, sched, loss_fn, data_fn = _tiny_setup()
    cfg_s = statsbank.StatsConfig(refresh_every=1)
    bank = statsbank.init_bank(loss_fn, params, data_fn(0), pol, cfg_s)
    bank_step = jax.jit(make_train_step(loss_fn, opt, sched, pol,
                                        stats=cfg_s))
    exact_step = jax.jit(make_train_step(loss_fn, opt, sched, pol))
    pb, sb = params, opt.init(params)
    pe, se = params, opt.init(params)
    for s in range(4):
        batch = data_fn(s)
        pb, sb, bank, mb = bank_step(pb, sb, bank, batch, jnp.int32(s))
        pe, se, me = exact_step(pe, se, batch, jnp.int32(s))
        np.testing.assert_allclose(float(mb["loss"]), float(me["loss"]),
                                   rtol=5e-3)


def test_bank_step_with_remat_and_ema():
    """scan + jax.checkpoint remat + EMA moments: the cotangent-carried
    bank composes with rematerialization."""
    _, pol, params, opt, sched, loss_fn, data_fn = _tiny_setup(remat=True)
    cfg_s = statsbank.StatsConfig(refresh_every=2, ema_decay=0.5)
    bank = statsbank.init_bank(loss_fn, params, data_fn(0), pol, cfg_s)
    step = jax.jit(make_train_step(loss_fn, opt, sched, pol, stats=cfg_s))
    p, st = params, opt.init(params)
    for s in range(5):
        p, st, bank, m = step(p, st, bank, data_fn(s), jnp.int32(s))
        assert np.isfinite(float(m["loss"])), s
    # EMA folded at least twice -> moments are mixes, last advanced
    st0 = bank["head/t0"]["fwd"]
    assert float(st0["last"]) == 4.0
    assert np.isfinite(float(st0["ema_mu"]))


def test_encdec_bank_single_step():
    """Enc-dec model: encoder scan, per-layer cross-KV map and decoder
    scan all thread their segment sites."""
    from repro.configs import get_config
    from repro.models import encdec
    cfg = get_config("transformer_tiny").replace(vocab=64)
    pol = make_policy("s2fp8")
    params = encdec.init_encdec(cfg, jax.random.PRNGKey(0))

    def loss_fn(p, b, pol_):
        return encdec.loss_fn(p, b["enc_tokens"], b["dec_tokens"],
                              b["dec_labels"], cfg, pol_)

    batch = synthetic.seq2seq_batch(0, 0, 4, 8, 8, cfg.vocab)
    cfg_s = statsbank.StatsConfig(refresh_every=4)
    bank = statsbank.init_bank(loss_fn, params, batch, pol, cfg_s)
    assert any(k.startswith("enc/") for k in bank)
    assert any(k.startswith("dec/") for k in bank)
    assert any(k.startswith("xkv/") for k in bank)
    opt = optimizers.adamw()
    step = jax.jit(make_train_step(loss_fn, opt, schedules.constant(1e-3),
                                   pol, stats=cfg_s))
    p, st = params, opt.init(params)
    p, st, bank, m = step(p, st, bank, batch, jnp.int32(0))
    assert np.isfinite(float(m["loss"]))
    assert float(bank["dec/t0"]["fwd"]["last"].max()) == 0.0


# ---------------------------------------------------------------------------
# acceptance: zero stats reductions on non-refresh steps (jaxpr inspection)
# ---------------------------------------------------------------------------

def test_zero_stats_reductions_outside_cond():
    _, pol, params, opt, sched, loss_fn, data_fn = _tiny_setup()
    batch = data_fn(0)
    ost = opt.init(params)
    cfg_s = statsbank.StatsConfig(refresh_every=4)
    bank = statsbank.init_bank(loss_fn, params, batch, pol, cfg_s)

    jx_bank = jax.make_jaxpr(
        make_train_step(loss_fn, opt, sched, pol, stats=cfg_s))(
        params, ost, bank, batch, jnp.int32(0))
    jx_exact = jax.make_jaxpr(
        make_train_step(loss_fn, opt, sched, pol))(
        params, ost, batch, jnp.int32(0))
    jx_fp32 = jax.make_jaxpr(
        make_train_step(loss_fn, opt, sched, make_policy("fp32")))(
        params, ost, batch, jnp.int32(0))

    n_bank = statsbank.count_reductions(jx_bank, include_cond=False)
    n_bank_all = statsbank.count_reductions(jx_bank, include_cond=True)
    n_exact = statsbank.count_reductions(jx_exact, include_cond=False)
    n_fp32 = statsbank.count_reductions(jx_fp32, include_cond=False)

    # Outside lax.cond branches the bank step runs EXACTLY the reductions
    # of the numerics-free baseline plus ONE O(n_sites) bookkeeping min
    # (the stats_refreshed metric over the concatenated last-refresh
    # scalars): zero TENSOR stats reductions on non-refresh steps.  The
    # Eq. 3-4 reductions exist, but only inside cond branches.
    assert n_bank == n_fp32 + 1, (n_bank, n_fp32)
    assert n_exact > n_bank, (n_exact, n_bank)
    assert n_bank_all > n_bank, (n_bank_all, n_bank)


# ---------------------------------------------------------------------------
# checkpoint round-trip + warm-stats resume
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("compress", [False, True])
def test_bank_checkpoint_roundtrip_bitexact(tmp_path, compress):
    _, pol, params, opt, sched, loss_fn, data_fn = _tiny_setup()
    cfg_s = statsbank.StatsConfig(refresh_every=2)
    bank = statsbank.init_bank(loss_fn, params, data_fn(0), pol, cfg_s)
    step = jax.jit(make_train_step(loss_fn, opt, sched, pol, stats=cfg_s))
    p, st = params, opt.init(params)
    for s in range(3):
        p, st, bank, _ = step(p, st, bank, data_fn(s), jnp.int32(s))

    ck = CheckpointManager(str(tmp_path / f"c{compress}"), compress=compress)
    big = jax.random.normal(jax.random.PRNGKey(0), (128, 128)) * 1e-5
    ck.save(3, (bank, {"w": big}))
    template = (jax.tree_util.tree_map(jnp.zeros_like, bank),
                {"w": jnp.zeros_like(big)})
    (restored, _), _ = ck.restore(template)
    # every (alpha, beta, ema_mu, ema_m, last) leaf identical, bit for bit
    for a, b in zip(jax.tree_util.tree_leaves(bank),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if compress:
        # the big leaf still went through s2fp8 compression
        d = tmp_path / "cTrue" / "step_0000000003"
        assert any(f.endswith("payload.npy") for f in os.listdir(d))


def test_trainloop_resumes_with_warm_stats(tmp_path):
    _, pol, params, opt, sched, loss_fn, data_fn = _tiny_setup()
    cfg_s = statsbank.StatsConfig(refresh_every=4)
    bank0 = statsbank.init_bank(loss_fn, params, data_fn(0), pol, cfg_s)
    step = make_train_step(loss_fn, opt, sched, pol, stats=cfg_s)

    ck = CheckpointManager(str(tmp_path))
    loop = TrainLoop(step, params, opt.init(params), data_fn,
                     ckpt_manager=ck, ckpt_every=3, log_every=0,
                     stats_bank=bank0)
    loop.run(6)
    warm = loop.stats_bank
    assert ck.latest_step() == 6

    loop2 = TrainLoop(step, params, opt.init(params), data_fn,
                      ckpt_manager=ck, ckpt_every=3, log_every=0,
                      stats_bank=statsbank.init_bank(
                          loss_fn, params, data_fn(0), pol, cfg_s))
    loop2.maybe_resume()
    assert loop2.start_step == 6
    # the restored bank is the warm one, not a cold re-init
    for a, b in zip(jax.tree_util.tree_leaves(warm),
                    jax.tree_util.tree_leaves(loop2.stats_bank)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(loop2.stats_bank["head/t0"]["fwd"]["last"]) >= 0.0


# ---------------------------------------------------------------------------
# host bank + deprecation shim
# ---------------------------------------------------------------------------

def test_host_stats_bank_cadence_and_numerics():
    hb = statsbank.HostStatsBank(backend="ref", refresh_every=4)
    x = jax.random.normal(jax.random.PRNGKey(0), (256,)) * 1e-6
    be = nbackend.get_backend("ref")
    y0 = hb.truncate(x, "g", 0)
    # refresh-then-use: step 0 output == exact truncation
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(be.truncate(x)))
    # steps 1..3 reuse step-0 stats
    st0 = dict(hb.bank["g"])
    x1 = x * 1.01
    y1 = hb.truncate(x1, "g", 3)
    np.testing.assert_array_equal(
        np.asarray(y1),
        np.asarray(be.truncate(x1, stats=(st0["alpha"], st0["beta"]))))
    assert float(hb.bank["g"]["last"]) == 0.0
    hb.truncate(x1, "g", 4)
    assert float(hb.bank["g"]["last"]) == 4.0
    # quantize path shares the bank
    t = hb.quantize(x1, "g", 5)
    assert float(t.alpha) == float(hb.bank["g"]["alpha"])
    hb.clear()
    assert not hb.bank


def test_delayed_stats_cache_is_deprecated_shim():
    with pytest.warns(DeprecationWarning):
        cache = nbackend.DelayedStatsCache(backend="ref", refresh_every=4)
    x = jax.random.normal(jax.random.PRNGKey(1), (128,)) * 1e-5
    outs = [cache.truncate(x * (1 + 0.001 * i), "g", i) for i in range(9)]
    assert all(np.isfinite(np.asarray(o)).all() for o in outs)
    assert cache._last_refresh["g"] == 8
    assert "g" in cache._stats
    cache.clear()
    assert cache._stats == {}


def test_zero_bootstrap_does_not_poison_ema():
    """A bootstrap refresh that sees only zeros must leave the site in
    bootstrap state (last = -1, identity stats); the first refresh with
    real data then seeds the EMA from the fresh moments instead of mixing
    in the placeholder zeros."""
    st0 = statsbank.init_site_state()
    st1 = statsbank.refresh_state(jnp.zeros((32,)), st0, jnp.float32(0.0),
                                  ema_decay=0.9, backend="ref")
    assert float(st1["last"]) == -1.0
    assert float(st1["alpha"]) == 1.0 and float(st1["beta"]) == 0.0
    st2 = statsbank.refresh_state(jnp.full((32,), 1024.0), st1,
                                  jnp.float32(5.0), ema_decay=0.9,
                                  backend="ref")
    # d = 0 on the true first refresh: ema seeded at the fresh moments
    assert abs(float(st2["ema_mu"]) - 10.0) < 1e-6
    assert abs(float(st2["ema_m"]) - 10.0) < 1e-6
    assert float(st2["last"]) == 5.0


def test_host_bank_ema_mixing():
    hb = statsbank.HostStatsBank(backend="ref", refresh_every=1,
                                 ema_decay=0.5)
    x = jnp.full((64,), 4.0)          # log2 moments: mu = m = 2
    hb.truncate(x, "w", 0)
    assert abs(float(hb.bank["w"]["ema_m"]) - 2.0) < 1e-6
    hb.truncate(x * 4.0, "w", 1)      # fresh m = 4 -> ema 0.5*2 + 0.5*4 = 3
    assert abs(float(hb.bank["w"]["ema_m"]) - 3.0) < 1e-6


def test_qdot_consumes_bank_entries():
    """Payload-domain GEMM inside a session: operand quantization reuses
    the bank's (alpha, beta) — no per-call stats reduction."""
    pol = make_policy("s2fp8", backend="ref")
    a = jax.random.normal(jax.random.PRNGKey(3), (64, 32)) * 1e-6
    w = jax.random.normal(jax.random.PRNGKey(4), (32, 16)) * 1e-6

    def loss_fn(p, batch, pol_):
        return jnp.sum(pol_.qdot(batch, p["w"]) ** 2), {}

    cfg_s = statsbank.StatsConfig(refresh_every=4)
    bank = statsbank.init_bank(loss_fn, {"w": w}, a, pol, cfg_s)
    qkeys = [k for k in bank if k.startswith("q")]
    assert len(qkeys) == 2, sorted(bank)
    # operand-stats entries are read-only: forward state only
    assert set(bank[qkeys[0]]) == {"fwd"}
    # warm the entries, then the in-session qdot must equal the exact one
    # (warm bank stats == fresh stats; the output-truncation site
    # bootstrap-refreshes, so it too uses fresh stats)
    for key, x in zip(sorted(qkeys), (a, w)):
        st = statsbank.refresh_state(x, statsbank.init_site_state(),
                                     jnp.float32(0.0), backend="ref")
        bank[key]["fwd"] = st
    with statsbank.bind(bank, 1, cfg_s):
        y = pol.qdot(a, w)
    exact = pol.qdot(a, w)        # no session: per-call exact stats
    np.testing.assert_allclose(np.asarray(y), np.asarray(exact),
                               rtol=1e-5, atol=1e-30)

    # under a differentiated (banked train) step, the read-only q-entries
    # must come through UNCHANGED — not overwritten by the mathematical
    # dLoss/dalpha cotangent (reads are gradient-stopped + merge_updates)
    opt = optimizers.adamw()
    step = jax.jit(make_train_step(loss_fn, opt, schedules.constant(1e-3),
                                   pol, stats=cfg_s))
    warm = {k: jax.tree_util.tree_map(jnp.asarray, bank[k]) for k in qkeys}
    _, _, bank2, m = step({"w": w}, opt.init({"w": w}), bank, a, jnp.int32(1))
    assert np.isfinite(float(m["loss"]))
    for k in qkeys:
        for f in statsbank.STATE_FIELDS:
            np.testing.assert_array_equal(np.asarray(bank2[k]["fwd"][f]),
                                          np.asarray(warm[k]["fwd"][f]))
    # while the truncation site's entry did refresh
    tkey = [k for k in bank if k not in qkeys][0]
    assert float(bank2[tkey]["fwd"]["last"]) == 1.0


# ---------------------------------------------------------------------------
# collectives through the backend registry (satellite)
# ---------------------------------------------------------------------------

def test_collectives_encode_decode_route_through_backend():
    x = jax.random.normal(jax.random.PRNGKey(2), (4096,)) * 1e-6
    payload, alpha, beta = collectives._encode_local(x, backend="ref")
    t = s2fp8.quantize(x)
    np.testing.assert_array_equal(np.asarray(payload), np.asarray(t.payload))
    np.testing.assert_array_equal(np.asarray(alpha), np.asarray(t.alpha))
    dec = collectives._decode_local(payload, alpha, beta, backend="ref")
    np.testing.assert_array_equal(np.asarray(dec),
                                  np.asarray(s2fp8.dequantize(t)))
    with pytest.raises(KeyError):
        collectives._encode_local(x, backend="no-such-backend")


# ---------------------------------------------------------------------------
# sharded stats: global refresh == single-device bank, bit for bit
# ---------------------------------------------------------------------------

_SHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.core import backend as nbackend
from repro.core import statsbank

mesh = jax.make_mesh((8,), ("data",))

# power-of-two magnitudes: log2 values are small integers, so the f32
# sum/max reductions are order-exact -> sharded == monolithic, bitwise
key = jax.random.PRNGKey(0)
exps = jax.random.randint(key, (8 * 2048,), -8, 9).astype(jnp.float32)
signs = jnp.where(jax.random.bernoulli(jax.random.fold_in(key, 1),
                                       shape=exps.shape), 1.0, -1.0)
x = signs * (2.0 ** exps)

be = nbackend.get_backend("ref")
out = {}

# 1) backend.compute_stats: global (axis_name) vs single-device
a1, b1 = be.compute_stats(x)

def stats_body(xl):
    a, b = be.compute_stats(xl, axis_name="data")
    return a[None], b[None]

a2, b2 = jax.shard_map(stats_body, mesh=mesh, in_specs=P("data"),
                           out_specs=P("data"), check_vma=False)(x)
out["stats_alpha_bitwise"] = bool((np.asarray(a2) == float(a1)).all())
out["stats_beta_bitwise"] = bool((np.asarray(b2) == float(b1)).all())

# 2) full bank refresh: refresh_state global vs single-device
st0 = statsbank.init_site_state()
ref_st = statsbank.refresh_state(x, st0, jnp.float32(7.0), backend="ref")

def refresh_body(xl):
    st = statsbank.refresh_state(xl, statsbank.init_site_state(),
                                 jnp.float32(7.0), backend="ref",
                                 axis_name="data")
    return {k: v[None] for k, v in st.items()}

sh_st = jax.shard_map(refresh_body, mesh=mesh, in_specs=P("data"),
                      out_specs=P("data"), check_vma=False)(x)
for k in statsbank.STATE_FIELDS:
    out[f"refresh_{k}_bitwise"] = bool(
        (np.asarray(sh_st[k]) == float(ref_st[k])).all())
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_global_stats_refresh_matches_single_device_bitwise():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][0]
    out = json.loads(line[len("RESULT "):])
    assert all(out.values()), out
