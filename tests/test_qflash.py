"""Payload-domain flash attention (core/qdot.qflash_attention).

Parity anchors, PR 3/4 pattern:
  * the banked forward equals the Fig. 4 flash chain
    (truncate -> flash -> truncate with the SAME bank stats) — tight
    allclose plus a <1% bitwise flip budget for XLA fusion-order effects;
  * pallas (interpret) vs ref backend agree on values and grads up to
    truncation-boundary flips;
  * the backward matches models/flash.py's recompute schedule fed the
    truncated tensors and payload-consistent (out, lse, delta) residues;
  * residual inspection proves the node saves 1-byte Q/K/V/out payloads
    and an O(S) lse — nothing O(S^2), no f32 operand copies;
  * a steady-state banked step runs ZERO stats reductions outside
    lax.cond (jaxpr-asserted: loss sum + the flash delta identity only).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend as nbackend
from repro.core import qdot, statsbank
from repro.core.policy import make_policy
from repro.core.statsbank import FLASH_DIRS, StatsConfig, init_site_state

jax.config.update("jax_platform_name", "cpu")

CFG = StatsConfig(refresh_every=16)
STEADY = (jnp.float32(0.0), jnp.float32(101.0))      # (pred_f, step_f)


def _inputs(sq=128, sk=128, b=1, kvh=2, g=2, d=32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, kvh, g, sq, d))
    k = jax.random.normal(ks[1], (b, kvh, sk, d))
    v = jax.random.normal(ks[2], (b, kvh, sk, d))
    cot = jax.random.normal(ks[3], (b, kvh, g, sq, d))
    return q, k, v, cot


def _warm_entry(q, k, v, cot, backend="ref"):
    """FLASH_DIRS entry refreshed once from representative tensors, so a
    steady-state (pred_f=0) banked call takes the fused branch with
    realistic stats.  The out direction is warmed from an exact-path
    forward so its stats cover the real output range."""
    out = qdot.qflash_attention(q, k, v, backend=backend)
    rep = {"q": {"fwd": q, "bwd": cot * 0.5}, "k": {"fwd": k, "bwd": cot},
           "v": {"fwd": v, "bwd": cot}, "out": {"fwd": out, "bwd": cot}}
    entry = {}
    for dname in FLASH_DIRS:
        op, dirn = dname.split(".")
        entry[dname] = statsbank.refresh_state(
            rep[op][dirn], init_site_state(None), jnp.float32(1.0),
            ema_decay=0.0, target_max=15.0, backend=backend, axis_name=None)
    return entry


def _flips(a, b):
    return np.mean(np.asarray(a) != np.asarray(b))


@pytest.mark.parametrize("causal,window", [(True, None), (True, 64),
                                           (False, None)])
def test_banked_forward_matches_fig4_flash_chain(causal, window):
    """Payload forward == truncate(q/k/v) -> flash -> truncate(out) with
    the SAME bank stats (the dequant∘quant == truncate anchor), up to
    fusion-order flips (<1%, PR 3 ref-backend budget)."""
    from repro.kernels.flash_attention import flash_fwd_reference
    q, k, v, cot = _inputs()
    entry = _warm_entry(q, k, v, cot)
    banked = qdot._qflash_banked("ref", "e5m2", CFG, causal, window, 64, 64)
    out = jax.jit(lambda *a: banked(*a, entry, *STEADY))(q, k, v)

    be = nbackend.get_backend("ref")

    @jax.jit
    def chain(q_, k_, v_):
        tq = be.truncate(q_, stats=(entry["q.fwd"]["alpha"],
                                    entry["q.fwd"]["beta"]))
        tk = be.truncate(k_, stats=(entry["k.fwd"]["alpha"],
                                    entry["k.fwd"]["beta"]))
        tv = be.truncate(v_, stats=(entry["v.fwd"]["alpha"],
                                    entry["v.fwd"]["beta"]))
        raw, _ = flash_fwd_reference(tq, tk, tv, causal=causal,
                                     window=window, q_chunk=64, kv_chunk=64)
        return be.truncate(raw, stats=(entry["out.fwd"]["alpha"],
                                       entry["out.fwd"]["beta"]))

    exp = chain(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-3, atol=1e-4)
    assert _flips(out, exp) < 0.01


# (site whose stats truncate each result, result name)
_RESULT_SITES = (("out.fwd", "out"), ("q.bwd", "dq"), ("k.bwd", "dk"),
                 ("v.bwd", "dv"))


@pytest.mark.parametrize("warm", ["shared", "per_backend"])
def test_banked_pallas_matches_ref(warm):
    """Same banked node, pallas (interpret) vs ref backend: forward and
    all three gradients.  Truncation snaps both to the fp8 grid, so
    disagreement is a small flip budget, not drift.

    ``shared``: both consume the ref-warmed entry, so a flip is any
    bitwise difference.  ``per_backend``: each engine warms its own entry
    (exact-path forward, then ``refresh_state``).  The out site's stats
    then differ in their last ulps, because the two engines sum the raw
    attention in different orders, and that moves every grid point of
    the site by the same hair: from Y = 2^((log2 Q - beta) / alpha),
    |d ln Y| <= ln2 (|d beta| + |log2 Y| |d alpha|) / alpha.  A flip is
    then a difference beyond twice that hair plus a few f32 ulps of the
    evaluation: a step to a neighbouring grid point, which only an
    element within the hair of a rounding boundary takes.  It moves Y by
    at most one grid step, a factor (1 + 2^-2)^(1/alpha) for the two
    e5m2 mantissa bits."""
    q, k, v, cot = _inputs()
    entries = {be: _warm_entry(q, k, v, cot,
                               backend=be if warm == "per_backend" else "ref")
               for be in ("ref", "pallas")}
    grads = {}
    for be_name in ("ref", "pallas"):
        banked = qdot._qflash_banked(be_name, "e5m2", CFG, True, None,
                                     64, 64)
        out, vjp = jax.vjp(lambda *a: banked(*a, entries[be_name], *STEADY),
                           q, k, v)
        grads[be_name] = (out,) + vjp(cot)[:3]
    for a, b, (site, name) in zip(grads["ref"], grads["pallas"],
                                  _RESULT_SITES):
        a, b = np.asarray(a), np.asarray(b)
        if warm == "shared":
            np.testing.assert_allclose(a, b, rtol=1e-2, atol=1e-3,
                                       err_msg=name)
        sr, sp = entries["ref"][site], entries["pallas"][site]
        alpha = float(sr["alpha"])
        d_alpha = abs(float(sp["alpha"]) - alpha)
        d_beta = abs(float(sp["beta"]) - float(sr["beta"]))
        # last-ulp stats differences, not drift
        assert d_alpha <= 1e-5 * alpha and \
            d_beta <= 1e-5 * abs(float(sr["beta"])), site
        with np.errstate(divide="ignore"):
            log2_y = np.where(a != 0, np.abs(np.log2(np.abs(a))), 0.0)
        hair = 2 * np.log(2) * (d_beta + log2_y * d_alpha) / alpha
        if d_alpha or d_beta:
            hair = hair + 8 * np.finfo(np.float32).epsneg
        flips = np.abs(a - b) > hair * np.abs(a)
        assert flips.mean() < 0.01, name
        step = (1 + 2.0 ** -2) ** (1 / alpha) * (1 + hair) - 1
        assert (np.abs(a - b) <= step * np.abs(a)).all(), name


def test_banked_vjp_matches_flash_reference():
    """Backward == models/flash.py's recompute schedule on the truncated
    tensors, fed the payload-consistent residues (out_t, lse, and delta
    from the truncated cotangent), with the raw grads truncated by the
    bwd-site stats."""
    from repro.models.flash import _flash_bwd
    q, k, v, cot = _inputs()
    entry = _warm_entry(q, k, v, cot)
    banked = qdot._qflash_banked("ref", "e5m2", CFG, True, None, 64, 64)
    out, vjp = jax.vjp(lambda *a: banked(*a, entry, *STEADY), q, k, v)
    dq, dk, dv = vjp(cot)[:3]

    be = nbackend.get_backend("ref")

    def t(x, dirn):
        st = entry[dirn]
        return be.dequantize(be.quantize(
            x, stats=(st["alpha"], st["beta"])))

    tq, tk, tv = t(q, "q.fwd"), t(k, "k.fwd"), t(v, "v.fwd")
    gt = t(cot, "out.bwd")
    _, res = banked.fwd_impl(q, k, v, entry, *STEADY)
    out_t = be.dequantize(res[3])                    # 1-byte out payload
    lse = res[4]
    rq, rk, rv = _flash_bwd(True, None, 64, 64, (tq, tk, tv, out_t, lse),
                            gt)
    exp = {}
    for name, raw in (("dq", rq), ("dk", rk), ("dv", rv)):
        st = entry[name[1] + ".bwd"]
        exp[name] = be.truncate(raw, stats=(st["alpha"], st["beta"]))
    for got, name in ((dq, "dq"), (dk, "dk"), (dv, "dv")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp[name]),
                                   rtol=1e-3, atol=1e-4, err_msg=name)
        assert _flips(got, exp[name]) < 0.01, name


def test_residuals_are_payloads():
    """ShapeDtypeStruct inspection: the saved residuals are the four
    1-byte payloads (q, k, v, out) plus O(S) lse and scalar site states —
    no O(S^2) tensor and no f32 operand copies.  This is the ~4x
    attention-residual cut vs the Fig. 4 flash chain (4 x 1-byte vs
    4 x f32) on top of flash's own O(S^2) -> O(S) cut."""
    q, k, v, cot = _inputs()
    entry = _warm_entry(q, k, v, cot)
    banked = qdot._qflash_banked("ref", "e5m2", CFG, True, None, 64, 64)
    res = jax.eval_shape(banked.fwd_impl, q, k, v, entry, *STEADY)[1]
    leaves = jax.tree_util.tree_leaves(res)
    fp8 = sorted(l.shape for l in leaves if l.dtype == jnp.float8_e5m2)
    assert fp8 == sorted([q.shape, k.shape, v.shape, q.shape])
    lse_size = q.shape[0] * q.shape[1] * q.shape[2] * q.shape[3]
    for l in leaves:
        if l.dtype != jnp.float8_e5m2:
            assert l.size <= lse_size, (l.shape, l.dtype)


def test_zero_steady_state_reductions():
    """jaxpr assert: a banked value_and_grad runs exactly TWO reductions
    outside lax.cond — the test's own loss sum and the flash-2 delta
    identity (sum(dout*out), an algorithmic term like lse, not a stats
    reduction).  Every Eq. 3–4 stats pass lives under the refresh cond."""
    q, k, v, cot = _inputs()
    entry = _warm_entry(q, k, v, cot)
    banked = qdot._qflash_banked("ref", "e5m2", CFG, True, None, 64, 64)

    def loss(q_, k_, v_):
        return jnp.sum(banked(q_, k_, v_, entry, *STEADY) ** 2)

    jx = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)
    assert statsbank.count_reductions(jx, include_cond=False) == 2
    # the refresh reductions exist — they are just gated behind cond
    assert statsbank.count_reductions(jx, include_cond=True) > 2


def test_exact_matches_einsum_payload_attention():
    """Flash-payload vs the einsum-payload attention pair (the pre-fusion
    routing): same masked-softmax semantics, but the einsum path
    truncates the [S, S] score/prob tensors while flash keeps them f32 in
    VMEM — so this is a tolerance/correlation parity, not bitwise (the
    fusion REMOVES two truncation points; exactness is pinned by the
    Fig. 4 chain test above)."""
    import math as pymath
    q, k, v, _ = _inputs(sq=64, sk=64)
    out_flash = qdot.qflash_attention(q, k, v, backend="ref")

    pol = make_policy("s2fp8", backend="ref", gemm_mode="payload")
    d, sq, sk = q.shape[-1], q.shape[3], k.shape[2]
    logits = pol.einsum("bkgqd,bksd->bkgqs", q, k) / pymath.sqrt(d)
    qpos = jnp.arange(sq)[:, None] + (sk - sq)
    mask = jnp.arange(sk)[None, :] <= qpos
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out_einsum = pol.einsum("bkgqs,bksd->bkgqd", probs, v)

    a = np.asarray(out_flash).ravel()
    b = np.asarray(out_einsum).ravel()
    assert np.corrcoef(a, b)[0, 1] > 0.995
    np.testing.assert_allclose(a, b, rtol=0.5, atol=0.08)


@pytest.mark.parametrize("causal,window", [(True, None), (True, 48),
                                           (False, None)])
def test_mask_semantics_vs_dense_oracle(causal, window):
    """flash_fwd_reference (the schedule both backends share) vs a dense
    masked softmax on the same dequantized payloads — END-aligned query
    rows, causal and sliding-window, rectangular sq != sk."""
    from repro.kernels.flash_attention import flash_fwd_reference
    q, k, v, _ = _inputs(sq=64, sk=192)
    be = nbackend.get_backend("ref")
    qd, kd, vd = (be.dequantize(be.quantize(t)) for t in (q, k, v))
    out, _ = flash_fwd_reference(qd, kd, vd, causal=causal, window=window,
                                 q_chunk=64, kv_chunk=64)

    d, sq, sk = q.shape[-1], q.shape[3], k.shape[2]
    s = jnp.einsum("bkgqd,bksd->bkgqs", qd, kd) / jnp.sqrt(jnp.float32(d))
    qpos = jnp.arange(sq)[:, None] + (sk - sq)
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None, None], s, -1e30)
    exp = jnp.einsum("bkgqs,bksd->bkgqd", jax.nn.softmax(s, axis=-1), vd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-6)


def test_ragged_head_dim_pallas():
    """d=80 heads route through the dispatch zero-pad machinery on the
    pallas path (pad to the 128-lane grid, slice back) — exact for S2FP8
    and bit-identical to the unpadded ref computation up to
    truncation-boundary flips."""
    q, k, v, cot = _inputs(sq=64, sk=64, kvh=1, g=2, d=80)
    res = {}
    for be_name in ("ref", "pallas"):
        f = lambda *a: qdot.qflash_attention(*a, backend=be_name)
        out, vjp = jax.vjp(f, q, k, v)
        res[be_name] = (out,) + vjp(cot)
    for a, b, name in zip(res["ref"], res["pallas"],
                          ("out", "dq", "dk", "dv")):
        # exact per-call stats are recomputed from each backend's raw
        # tensors, whose accumulation orders differ — a last-ulp stats
        # difference shifts EVERY truncated value a hair (the value
        # tolerance), and an element within that hair of a rounding
        # boundary lands on the neighbouring grid point.  The chance of
        # that is about the relative shift (~1e-6) over the relative grid
        # spacing (~0.1) per element, so a 0.1% budget of such flips
        # covers it with a wide margin.
        far = ~np.isclose(np.asarray(b), np.asarray(a), rtol=1e-2, atol=1e-3)
        assert far.mean() < 1e-3, name
        assert a.shape[-1] == 80


def test_bank_update_idiom():
    """The entry cotangent is the refreshed bank entry: on a refresh step
    every direction's `last` advances to the step; merge_updates accepts
    the qf node (every direction has a bwd twin)."""
    q, k, v, cot = _inputs(sq=32, sk=32)
    entry = {d: init_site_state(None) for d in FLASH_DIRS}  # cold: last=-1
    banked = qdot._qflash_banked("ref", "e5m2", CFG, True, None, 32, 32)
    step = jnp.float32(7.0)
    _, vjp = jax.vjp(
        lambda e: banked(q, k, v, e, jnp.float32(0.0), step), entry)
    entry_cot = vjp(cot)[0]
    for dname in FLASH_DIRS:
        assert float(entry_cot[dname]["last"]) == 7.0, dname
    bank = {"qf0": entry}
    merged = statsbank.merge_updates(bank, {"qf0": entry_cot})
    assert float(merged["qf0"]["q.fwd"]["last"]) == 7.0


def test_full_attention_payload_trains_through_bank():
    """End-to-end: a loss over full_attention with a payload policy
    discovers one qf node, init_bank builds its FLASH_DIRS states, and a
    banked value_and_grad step yields finite grads plus a refreshed
    bank."""
    from repro.models.blocks import full_attention
    q, k, v, _ = _inputs(sq=16, sk=16, d=16)
    pol = make_policy("s2fp8", backend="ref", gemm_mode="payload")

    def loss_fn(params, batch, policy):
        out = full_attention(params["q"], batch["k"], batch["v"],
                             causal=True, policy=policy)
        return jnp.mean(out ** 2), {}

    params, batch = {"q": q}, {"k": k, "v": v}
    bank = statsbank.init_bank(loss_fn, params, batch, pol, CFG)
    assert set(bank) == {"qf0"} and set(bank["qf0"]) == set(FLASH_DIRS)

    @jax.jit
    def step(p, bank, step_idx):
        def banked(p_, b_):
            with statsbank.bind(b_, step_idx, CFG):
                l, _ = loss_fn(p_, batch, pol)
            return l
        l, (g, bank_cot) = jax.value_and_grad(
            banked, argnums=(0, 1))(p, bank)
        return l, g, statsbank.merge_updates(bank, bank_cot)

    loss, grads, bank = step(params, bank, jnp.int32(0))
    assert np.isfinite(float(loss))
    assert np.all(np.isfinite(np.asarray(grads["q"])))
    assert float(bank["qf0"]["out.fwd"]["last"]) == 0.0
    # steady step: stats carried, still finite
    loss2, _, bank = step(params, bank, jnp.int32(1))
    assert np.isfinite(float(loss2))
