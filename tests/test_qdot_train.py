"""qdot_train — the differentiable payload-domain training GEMM.

Acceptance anchors (core/qdot.py, ISSUE 3):

  * forward parity: payload-domain output == the Fig. 4 chain BITWISE when
    both consume the same bank stats (truncate = dequant∘quantize
    elementwise; single-K-block GEMM), on the ref AND pallas backends;
  * VJP parity: gradients match the Fig. 4 reference chain within float
    tolerance;
  * NT/TN layout kernels match jnp transposes without materializing one;
  * residuals are FP8 payloads + scalars — no f32 operand residuals;
  * steady-state banked steps run zero stats reductions outside lax.cond;
  * e4m3 storage parity rides the same path (``fmt``/``qdtype`` plumbing).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend as nbackend
from repro.core import qdot
from repro.core import s2fp8
from repro.core import statsbank
from repro.core.backend import plan_einsum
from repro.core.policy import make_policy
from repro.core.s2fp8 import S2FP8Tensor
from repro.kernels import dispatch
from repro.kernels.ref import (GEMM_SPECS, dot_error_bound, gemm_dims,
                               truncation_bracket)
from repro.kernels.s2fp8_matmul import pick_gemm_block

jax.config.update("jax_platform_name", "cpu")

CFG = statsbank.StatsConfig(refresh_every=16)


def _warm_state(stats, last=100.0):
    alpha, beta = stats
    return {"alpha": jnp.asarray(alpha, jnp.float32),
            "beta": jnp.asarray(beta, jnp.float32),
            "ema_mu": jnp.float32(0.0), "ema_m": jnp.float32(0.0),
            "last": jnp.float32(last)}


def _shared_entry(a, b, cot=None):
    """Bank entry whose six directions carry exact shared stats — the
    'same bank stats' premise of the parity anchor."""
    sa = s2fp8.compute_stats_jit(a)
    sb = s2fp8.compute_stats_jit(b)
    be = nbackend.get_backend("ref")
    y = jnp.dot(be.truncate(a, stats=sa), be.truncate(b, stats=sb),
                preferred_element_type=jnp.float32)
    so = s2fp8.compute_stats_jit(y)
    sg = s2fp8.compute_stats_jit(cot) if cot is not None else so
    return {"a.fwd": _warm_state(sa), "a.bwd": _warm_state(sa),
            "b.fwd": _warm_state(sb), "b.bwd": _warm_state(sb),
            "out.fwd": _warm_state(so), "out.bwd": _warm_state(sg)}, \
        (sa, sb, so, sg)


# K <= 256 keeps the contraction in one K block after padding, where the
# tiled Pallas accumulation is bitwise-identical to the monolithic dot
# (tiling only output rows/cols preserves each element's reduction order).
PARITY_SHAPES = [(96, 192, 80), (128, 256, 128), (64, 130, 40)]


@pytest.mark.parametrize("scale", [1e-6, 1.0, 1e4])
@pytest.mark.parametrize("mkn", PARITY_SHAPES)
def test_forward_parity_bitwise_vs_fig4_chain_pallas(mkn, scale):
    """The acceptance anchor on the kernel engine: the SHIPPED jitted
    banked payload path (quant kernel -> dequant-matmul kernel -> in-VMEM
    epilogue) is bitwise identical to the jitted Fig. 4 chain (truncate
    kernels around jnp.dot) when both consume the same bank stats.  The
    pallas_call boundaries pin each stage's program, which is what makes
    cross-chain bitwise equality well-defined (kernels/README.md, "A note
    on bitwise parity")."""
    m, k, n = mkn
    a = jax.random.normal(jax.random.PRNGKey(0), (m, k)) * scale
    b = jax.random.normal(jax.random.PRNGKey(1), (k, n)) * scale
    entry, (sa, sb, so, _) = _shared_entry(a, b)
    be = nbackend.get_backend("pallas")
    fig4 = jax.jit(lambda a_, b_: be.truncate(
        jnp.dot(be.truncate(a_, stats=sa), be.truncate(b_, stats=sb),
                preferred_element_type=jnp.float32), stats=so))
    f = qdot._qdot_banked("pallas", "e5m2", CFG)
    payload = jax.jit(lambda a_, b_: f(a_, b_, entry, jnp.float32(0.0),
                                       jnp.float32(101.0)))
    np.testing.assert_array_equal(np.asarray(payload(a, b)),
                                  np.asarray(fig4(a, b)))


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("scale", [1e-6, 1.0, 1e4])
def test_forward_parity_bitwise_stage_pinned(backend, scale):
    """Fig. 4 == payload-domain, proven stage by stage with materialized
    intermediates (each stage one pinned program):

      (1) the payload GEMM == dot on the dequantized payloads — bitwise on
          the ref engine (the same XLA dot on the same shapes); on the
          kernel engine within the f32 accumulation-order bound
          ``dot_error_bound``, because the kernel's tile is zero-padded to
          the 128-lane grid and XLA:CPU sums a padded dot in another order;
      (2) fused epilogue == separate truncation of the same raw GEMM,
          bitwise on both engines;

    and the Fig. 4 chain's operand truncation IS ``dequant∘quantize``
    (paper Eq. 5 = the storage round trip), so (1)+(2) chain into the
    end-to-end identity: exact where the raw value is farther than the
    bound from a rounding boundary, else one of its two neighbours
    (``truncation_bracket``)."""
    m, k, n = 96, 192, 80
    a = jax.random.normal(jax.random.PRNGKey(2), (m, k)) * scale
    b = jax.random.normal(jax.random.PRNGKey(3), (k, n)) * scale
    sa = s2fp8.compute_stats_jit(a)
    sb = s2fp8.compute_stats_jit(b)
    be = nbackend.get_backend(backend)
    qa, qb = be.quantize(a, stats=sa), be.quantize(b, stats=sb)
    ta, tb = be.dequantize(qa), be.dequantize(qb)       # truncated operands
    y_fig4 = jnp.dot(ta, tb, preferred_element_type=jnp.float32)
    so = s2fp8.compute_stats_jit(y_fig4)
    y_pay = np.asarray(be.qmatmul(qa, qb))
    bound = dot_error_bound(GEMM_SPECS["nn"], ta, tb)
    assert (np.abs(y_pay - np.asarray(y_fig4)) <= bound).all()    # (1)
    if backend == "ref":
        np.testing.assert_array_equal(y_pay, np.asarray(y_fig4))
    fused = np.asarray(be.qmatmul(qa, qb, epilogue_stats=so))
    np.testing.assert_array_equal(                                 # (2)
        fused, np.asarray(be.truncate(jnp.asarray(y_pay), stats=so)))
    lo, hi = truncation_bracket(                                   # end to end
        y_fig4, bound, lambda x: be.truncate(jnp.asarray(x), stats=so))
    assert ((lo <= fused) & (fused <= hi)).all()
    if backend == "ref":
        np.testing.assert_array_equal(
            fused, np.asarray(be.truncate(y_fig4, stats=so)))


def test_truncate_is_dequant_of_quantize():
    """The elementwise identity behind the parity anchor, compared as
    same-structured compiled programs (identical HLO op sequence)."""
    x = jax.random.normal(jax.random.PRNGKey(4), (128, 96)) * 1e-5
    stats = s2fp8.compute_stats_jit(x)
    roundtrip = jax.jit(
        lambda v: s2fp8.dequantize(s2fp8.quantize(v, stats=stats)))
    trunc = jax.jit(lambda v: s2fp8.truncate_value(v, stats=stats))
    np.testing.assert_array_equal(np.asarray(roundtrip(x)),
                                  np.asarray(trunc(x)))
    # pallas: quant kernel + dequant kernel vs the fused truncate kernel
    pal = nbackend.get_backend("pallas")
    np.testing.assert_array_equal(
        np.asarray(pal.dequantize(pal.quantize(x, stats=stats))),
        np.asarray(pal.truncate(x, stats=stats)))


def test_forward_parity_ref_fused_programs_close():
    """The jitted-vs-jitted comparison on the ref engine: XLA may fuse the
    quantize chain differently across program structures (the documented
    1-ulp FMA hazard), flipping rare RNE-boundary payload bits — so this
    is a tolerance assertion with a bounded flip rate, while the bitwise
    claims above hold in the stage-pinned regime."""
    m, k, n = 96, 192, 80
    a = jax.random.normal(jax.random.PRNGKey(5), (m, k)) * 1e-6
    b = jax.random.normal(jax.random.PRNGKey(6), (k, n)) * 1e-6
    entry, (sa, sb, so, _) = _shared_entry(a, b)
    be = nbackend.get_backend("ref")
    fig4 = jax.jit(lambda a_, b_: be.truncate(
        jnp.dot(be.truncate(a_, stats=sa), be.truncate(b_, stats=sb),
                preferred_element_type=jnp.float32), stats=so))
    f = qdot._qdot_banked("ref", "e5m2", CFG)
    payload = jax.jit(lambda a_, b_: f(a_, b_, entry, jnp.float32(0.0),
                                       jnp.float32(101.0)))
    yf, yp = np.asarray(fig4(a, b)), np.asarray(payload(a, b))
    assert (yf != yp).mean() < 0.01
    nz = (yf != 0) & (yp != 0)
    np.testing.assert_allclose(yp[nz], yf[nz], rtol=1e-3)


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_vjp_parity_vs_fig4_reference_chain(backend):
    m, k, n = 64, 192, 48
    a = jax.random.normal(jax.random.PRNGKey(2), (m, k)) * 1e-6
    b = jax.random.normal(jax.random.PRNGKey(3), (k, n)) * 1e-6
    cot = jax.random.normal(jax.random.PRNGKey(4), (m, n)) * 1e-8
    entry, (sa, sb, so, sg) = _shared_entry(a, b, cot)
    be = nbackend.get_backend(backend)
    f = qdot._qdot_banked(backend, "e5m2", CFG)
    pred_f, step_f = jnp.float32(0.0), jnp.float32(101.0)
    _, vjp = jax.vjp(lambda a_, b_: f(a_, b_, entry, pred_f, step_f), a, b)
    da, db = vjp(cot)
    # Fig. 4 backward with the same shared stats: truncate the cotangent,
    # transposed GEMMs against the truncated forward operands, truncate
    # the operand gradients.
    g_t = be.truncate(cot, stats=sg)
    da_ref = be.truncate(
        jax.lax.dot_general(g_t, be.truncate(b, stats=sb),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32), stats=sa)
    db_ref = be.truncate(
        jax.lax.dot_general(be.truncate(a, stats=sa), g_t,
                            (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32), stats=sb)
    np.testing.assert_allclose(np.asarray(da), np.asarray(da_ref),
                               rtol=1e-6, atol=0)
    np.testing.assert_allclose(np.asarray(db), np.asarray(db_ref),
                               rtol=1e-6, atol=0)


def test_cross_backend_banked_grads_close():
    """ref and pallas payload paths agree on gradients (float tolerance —
    the backward GEMMs tile differently)."""
    a = jax.random.normal(jax.random.PRNGKey(5), (48, 160)) * 1e-5
    b = jax.random.normal(jax.random.PRNGKey(6), (160, 32)) * 1e-5
    entry, _ = _shared_entry(a, b)
    outs = {}
    for backend in ("ref", "pallas"):
        f = qdot._qdot_banked(backend, "e5m2", CFG)
        loss = lambda a_, b_: jnp.sum(
            f(a_, b_, entry, jnp.float32(0.0), jnp.float32(101.0)) ** 2)
        outs[backend] = jax.grad(loss, argnums=(0, 1))(a, b)
    for gr, gp in zip(outs["ref"], outs["pallas"]):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gp),
                                   rtol=1e-5, atol=1e-30)


# ---------------------------------------------------------------------------
# NT / TN layouts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shapes,layout", [
    (((130, 70), (40, 70)), "nt"),     # C[130,40] = A @ B^T
    (((70, 130), (70, 33)), "tn"),     # C[130,33] = A^T @ B
    (((128, 256), (64, 256)), "nt"),
    (((256, 128), (256, 64)), "tn"),
])
def test_layout_kernels_vs_jnp_transposes(shapes, layout):
    (ash, bsh) = shapes
    a = jax.random.normal(jax.random.PRNGKey(7), ash) * 1e-3
    b = jax.random.normal(jax.random.PRNGKey(8), bsh) * 1e-3
    pal = nbackend.get_backend("pallas")
    qa, qb = pal.quantize(a), pal.quantize(b)
    out = np.asarray(pal.qmatmul(qa, qb, layout=layout))
    da, db = s2fp8.dequantize(qa), s2fp8.dequantize(qb)
    exp = np.asarray(jnp.dot(da, db.T) if layout == "nt"
                     else jnp.dot(da.T, db))
    m, k, n = gemm_dims(layout, ash, bsh)
    assert out.shape == (m, n)
    # the kernel and the jnp dot sum K in different orders (the kernel's
    # tile is lane-padded); both lie within the f32 accumulation bound of
    # the exact product, elementwise — a relative tolerance cannot hold
    # where the sum cancels to near zero
    bound = dot_error_bound(GEMM_SPECS[layout], da, db)
    assert (np.abs(out - exp) <= bound).all()
    # and the ref backend agrees (same layout semantics, jnp engine)
    refo = np.asarray(nbackend.get_backend("ref").qmatmul(qa, qb,
                                                          layout=layout))
    assert (np.abs(out - refo) <= bound).all()


def test_epilogue_matches_separate_truncation_bitwise():
    a = jax.random.normal(jax.random.PRNGKey(9), (128, 192)) * 1e-5
    b = jax.random.normal(jax.random.PRNGKey(10), (192, 64)) * 1e-5
    for name in ("ref", "pallas"):
        be = nbackend.get_backend(name)
        qa, qb = be.quantize(a), be.quantize(b)
        y_raw = be.qmatmul(qa, qb)
        so = nbackend.get_backend("ref").compute_stats(y_raw)
        fused = np.asarray(be.qmatmul(qa, qb, epilogue_stats=so))
        separate = np.asarray(be.truncate(y_raw, stats=so))
        np.testing.assert_array_equal(fused, separate, err_msg=name)


def test_epilogue_saturates_under_stale_stats():
    """Stale out-site stats after upward drift: the in-kernel clamp must
    saturate at the format max, never inf."""
    noise = 1.0 + 1e-3 * jax.random.normal(jax.random.PRNGKey(11), (64, 64))
    a = 3.0 * noise
    b = jnp.eye(64) * (1.0 + 1e-3)
    for name in ("ref", "pallas"):
        be = nbackend.get_backend(name)
        qa, qb = be.quantize(a), be.quantize(b)
        stale = nbackend.get_backend("ref").compute_stats(
            be.qmatmul(qa, qb) * 0.5)          # stats of a smaller tensor
        y = np.asarray(be.qmatmul(qa, qb, epilogue_stats=stale))
        assert np.isfinite(y).all(), name


# ---------------------------------------------------------------------------
# residual memory: payload residuals only
# ---------------------------------------------------------------------------

def _residual_leaves(fwd_impl, *args):
    _, res = jax.eval_shape(fwd_impl, *args)
    return jax.tree_util.tree_leaves(res)


@pytest.mark.parametrize("banked", [True, False])
def test_no_f32_operand_residuals_saved(banked):
    m, k, n = 96, 128, 64
    a = jnp.zeros((m, k), jnp.float32)
    b = jnp.zeros((k, n), jnp.float32)
    if banked:
        entry, _ = _shared_entry(jnp.ones((m, k)), jnp.ones((k, n)))
        f = qdot._qdot_banked("ref", "e5m2", CFG)
        leaves = _residual_leaves(f.fwd_impl, a, b, entry,
                                  jnp.float32(0.0), jnp.float32(1.0))
    else:
        f = qdot._qdot_exact("ref", "e5m2")
        leaves = _residual_leaves(f.fwd_impl, a, b)
    fp8_bytes = [l for l in leaves if l.dtype == jnp.float8_e5m2]
    assert {l.shape for l in fp8_bytes} == {(m, k), (k, n)}
    for l in leaves:
        if l.dtype == jnp.float32:
            # scalars (stats / bookkeeping) only — never operand-sized f32
            assert np.prod(l.shape, dtype=np.int64) <= 1, l
    # the residual payload footprint is ~1/4 of the Fig. 4 chain's f32
    # truncated operands
    payload_bytes = sum(int(np.prod(l.shape)) for l in fp8_bytes)
    assert payload_bytes == m * k + k * n


# ---------------------------------------------------------------------------
# banked training integration
# ---------------------------------------------------------------------------

def _payload_setup(dim=32, batch=4):
    key = jax.random.PRNGKey(12)
    params = {"w1": jax.random.normal(key, (dim, dim)) * 1e-3,
              "w2": jax.random.normal(jax.random.fold_in(key, 1),
                                      (dim, dim)) * 1e-3}
    x = jax.random.normal(jax.random.fold_in(key, 2), (batch, dim)) * 1e-3
    pol = make_policy("s2fp8", backend="ref", gemm_mode="payload")

    def loss_fn(p, b, pol_):
        h = pol_.dot(b, p["w1"])
        h = pol_.dot(h, p["w2"])
        return jnp.sum(h * h), {}

    return params, x, pol, loss_fn


def test_banked_training_step_refresh_cadence():
    from repro.optim import optimizers, schedules
    from repro.training.trainer import make_train_step
    params, x, pol, loss_fn = _payload_setup()
    scfg = statsbank.StatsConfig(refresh_every=4)
    bank = statsbank.init_bank(loss_fn, params, x, pol, scfg)
    assert set(next(iter(bank.values()))) == set(statsbank.GEMM_DIRS)
    opt = optimizers.adamw()
    step_fn = jax.jit(make_train_step(loss_fn, opt,
                                      schedules.constant(1e-3), pol,
                                      stats=scfg))
    ost = opt.init(params)
    lasts = []
    for s in range(6):
        params, ost, bank, m = step_fn(params, ost, bank, x, jnp.int32(s))
        assert np.isfinite(float(m["loss"]))
        lasts.append(float(next(iter(bank.values()))["out.bwd"]["last"]))
    # bootstrap refresh at step 0, cadence refresh at step 4
    assert lasts == [0.0, 0.0, 0.0, 0.0, 4.0, 4.0]


def test_zero_stats_reductions_outside_cond_payload():
    """Steady-state payload-GEMM bank steps run ZERO stats reductions
    outside lax.cond — same invariant as the fig4 bank step, now with the
    GEMM itself payload-domain."""
    from repro.optim import optimizers, schedules
    from repro.training.trainer import make_train_step
    params, x, pol, loss_fn = _payload_setup()
    scfg = statsbank.StatsConfig(refresh_every=4)
    bank = statsbank.init_bank(loss_fn, params, x, pol, scfg)
    opt = optimizers.adamw()
    sched = schedules.constant(1e-3)
    ost = opt.init(params)
    jx_bank = jax.make_jaxpr(make_train_step(loss_fn, opt, sched, pol,
                                             stats=scfg))(
        params, ost, bank, x, jnp.int32(0))
    jx_fp32 = jax.make_jaxpr(make_train_step(loss_fn, opt, sched,
                                             make_policy("fp32")))(
        params, ost, x, jnp.int32(0))
    n_bank = statsbank.count_reductions(jx_bank, include_cond=False)
    n_fp32 = statsbank.count_reductions(jx_fp32, include_cond=False)
    # the +1 is the O(n_sites) bookkeeping min (stats_refreshed metric)
    assert n_bank == n_fp32 + 1, (n_bank, n_fp32)


def test_payload_vs_fig4_training_losses_track():
    """Same model trained payload-domain vs Fig. 4: losses stay close
    (the two dataflows are numerically equivalent up to stats cadence)."""
    from repro.optim import optimizers, schedules
    from repro.training.trainer import make_train_step
    params, x, _, loss_fn = _payload_setup()
    losses = {}
    for gm in ("payload", "fig4"):
        pol = make_policy("s2fp8", backend="ref", gemm_mode=gm)
        scfg = statsbank.StatsConfig(refresh_every=2)
        bank = statsbank.init_bank(loss_fn, params, x, pol, scfg)
        opt = optimizers.adamw()
        step_fn = jax.jit(make_train_step(loss_fn, opt,
                                          schedules.constant(1e-3), pol,
                                          stats=scfg))
        p, ost = params, opt.init(params)
        hist = []
        for s in range(4):
            p, ost, bank, m = step_fn(p, ost, bank, x, jnp.int32(s))
            hist.append(float(m["loss"]))
        losses[gm] = hist
    np.testing.assert_allclose(losses["payload"], losses["fig4"], rtol=0.02)


# ---------------------------------------------------------------------------
# policy routing
# ---------------------------------------------------------------------------

def test_policy_gemm_mode_routing():
    a = jax.random.normal(jax.random.PRNGKey(13), (8, 16)) * 1e-6
    b = jax.random.normal(jax.random.PRNGKey(14), (16, 8)) * 1e-6
    # auto on the ref engine -> fig4 (CPU default): unchanged semantics
    auto = make_policy("s2fp8", backend="ref")
    assert not auto.uses_payload_gemm
    fig4 = make_policy("s2fp8", backend="ref", gemm_mode="fig4")
    np.testing.assert_array_equal(np.asarray(auto.dot(a, b)),
                                  np.asarray(fig4.dot(a, b)))
    # auto on a pallas engine -> payload
    assert make_policy("s2fp8", backend="pallas").uses_payload_gemm
    # forced payload routes through qdot_train (same result, any backend)
    pay = make_policy("s2fp8", backend="ref", gemm_mode="payload")
    exp = qdot.qdot_train(a, b, backend="ref")
    np.testing.assert_array_equal(np.asarray(pay.dot(a, b)),
                                  np.asarray(exp.astype(a.dtype)))
    # non-s2fp8 modes and truncate_output=False stay on the classic path
    assert not make_policy("fp32").uses_payload_gemm
    from repro.core.policy import Policy
    assert not Policy(mode="s2fp8", gemm_mode="auto",
                      truncate_output=False).uses_payload_gemm
    # the bf16 GEMM-boundary lever no longer forces fig4: the payload
    # return rounds through accum_dtype at the boundary instead
    assert Policy(mode="s2fp8", gemm_mode="auto", backend="pallas",
                  output_dtype="bfloat16").uses_payload_gemm
    # explicit payload requests incompatible with the fused epilogue are
    # rejected, not silently downgraded
    with pytest.raises(ValueError):
        Policy(mode="s2fp8", gemm_mode="payload", truncate_output=False)
    with pytest.raises(ValueError):
        Policy(mode="s2fp8", gemm_mode="tiled")


def test_einsum_planner_routing():
    """The PR-3 whitelist is gone: Policy.einsum routes through the
    backend planner.  The dense family still plans 2-D; the previously
    rejected batched/attention specs now plan batched (covered in depth
    by tests/test_qdot_batched.py); genuinely unplannable specs fall
    back to the Fig. 4 chain."""
    dense = plan_einsum("bsd,df->bsf", (2, 6, 16), (16, 8))
    assert dense is not None and dense.batch == 1
    assert plan_einsum("md,df->mf", (4, 16), (16, 8)) is not None
    assert plan_einsum("...d,df->...f", (2, 6, 16), (16, 8)) == dense
    assert plan_einsum("ecd,edf->ecf", (2, 4, 16), (2, 16, 8)).batch == 2
    assert plan_einsum("bhqd,bhkd->bhqk",
                       (2, 3, 4, 16), (2, 3, 5, 16)).layout == "nt"
    assert plan_einsum("dd,df->df", (4, 4), (4, 8)) is None   # repeated idx
    assert plan_einsum("...d,...df->...f",
                       (2, 6, 16), (2, 16, 8)) is None        # ellipsis rhs
    assert plan_einsum("...d,df->f", (2, 6, 16), (16, 8)) is None  # dropped
    assert plan_einsum("abc,abc->a", (2, 3, 4), (2, 3, 4)) is None  # multi-k
    # routed einsum == routed dot, explicit and ellipsis forms
    pol = make_policy("s2fp8", backend="ref", gemm_mode="payload")
    a = jax.random.normal(jax.random.PRNGKey(15), (2, 6, 16)) * 1e-6
    w = jax.random.normal(jax.random.PRNGKey(16), (16, 8)) * 1e-6
    np.testing.assert_array_equal(
        np.asarray(pol.einsum("bsd,df->bsf", a, w)),
        np.asarray(pol.dot(a, w)))
    np.testing.assert_array_equal(
        np.asarray(pol.einsum("...d,df->...f", a, w)),
        np.asarray(pol.dot(a, w)))


def test_host_bank_quantize_respects_fmt():
    bank = statsbank.HostStatsBank(backend="ref", fmt="e4m3")
    x = jax.random.normal(jax.random.PRNGKey(28), (64,)) * 1e-4
    t = bank.quantize(x, "w", 0)
    assert t.fmt == "e4m3" and t.payload.dtype == jnp.float8_e4m3fn


def test_operand_stats_rederives_per_fmt():
    """A q-site's carried moments are format-agnostic: reads re-derive
    (alpha, beta) with the caller's fmt target, so an e5m2-warmed bank
    serves e4m3 qdot correctly (and reproduces the stored scalars exactly
    for the warming format)."""
    x = jax.random.normal(jax.random.PRNGKey(29), (64,)) * 1e-3
    entry = {"fwd": statsbank.refresh_state(
        x, statsbank.init_site_state(), jnp.float32(0.0),
        target_max=s2fp8.TARGET_MAX_LOG2)}
    bank = {"q0": entry}
    cfg = statsbank.StatsConfig(refresh_every=4)
    with statsbank.bind(bank, jnp.int32(1), cfg) as sess:
        a5 = sess.operand_stats(x, fmt="e5m2")
        sess._counters.clear()
        a4 = sess.operand_stats(x, fmt="e4m3")
    assert float(a5[0]) == float(entry["fwd"]["alpha"])
    exp4 = s2fp8.stats_from_reduction(
        entry["fwd"]["ema_mu"], entry["fwd"]["ema_m"], jnp.float32(1.0),
        s2fp8.TARGET_MAX_LOG2_E4M3)
    assert float(a4[0]) == float(exp4[0]) != float(a5[0])


def test_qdot_general_plan_and_execution():
    plan = nbackend.plan_qdot_general((4, 8), (8, 5), (((1,), (0,)), ((), ())))
    assert (plan.layout, plan.a2_shape, plan.b2_shape, plan.out_shape) == \
        ("nn", (4, 8), (8, 5), (4, 5)) and plan.batch == 1
    assert nbackend.plan_qdot_general((4, 8), (5, 8),
                                      (((1,), (1,)), ((), ())))[0] == "nt"
    assert nbackend.plan_qdot_general((8, 4), (8, 5),
                                      (((0,), (0,)), ((), ())))[0] == "tn"
    # unsupported: tt, multi-contraction; batch dims now PLAN (batched)
    assert nbackend.plan_qdot_general((8, 4), (5, 8),
                                      (((0,), (1,)), ((), ()))) is None
    bplan = nbackend.plan_qdot_general((2, 4, 8), (2, 8, 5),
                                       (((2,), (1,)), ((0,), (0,))))
    assert bplan is not None and bplan.batch == 2 and bplan.layout == "nn"
    be = nbackend.get_backend("ref")
    a = jax.random.normal(jax.random.PRNGKey(17), (3, 4, 16)) * 1e-4
    b = jax.random.normal(jax.random.PRNGKey(18), (16, 6)) * 1e-4
    qa, qb = be.quantize(a), be.quantize(b)
    out = be.qdot_general(qa, qb, (((2,), (0,)), ((), ())))
    exp = jnp.einsum("bsk,kn->bsn", s2fp8.dequantize(qa),
                     s2fp8.dequantize(qb))
    assert out.shape == (3, 4, 6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-5)
    with pytest.raises(ValueError):
        be.qdot_general(qa, qb, (((0,), (1,)), ((), ())))


# ---------------------------------------------------------------------------
# e4m3 storage parity (ROADMAP open item)
# ---------------------------------------------------------------------------

def test_e4m3_storage_and_tensor_fmt_tag():
    x = jax.random.normal(jax.random.PRNGKey(19), (64, 48)) * 1e-4
    for name in ("ref", "pallas"):
        t = nbackend.get_backend(name).quantize(x, fmt="e4m3")
        assert t.payload.dtype == jnp.float8_e4m3fn and t.fmt == "e4m3"
        # fmt survives pytree flatten/unflatten (jit boundaries, ckpt)
        leaves, treedef = jax.tree_util.tree_flatten(t)
        t2 = jax.tree_util.tree_unflatten(treedef, leaves)
        assert t2.fmt == "e4m3"
        # round-trip accuracy: e4m3's extra mantissa bit with the squeeze
        d = np.asarray(nbackend.get_backend(name).dequantize(t))
        nz = d != 0
        rel = np.abs(d[nz] - np.asarray(x)[nz]) / np.abs(np.asarray(x)[nz])
        assert np.median(rel) < 0.04, name
    # payloads agree bitwise across backends given shared stats
    stats = nbackend.get_backend("ref").compute_stats(x, fmt="e4m3")
    pr = nbackend.get_backend("ref").quantize(x, stats=stats, fmt="e4m3")
    pp = nbackend.get_backend("pallas").quantize(x, stats=stats, fmt="e4m3")
    np.testing.assert_array_equal(np.asarray(pr.payload).view(np.uint8),
                                  np.asarray(pp.payload).view(np.uint8))


def test_e4m3_policy_qdot_unblocked():
    a = jax.random.normal(jax.random.PRNGKey(20), (66, 40)) * 1e-6
    b = jax.random.normal(jax.random.PRNGKey(21), (40, 24)) * 1e-6
    for backend in ("ref", "pallas"):
        out = np.asarray(make_policy("s2fp8_e4m3", backend=backend).qdot(a, b))
        exact = np.asarray(jnp.dot(a, b))
        assert np.corrcoef(out.ravel(), exact.ravel())[0, 1] > 0.99


def test_bf16_operands_grads_match_dtype():
    """bf16 models: cotangents must come back in the operands' dtype (the
    f32 cast sits outside the custom_vjp)."""
    a = jax.random.normal(jax.random.PRNGKey(26), (16, 32), jnp.bfloat16)
    b = jax.random.normal(jax.random.PRNGKey(27), (32, 8), jnp.bfloat16)
    pol = make_policy("s2fp8", backend="ref", gemm_mode="payload")
    y, vjp = jax.vjp(lambda a_, b_: pol.dot(a_, b_), a, b)
    assert y.dtype == jnp.bfloat16
    da, db = vjp(jnp.ones_like(y))
    assert da.dtype == jnp.bfloat16 and db.dtype == jnp.bfloat16
    assert np.isfinite(np.asarray(da, dtype=np.float32)).all()


def test_e4m3_qdot_train_grads():
    a = jax.random.normal(jax.random.PRNGKey(22), (32, 64)) * 1e-6
    b = jax.random.normal(jax.random.PRNGKey(23), (64, 16)) * 1e-6
    loss = lambda a_, b_: jnp.sum(
        qdot.qdot_train(a_, b_, backend="ref", fmt="e4m3") ** 2)
    val, (da, db) = jax.value_and_grad(loss, argnums=(0, 1))(a, b)
    assert np.isfinite(float(val))
    assert np.isfinite(np.asarray(da)).all() and np.abs(np.asarray(da)).max() > 0
    # Policy-level routing in e4m3 payload mode
    pol = make_policy("s2fp8_e4m3", backend="ref", gemm_mode="payload")
    out = np.asarray(pol.dot(a, b))
    assert np.corrcoef(out.ravel(),
                       np.asarray(jnp.dot(a, b)).ravel())[0, 1] > 0.99


# ---------------------------------------------------------------------------
# block heuristic + env override
# ---------------------------------------------------------------------------

def test_block_heuristic_table_and_env_override(monkeypatch):
    monkeypatch.delenv("REPRO_GEMM_BLOCK", raising=False)
    for mkn in [(256, 256, 256), (1024, 1024, 1024), (4096, 4096, 4096)]:
        bm, bk, bn = pick_gemm_block(*mkn, platform="tpu")
        assert all(v % 128 == 0 for v in (bm, bk, bn)), mkn
    # bigger problems never pick smaller K blocks (streaming depth grows)
    assert pick_gemm_block(4096, 4096, 4096, platform="tpu")[1] >= \
        pick_gemm_block(256, 256, 256, platform="tpu")[1]
    monkeypatch.setenv("REPRO_GEMM_BLOCK", "128,128,128")
    assert pick_gemm_block(2048, 2048, 2048) == (128, 128, 128)
    # the override reaches the dispatch layer and stays correct
    a = jax.random.normal(jax.random.PRNGKey(24), (130, 70)) * 1e-3
    b = jax.random.normal(jax.random.PRNGKey(25), (70, 33)) * 1e-3
    pal = nbackend.get_backend("pallas")
    qa, qb = pal.quantize(a), pal.quantize(b)
    out = np.asarray(pal.qmatmul(qa, qb))
    da, db = s2fp8.dequantize(qa), s2fp8.dequantize(qb)
    exp = np.asarray(jnp.dot(da, db))
    # 128-wide blocks over K=70: f32 accumulation-order bound, elementwise
    assert (np.abs(out - exp) <= dot_error_bound(GEMM_SPECS["nn"], da,
                                                 db)).all()
    monkeypatch.setenv("REPRO_GEMM_BLOCK", "banana")
    with pytest.raises(ValueError):
        pick_gemm_block(256, 256, 256)
