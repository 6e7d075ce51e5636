"""Batched payload-domain contractions (ISSUE 4).

Acceptance anchors:

  * the planner (backend.plan_einsum / plan_qdot_general) maps the MoE
    expert einsums (``ecd,edf->ecf``, broadcast-B ``becd,edf->becf``), the
    attention score/value contractions and the dense family onto batched
    payload GEMM plans, and rejects everything the kernels cannot run;
  * batched payload forward == the Fig. 4 chain BITWISE under shared bank
    stats, jitted on the pallas engine (same anchor as the dense PR-3
    tests, now with a batch grid axis);
  * batched NT/TN backward GEMMs match jnp-transposed references, the
    broadcast-B weight gradient sums its broadcast groups correctly;
  * ``Policy.conv`` lowers to the im2col payload GEMM: forward/VJP track
    ``lax.conv_general_dilated`` on strided + SAME/VALID cases and output
    dims are validated against it;
  * MoE einsums and conv route payload-domain under ``gemm_mode="auto"``
    on the pallas backend with ZERO steady-state stats reductions
    (jaxpr-asserted);
  * dtype-routing bugfixes: einsum fallback promotes with
    ``jnp.result_type``, the payload path honors ``output_dtype`` at the
    GEMM boundary, and discovery-step (step-0) forwards run the exact
    payload path instead of a raw f32 dot.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend as nbackend
from repro.core import qdot
from repro.core import s2fp8
from repro.core import statsbank
from repro.core.backend import plan_einsum, plan_qdot_general
from repro.core.policy import Policy, make_policy
from repro.kernels.ref import dot_error_bound, truncation_bracket

jax.config.update("jax_platform_name", "cpu")

CFG = statsbank.StatsConfig(refresh_every=16)


def _warm_state(stats, last=100.0):
    alpha, beta = stats
    return {"alpha": jnp.asarray(alpha, jnp.float32),
            "beta": jnp.asarray(beta, jnp.float32),
            "ema_mu": jnp.float32(0.0), "ema_m": jnp.float32(0.0),
            "last": jnp.float32(last)}


def _shared_entry(spec, a, b, cot=None):
    """Bank entry whose six directions carry exact shared stats for the
    given einsum — the 'same bank stats' premise of the parity anchor.
    Stats are per-tensor reductions, so they are reshape-invariant: the
    same scalars serve the original operands and their plan layouts."""
    sa = s2fp8.compute_stats_jit(a)
    sb = s2fp8.compute_stats_jit(b)
    be = nbackend.get_backend("ref")
    y = jnp.einsum(spec, be.truncate(a, stats=sa), be.truncate(b, stats=sb),
                   preferred_element_type=jnp.float32)
    so = s2fp8.compute_stats_jit(y)
    sg = s2fp8.compute_stats_jit(cot) if cot is not None else so
    return {"a.fwd": _warm_state(sa), "a.bwd": _warm_state(sa),
            "b.fwd": _warm_state(sb), "b.bwd": _warm_state(sb),
            "out.fwd": _warm_state(so), "out.bwd": _warm_state(sg)}, \
        (sa, sb, so, sg)


# ---------------------------------------------------------------------------
# planner decisions
# ---------------------------------------------------------------------------

PLANNED = [
    # spec, a_shape, b_shape, (layout, batch, b_batch)
    ("ecd,edf->ecf", (4, 8, 16), (4, 16, 12), ("nn", 4, 4)),
    ("ecf,efd->ecd", (4, 8, 12), (4, 12, 16), ("nn", 4, 4)),
    ("becd,edf->becf", (2, 4, 8, 16), (4, 16, 12), ("nn", 8, 4)),
    ("bkgqd,bksd->bkgqs", (2, 3, 4, 8, 16), (2, 3, 10, 16), ("nt", 6, 6)),
    ("bkgqs,bksd->bkgqd", (2, 3, 4, 8, 10), (2, 3, 10, 16), ("nn", 6, 6)),
    ("bsd,df->bsf", (2, 6, 16), (16, 8), ("nn", 1, 1)),
    ("km,ksn->msn", (4, 8), (4, 6, 10), ("tn", 1, 1)),    # k first on both
]

REJECTED = [
    ("abc,abc->a", (2, 3, 4), (2, 3, 4)),          # multi-label contraction
    ("ab,bc->ca", (2, 3), (3, 4)),                 # transposed output
    ("abd,dc->bac", (2, 3, 4), (4, 5)),            # permuted free dims
    ("ad,bd->a", (2, 4), (3, 4)),                  # sum over free b
    ("dd,df->df", (4, 4), (4, 5)),                 # repeated label
    ("da,bd->ab", (4, 2), (3, 4)),                 # "tt": no kernel layout
    ("aeb,ecd->abcd", (2, 3, 4), (3, 5, 6)),       # shared label not batch
    ("ecd,def->ecf", (4, 8, 16), (16, 4, 12)),     # batch not leading on b
]


@pytest.mark.parametrize("spec,ash,bsh,want", PLANNED)
def test_planner_accepts(spec, ash, bsh, want):
    plan = plan_einsum(spec, ash, bsh)
    assert plan is not None, spec
    assert (plan.layout, plan.batch, plan.b_batch) == want, (spec, plan)
    # the plan is pure reshapes: running it on dequantized payloads must
    # reproduce jnp.einsum on the same values
    a = jax.random.normal(jax.random.PRNGKey(0), ash) * 1e-3
    b = jax.random.normal(jax.random.PRNGKey(1), bsh) * 1e-3
    be = nbackend.get_backend("ref")
    qa = be.quantize(a.reshape(plan.a2_shape))
    qb = be.quantize(b.reshape(plan.b2_shape))
    out = nbackend.execute_qdot_plan(be, plan, qa, qb)
    exp = jnp.einsum(spec, s2fp8.dequantize(qa).reshape(ash),
                     s2fp8.dequantize(qb).reshape(bsh))
    assert out.shape == exp.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-30)


@pytest.mark.parametrize("spec,ash,bsh", REJECTED)
def test_planner_rejects(spec, ash, bsh):
    assert plan_einsum(spec, ash, bsh) is None, spec
    # ...and the Policy falls back to the Fig. 4 chain without error
    pol = make_policy("s2fp8", backend="ref", gemm_mode="payload")
    a = jax.random.normal(jax.random.PRNGKey(2), ash) * 1e-4
    b = jax.random.normal(jax.random.PRNGKey(3), bsh) * 1e-4
    y = pol.einsum(spec, a, b)
    assert y.shape == jnp.einsum(spec, a, b).shape


def test_plan_qdot_general_batched():
    # leading in-order batch dims plan; permuted/trailing ones do not
    p = plan_qdot_general((3, 4, 8), (3, 8, 5), (((2,), (1,)), ((0,), (0,))))
    assert p is not None and p.batch == 3 and p.layout == "nn"
    assert p.out_shape == (3, 4, 5)
    assert plan_qdot_general((4, 3, 8), (3, 8, 5),
                             (((2,), (1,)), ((1,), (0,)))) is None
    # batched nt / tn orientations
    assert plan_qdot_general((3, 4, 8), (3, 5, 8),
                             (((2,), (2,)), ((0,), (0,)))).layout == "nt"
    assert plan_qdot_general((3, 8, 4), (3, 8, 5),
                             (((1,), (1,)), ((0,), (0,)))).layout == "tn"
    # zero-size dims never plan (no kernel path)
    assert plan_einsum("ecd,edf->ecf", (0, 8, 16), (0, 16, 12)) is None


# ---------------------------------------------------------------------------
# bitwise parity: batched payload == Fig. 4 chain under shared bank stats
# ---------------------------------------------------------------------------

PARITY_SPECS = [
    ("ecd,edf->ecf", (4, 48, 96), (4, 96, 40)),
    ("becd,edf->becf", (2, 3, 32, 64), (3, 64, 24)),
    ("bkgqd,bksd->bkgqs", (2, 2, 3, 16, 32), (2, 2, 24, 32)),
    ("bkgqs,bksd->bkgqd", (2, 2, 3, 16, 24), (2, 2, 24, 32)),
]


@pytest.mark.parametrize("scale", [1e-6, 1.0])
@pytest.mark.parametrize("spec,ash,bsh", PARITY_SPECS)
def test_batched_forward_parity_bitwise_vs_fig4_pallas(spec, ash, bsh, scale):
    """The acceptance anchor, batched: the JITTED banked batched payload
    path (quant kernel -> batched dequant-matmul kernel -> in-VMEM
    epilogue) equals the stage-pinned Fig. 4 chain (truncate kernels,
    materialized intermediates, jnp.einsum) when both consume the same
    bank stats — exactly, wherever the raw contraction sits farther than
    the f32 accumulation-order bound (``dot_error_bound``) from a
    truncation rounding boundary, and otherwise one of the two
    neighbouring grid points (``truncation_bracket``).  The bound is
    needed because the kernel's tile is zero-padded to the 128-lane grid
    and XLA:CPU sums a dot of the padded shape in a different order.

    The FIG4 side is stage-pinned (materialized intermediates) so that
    its raw contraction is one plain einsum the bound is taken around:
    jitting the fig4 chain lets XLA fuse the batched einsum with the
    truncate kernels' layout restores (the documented 1-ulp FMA/fusion
    hazard — kernels/README.md "A note on bitwise parity")."""
    a = jax.random.normal(jax.random.PRNGKey(4), ash) * scale
    b = jax.random.normal(jax.random.PRNGKey(5), bsh) * scale
    plan = plan_einsum(spec, ash, bsh)
    entry, (sa, sb, so, _) = _shared_entry(spec, a, b)
    be = nbackend.get_backend("pallas")
    # stage-pinned Fig. 4: each stage one pallas/compiled program,
    # intermediates materialized
    ta, tb = be.truncate(a, stats=sa), be.truncate(b, stats=sb)
    y_raw = jnp.einsum(spec, ta, tb, preferred_element_type=jnp.float32)
    fig4 = np.asarray(be.truncate(y_raw, stats=so))
    f = qdot._qdot_banked("pallas", "e5m2", CFG, plan)
    payload = jax.jit(lambda a_, b_: f(
        a_.reshape(plan.a2_shape), b_.reshape(plan.b2_shape), entry,
        jnp.float32(0.0), jnp.float32(101.0)).reshape(plan.out_shape))
    yp = np.asarray(payload(a, b))
    lo, hi = truncation_bracket(
        y_raw, dot_error_bound(spec, ta, tb),
        lambda x: be.truncate(jnp.asarray(x), stats=so))
    assert ((lo <= yp) & (yp <= hi)).all()
    assert (lo == hi).mean() > 0.9 and (yp == fig4).mean() > 0.9
    # eager execution of the same path lands in the same bracket: in
    # interpret mode a kernel body is ordinary XLA ops that fuse with
    # their jitted surroundings, so jit vs eager is one more summation
    # order (compiled TPU kernels are opaque custom calls)
    yp_eager = np.asarray(f(a.reshape(plan.a2_shape),
                            b.reshape(plan.b2_shape), entry,
                            jnp.float32(0.0), jnp.float32(101.0)
                            ).reshape(plan.out_shape))
    assert ((lo <= yp_eager) & (yp_eager <= hi)).all()


def test_batched_forward_vs_jitted_fig4_close():
    """The jitted-vs-jitted comparison: XLA may fuse the batched einsum
    differently inside the jitted fig4 chain (1-ulp raw-GEMM wobble that
    survives truncation when the output grid is fine), so this is a
    tolerance assertion — same structure as the dense ref-engine test."""
    spec, ash, bsh = PARITY_SPECS[0][0], PARITY_SPECS[0][1], PARITY_SPECS[0][2]
    a = jax.random.normal(jax.random.PRNGKey(4), ash) * 1e-6
    b = jax.random.normal(jax.random.PRNGKey(5), bsh) * 1e-6
    plan = plan_einsum(spec, ash, bsh)
    entry, (sa, sb, so, _) = _shared_entry(spec, a, b)
    be = nbackend.get_backend("pallas")
    fig4 = jax.jit(lambda a_, b_: be.truncate(
        jnp.einsum(spec, be.truncate(a_, stats=sa), be.truncate(b_, stats=sb),
                   preferred_element_type=jnp.float32), stats=so))
    f = qdot._qdot_banked("pallas", "e5m2", CFG, plan)
    payload = jax.jit(lambda a_, b_: f(
        a_.reshape(plan.a2_shape), b_.reshape(plan.b2_shape), entry,
        jnp.float32(0.0), jnp.float32(101.0)).reshape(plan.out_shape))
    yf, yp = np.asarray(fig4(a, b)), np.asarray(payload(a, b))
    nz = (yf != 0) & (yp != 0)
    np.testing.assert_allclose(yp[nz], yf[nz], rtol=1e-4)


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("spec,ash,bsh", PARITY_SPECS[:2])
def test_batched_vjp_parity_vs_fig4_reference_chain(spec, ash, bsh, backend):
    """Batched backward: dA/dB from the NT/TN batched kernels (broadcast
    groups summed in-kernel for the becd weight grad) match the Fig. 4
    backward computed with jnp transposes and the same shared stats."""
    a = jax.random.normal(jax.random.PRNGKey(6), ash) * 1e-6
    b = jax.random.normal(jax.random.PRNGKey(7), bsh) * 1e-6
    plan = plan_einsum(spec, ash, bsh)
    out_shape = plan.out_shape
    cot = jax.random.normal(jax.random.PRNGKey(8), out_shape) * 1e-8
    entry, (sa, sb, so, sg) = _shared_entry(spec, a, b, cot)
    be = nbackend.get_backend(backend)
    f = qdot._qdot_banked(backend, "e5m2", CFG, plan)
    pred_f, step_f = jnp.float32(0.0), jnp.float32(101.0)

    def run(a_, b_):
        return f(a_.reshape(plan.a2_shape), b_.reshape(plan.b2_shape),
                 entry, pred_f, step_f).reshape(out_shape)

    _, vjp = jax.vjp(run, a, b)
    da, db = vjp(cot)
    # Fig. 4 backward with the same shared stats, via einsum transposes
    lhs, out = spec.split("->")
    la, lb = lhs.split(",")
    g_t = be.truncate(cot, stats=sg)
    a_t, b_t = be.truncate(a, stats=sa), be.truncate(b, stats=sb)
    da_ref = be.truncate(jnp.einsum(f"{out},{lb}->{la}", g_t, b_t,
                                    preferred_element_type=jnp.float32),
                         stats=sa)
    db_ref = be.truncate(jnp.einsum(f"{la},{out}->{lb}", a_t, g_t,
                                    preferred_element_type=jnp.float32),
                         stats=sb)
    np.testing.assert_allclose(np.asarray(da), np.asarray(da_ref),
                               rtol=1e-5, atol=1e-32)
    np.testing.assert_allclose(np.asarray(db), np.asarray(db_ref),
                               rtol=1e-5, atol=1e-32)


def test_batched_nt_tn_layout_kernels_vs_jnp_transposes():
    """The batched NT/TN kernel layouts against explicit jnp batched
    transposes — no payload transpose is ever materialized."""
    g, m, k, n = 5, 40, 24, 18
    a = jax.random.normal(jax.random.PRNGKey(9), (g, m, k)) * 1e-3
    bt = jax.random.normal(jax.random.PRNGKey(10), (g, n, k)) * 1e-3
    at = jax.random.normal(jax.random.PRNGKey(11), (g, k, m)) * 1e-3
    b = jax.random.normal(jax.random.PRNGKey(12), (g, k, n)) * 1e-3
    for name in ("ref", "pallas"):
        be = nbackend.get_backend(name)
        qa, qbt = be.quantize(a), be.quantize(bt)
        out = np.asarray(be.qmatmul_batched(qa, qbt, layout="nt"))
        exp = np.asarray(jnp.einsum("gmk,gnk->gmn", s2fp8.dequantize(qa),
                                    s2fp8.dequantize(qbt)))
        np.testing.assert_allclose(out, exp, rtol=1e-5, atol=1e-11,
                                   err_msg=name)
        qat, qb = be.quantize(at), be.quantize(b)
        out = np.asarray(be.qmatmul_batched(qat, qb, layout="tn"))
        exp = np.asarray(jnp.einsum("gkm,gkn->gmn", s2fp8.dequantize(qat),
                                    s2fp8.dequantize(qb)))
        # atol floor: the batched grid reassociates the K accumulation
        # (1-ulp at near-cancellation elements)
        np.testing.assert_allclose(out, exp, rtol=1e-5, atol=1e-11,
                                   err_msg=name)


def test_broadcast_and_out_batch_reduction():
    """Trailing-aligned broadcast (b slice = g % Gb) and the out_batch
    group reduction (the broadcast weight's gradient) agree between the
    ref oracle and the pallas kernel, and with a dense jnp reference."""
    g, gb, m, k, n = 6, 3, 16, 24, 12
    a = jax.random.normal(jax.random.PRNGKey(13), (g, m, k)) * 1e-3
    b = jax.random.normal(jax.random.PRNGKey(14), (gb, k, n)) * 1e-3
    cot = jax.random.normal(jax.random.PRNGKey(15), (g, m, n)) * 1e-3
    for name in ("ref", "pallas"):
        be = nbackend.get_backend(name)
        qa, qb, qg = be.quantize(a), be.quantize(b), be.quantize(cot)
        da, db_, dg = (s2fp8.dequantize(t) for t in (qa, qb, qg))
        # broadcast forward: slice e of b serves combined steps e, gb+e, ...
        y = np.asarray(be.qmatmul_batched(qa, qb))
        exp = np.asarray(jnp.einsum("xemk,ekn->xemn",
                                    da.reshape(g // gb, gb, m, k), db_
                                    ).reshape(g, m, n))
        np.testing.assert_allclose(y, exp, rtol=1e-5, atol=1e-11,
                                   err_msg=name)
        # out_batch reduction: dB = sum over broadcast groups of A^T g
        db_out = np.asarray(be.qmatmul_batched(qa, qg, layout="tn",
                                               out_batch=gb))
        exp_db = np.asarray(jnp.einsum("xemk,xemn->ekn",
                                       da.reshape(g // gb, gb, m, k),
                                       dg.reshape(g // gb, gb, m, n)))
        # atol floor: the group reduction reassociates the (x, m) sum
        np.testing.assert_allclose(db_out, exp_db, rtol=1e-5, atol=1e-11,
                                   err_msg=name)


def test_batched_residuals_are_payloads_only():
    spec, ash, bsh = "ecd,edf->ecf", (4, 32, 16), (4, 16, 24)
    plan = plan_einsum(spec, ash, bsh)
    entry, _ = _shared_entry(spec, jnp.ones(ash), jnp.ones(bsh))
    f = qdot._qdot_banked("ref", "e5m2", CFG, plan)
    _, res = jax.eval_shape(f.fwd_impl, jnp.zeros(plan.a2_shape),
                            jnp.zeros(plan.b2_shape), entry,
                            jnp.float32(0.0), jnp.float32(1.0))
    leaves = jax.tree_util.tree_leaves(res)
    fp8 = [l for l in leaves if l.dtype == jnp.float8_e5m2]
    assert {l.shape for l in fp8} == {plan.a2_shape, plan.b2_shape}
    for l in leaves:
        if l.dtype == jnp.float32:
            assert np.prod(l.shape, dtype=np.int64) <= 1, l


def test_batched_e4m3_rides_same_path():
    spec, ash, bsh = "ecd,edf->ecf", (3, 16, 24), (3, 24, 8)
    a = jax.random.normal(jax.random.PRNGKey(16), ash) * 1e-5
    b = jax.random.normal(jax.random.PRNGKey(17), bsh) * 1e-5
    pol = make_policy("s2fp8_e4m3", backend="ref", gemm_mode="payload")
    out = np.asarray(pol.einsum(spec, a, b))
    exact = np.asarray(jnp.einsum(spec, a, b))
    assert np.corrcoef(out.ravel(), exact.ravel())[0, 1] > 0.99
    da, db = jax.grad(lambda a_, b_: jnp.sum(pol.einsum(spec, a_, b_) ** 2),
                      argnums=(0, 1))(a, b)
    assert np.isfinite(np.asarray(da)).all() and \
        np.abs(np.asarray(db)).max() > 0


# ---------------------------------------------------------------------------
# im2col conv lowering
# ---------------------------------------------------------------------------

CONV_CASES = [((1, 1), "SAME"), ((2, 2), "SAME"),
              ((1, 1), "VALID"), ((2, 2), "VALID"), ((2, 1), "SAME")]


@pytest.mark.parametrize("stride,padding", CONV_CASES)
def test_conv_im2col_forward_tracks_lax_conv(stride, padding):
    x = jax.random.normal(jax.random.PRNGKey(18), (2, 15, 16, 8)) * 0.1
    k = jax.random.normal(jax.random.PRNGKey(19), (3, 3, 8, 12)) * 0.1
    pol = make_policy("s2fp8", backend="ref", gemm_mode="payload")
    y = pol.conv(x, k, stride=stride, padding=padding)
    exact = jax.lax.conv_general_dilated(
        x, k, window_strides=stride, padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    assert y.shape == exact.shape          # validated against lax.conv dims
    c = np.corrcoef(np.asarray(y).ravel(), np.asarray(exact).ravel())[0, 1]
    assert c > 0.999, (stride, padding, c)


@pytest.mark.parametrize("stride,padding", [((1, 1), "SAME"),
                                            ((2, 2), "VALID")])
def test_conv_im2col_vjp_tracks_lax_conv(stride, padding):
    x = jax.random.normal(jax.random.PRNGKey(20), (2, 12, 12, 6)) * 0.1
    k = jax.random.normal(jax.random.PRNGKey(21), (3, 3, 6, 8)) * 0.1
    pol = make_policy("s2fp8", backend="ref", gemm_mode="payload")

    def loss_pay(x_, k_):
        return jnp.sum(pol.conv(x_, k_, stride=stride, padding=padding) ** 2)

    def loss_exact(x_, k_):
        return jnp.sum(jax.lax.conv_general_dilated(
            x_, k_, window_strides=stride, padding=padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC")) ** 2)

    gp = jax.grad(loss_pay, argnums=(0, 1))(x, k)
    ge = jax.grad(loss_exact, argnums=(0, 1))(x, k)
    for p, e, name in zip(gp, ge, ("dx", "dk")):
        p, e = np.asarray(p), np.asarray(e)
        assert p.shape == e.shape
        c = np.corrcoef(p.ravel(), e.ravel())[0, 1]
        assert c > 0.995, (stride, padding, name, c)


def test_conv_im2col_gemm_parity_bitwise_under_shared_stats():
    """The conv lowering IS the payload GEMM: against the Fig. 4 chain
    applied to the same im2col patches with shared stats, the conv
    forward is bitwise identical (the lowering adds no numerics of its
    own; stride/padding live in the exact zero-pad + gather)."""
    x = jax.random.normal(jax.random.PRNGKey(22), (2, 8, 8, 16)) * 1e-4
    k = jax.random.normal(jax.random.PRNGKey(23), (3, 3, 16, 24)) * 1e-4
    kh, kw, cin, cout = k.shape
    pads = jax.lax.padtype_to_pads(x.shape[1:3], (kh, kw), (1, 1), "SAME")
    xp = jnp.pad(x, ((0, 0),) + tuple(pads) + ((0, 0),))
    b, hp, wp, _ = xp.shape
    oh, ow = hp - kh + 1, wp - kw + 1
    cols = [jax.lax.slice(xp, (0, i, j, 0), (b, i + oh, j + ow, cin))
            for i in range(kh) for j in range(kw)]
    patches = jnp.concatenate(cols, axis=-1)
    w2 = k.reshape(kh * kw * cin, cout)
    sa = s2fp8.compute_stats_jit(patches)
    sb = s2fp8.compute_stats_jit(w2)
    be = nbackend.get_backend("ref")
    y_raw = jnp.dot(be.truncate(patches, stats=sa).reshape(-1, kh * kw * cin),
                    be.truncate(w2, stats=sb),
                    preferred_element_type=jnp.float32)
    so = s2fp8.compute_stats_jit(y_raw)
    fig4 = be.truncate(y_raw, stats=so).reshape(b, oh, ow, cout)
    entry = {"a.fwd": _warm_state(sa), "a.bwd": _warm_state(sa),
             "b.fwd": _warm_state(sb), "b.bwd": _warm_state(sb),
             "out.fwd": _warm_state(so), "out.bwd": _warm_state(so)}
    f = qdot._qdot_banked("ref", "e5m2", CFG)
    pay = f(patches.reshape(-1, kh * kw * cin), w2, entry,
            jnp.float32(0.0), jnp.float32(101.0)).reshape(b, oh, ow, cout)
    np.testing.assert_array_equal(np.asarray(pay), np.asarray(fig4))


def test_conv_explicit_padding_and_fig4_shape_agreement():
    x = jax.random.normal(jax.random.PRNGKey(24), (1, 9, 9, 4)) * 0.1
    k = jax.random.normal(jax.random.PRNGKey(25), (3, 3, 4, 4)) * 0.1
    pol = make_policy("s2fp8", backend="ref", gemm_mode="payload")
    polf = make_policy("s2fp8", backend="ref", gemm_mode="fig4")
    pad = ((2, 1), (0, 2))
    yp = pol.conv(x, k, stride=(2, 1), padding=pad)
    yf = polf.conv(x, k, stride=(2, 1), padding=pad)
    assert yp.shape == yf.shape
    assert np.corrcoef(np.asarray(yp).ravel(),
                       np.asarray(yf).ravel())[0, 1] > 0.999


# ---------------------------------------------------------------------------
# policy routing + dtype bugfixes
# ---------------------------------------------------------------------------

def test_moe_and_conv_route_payload_under_auto_on_pallas():
    """Acceptance: under gemm_mode='auto' on the pallas backend the MoE
    expert einsums and conv run the payload path — their outputs equal
    the forced-payload policy's bitwise, and differ in execution from
    fig4 (payload quantizes patches/operands once)."""
    auto = make_policy("s2fp8", backend="pallas")
    forced = make_policy("s2fp8", backend="pallas", gemm_mode="payload")
    assert auto.uses_payload_gemm
    xe = jax.random.normal(jax.random.PRNGKey(26), (2, 16, 24)) * 1e-4
    we = jax.random.normal(jax.random.PRNGKey(27), (2, 24, 16)) * 1e-4
    np.testing.assert_array_equal(
        np.asarray(auto.einsum("ecd,edf->ecf", xe, we)),
        np.asarray(forced.einsum("ecd,edf->ecf", xe, we)))
    xb = jax.random.normal(jax.random.PRNGKey(28), (2, 2, 16, 24)) * 1e-4
    np.testing.assert_array_equal(
        np.asarray(auto.einsum("becd,edf->becf", xb, we)),
        np.asarray(forced.einsum("becd,edf->becf", xb, we)))
    x = jax.random.normal(jax.random.PRNGKey(29), (1, 8, 8, 8)) * 1e-4
    kk = jax.random.normal(jax.random.PRNGKey(30), (3, 3, 8, 8)) * 1e-4
    np.testing.assert_array_equal(np.asarray(auto.conv(x, kk)),
                                  np.asarray(forced.conv(x, kk)))


def test_einsum_fallback_mixed_dtype_result_type():
    """Satellite bugfix: the einsum fallback must promote with
    jnp.result_type, not silently cast to operands[0].dtype — and dot /
    dot_general must agree, so the same contraction gets the same output
    dtype no matter which API expresses it."""
    a16 = jax.random.normal(jax.random.PRNGKey(31), (4, 8), jnp.bfloat16)
    b32 = jax.random.normal(jax.random.PRNGKey(32), (8, 4), jnp.float32)
    dn = (((1,), (0,)), ((), ()))
    for mode in ("fp32", "bf16", "s2fp8"):
        for gm in (("auto",) if mode != "s2fp8" else ("fig4", "payload")):
            pol = make_policy(mode, backend="ref", gemm_mode=gm)
            want = jnp.result_type(a16, b32)
            assert pol.einsum("md,df->mf", a16, b32).dtype == want, (mode, gm)
            assert pol.dot(a16, b32).dtype == want, (mode, gm)
            assert pol.dot_general(a16, b32, dn).dtype == want, (mode, gm)
        # three-operand fallback too
        pol = make_policy(mode, backend="ref")
        c = jax.random.normal(jax.random.PRNGKey(33), (4,), jnp.float32)
        assert pol.einsum("md,df,m->f", a16, b32, c).dtype == jnp.float32


@pytest.mark.parametrize("mode", ["s2fp8", "s2fp8_e4m3"])
@pytest.mark.parametrize("output_dtype", [None, "bfloat16"])
def test_gemm_mode_dtype_parity(mode, output_dtype):
    """Satellite bugfix: payload and fig4 must agree on output dtype at
    the GEMM boundary for every (mode, output_dtype) combination —
    including the bf16 hillclimb lever, which the payload return now
    honors by rounding the kernel's f32 output through accum_dtype."""
    a = jax.random.normal(jax.random.PRNGKey(34), (8, 16)) * 1e-4
    b = jax.random.normal(jax.random.PRNGKey(35), (16, 8)) * 1e-4
    x = jax.random.normal(jax.random.PRNGKey(36), (1, 8, 8, 4)) * 1e-4
    kk = jax.random.normal(jax.random.PRNGKey(37), (3, 3, 4, 4)) * 1e-4
    pay = Policy(mode=mode, backend="ref", gemm_mode="payload",
                 output_dtype=output_dtype)
    fig = Policy(mode=mode, backend="ref", gemm_mode="fig4",
                 output_dtype=output_dtype)
    assert pay.uses_payload_gemm and not fig.uses_payload_gemm
    assert pay.dot(a, b).dtype == fig.dot(a, b).dtype
    assert pay.einsum("md,df->mf", a, b).dtype == \
        fig.einsum("md,df->mf", a, b).dtype
    assert pay.conv(x, kk).dtype == fig.conv(x, kk).dtype
    if output_dtype == "bfloat16":
        # the boundary rounding really happens: payload output is bf16-
        # representable even though the kernel emitted f32
        y = pay.dot(a, b)
        np.testing.assert_array_equal(
            np.asarray(y), np.asarray(y.astype(jnp.bfloat16), np.float32))


def test_discovery_step_matches_sessionless_exact_path():
    """Satellite bugfix: the discovery-mode forward routes through the
    exact payload path, so a step-0 (discovery) trace produces the same
    loss as a sessionless qdot_train call — not a raw untruncated dot."""
    a = jax.random.normal(jax.random.PRNGKey(38), (16, 32)) * 1e-6
    b = jax.random.normal(jax.random.PRNGKey(39), (32, 8)) * 1e-6
    y_plain = qdot.qdot_train(a, b, backend="ref")
    sess = statsbank.Session(None, 0, CFG, discovery=True)
    statsbank._ACTIVE.session = sess
    try:
        y_disc = qdot.qdot_train(a, b, backend="ref")
    finally:
        statsbank._ACTIVE.session = None
    np.testing.assert_array_equal(np.asarray(y_disc), np.asarray(y_plain))
    assert "qt0" in sess.recorded          # site registration still happens
    # and the raw dot would NOT have matched (truncation is real here)
    raw = jnp.dot(a, b, preferred_element_type=jnp.float32)
    assert not np.array_equal(np.asarray(y_disc), np.asarray(raw))


# ---------------------------------------------------------------------------
# banked training: zero steady-state reductions for MoE einsum + conv nodes
# ---------------------------------------------------------------------------

def _batched_setup():
    key = jax.random.PRNGKey(40)
    params = {
        "we": jax.random.normal(key, (2, 16, 24)) * 1e-3,
        "wd": jax.random.normal(jax.random.fold_in(key, 1), (2, 24, 16)) * 1e-3,
        "ck": jax.random.normal(jax.random.fold_in(key, 2),
                                (3, 3, 4, 4)) * 1e-2,
    }
    batch = {"xe": jax.random.normal(jax.random.fold_in(key, 3),
                                     (2, 32, 16)) * 1e-3,
             "img": jax.random.normal(jax.random.fold_in(key, 4),
                                      (2, 8, 8, 4)) * 1e-2}
    pol = make_policy("s2fp8", backend="ref", gemm_mode="payload")

    def loss_fn(p, b, pol_):
        h = pol_.einsum("ecd,edf->ecf", b["xe"], p["we"])
        h = pol_.einsum("ecf,efd->ecd", h, p["wd"])
        y = pol_.conv(b["img"], p["ck"], stride=(2, 2))
        return jnp.sum(h * h) + jnp.sum(y * y), {}

    return params, batch, pol, loss_fn


def test_batched_banked_training_step_and_refresh_cadence():
    from repro.optim import optimizers, schedules
    from repro.training.trainer import make_train_step
    params, batch, pol, loss_fn = _batched_setup()
    scfg = statsbank.StatsConfig(refresh_every=4)
    bank = statsbank.init_bank(loss_fn, params, batch, pol, scfg)
    # three GEMM nodes (two MoE einsums + the conv), six dirs each
    qt = [k for k in bank if "qt" in k]
    assert len(qt) == 3, sorted(bank)
    for k in qt:
        assert set(bank[k]) == set(statsbank.GEMM_DIRS)
    opt = optimizers.adamw()
    step_fn = jax.jit(make_train_step(loss_fn, opt,
                                      schedules.constant(1e-3), pol,
                                      stats=scfg))
    ost = opt.init(params)
    lasts = []
    for s in range(6):
        params, ost, bank, m = step_fn(params, ost, bank, batch, jnp.int32(s))
        assert np.isfinite(float(m["loss"]))
        lasts.append(float(bank[qt[0]]["out.bwd"]["last"]))
    assert lasts == [0.0, 0.0, 0.0, 0.0, 4.0, 4.0]


def test_zero_stats_reductions_outside_cond_batched():
    """Acceptance: steady-state batched payload bank steps (MoE einsums +
    conv GEMM nodes) run ZERO stats reductions outside lax.cond — the
    jaxpr's outside-cond reduce count equals the fp32 baseline's plus the
    one O(n_sites) bookkeeping min."""
    from repro.optim import optimizers, schedules
    from repro.training.trainer import make_train_step
    params, batch, pol, loss_fn = _batched_setup()
    scfg = statsbank.StatsConfig(refresh_every=4)
    bank = statsbank.init_bank(loss_fn, params, batch, pol, scfg)
    opt = optimizers.adamw()
    sched = schedules.constant(1e-3)
    ost = opt.init(params)
    jx_bank = jax.make_jaxpr(make_train_step(loss_fn, opt, sched, pol,
                                             stats=scfg))(
        params, ost, bank, batch, jnp.int32(0))
    jx_fp32 = jax.make_jaxpr(make_train_step(loss_fn, opt, sched,
                                             make_policy("fp32")))(
        params, ost, batch, jnp.int32(0))
    n_bank = statsbank.count_reductions(jx_bank, include_cond=False)
    n_fp32 = statsbank.count_reductions(jx_fp32, include_cond=False)
    assert n_bank == n_fp32 + 1, (n_bank, n_fp32)


def test_batched_payload_vs_fig4_training_losses_track():
    from repro.optim import optimizers, schedules
    from repro.training.trainer import make_train_step
    params, batch, _, loss_fn = _batched_setup()
    losses = {}
    for gm in ("payload", "fig4"):
        pol = make_policy("s2fp8", backend="ref", gemm_mode=gm)
        scfg = statsbank.StatsConfig(refresh_every=2)
        bank = statsbank.init_bank(loss_fn, params, batch, pol, scfg)
        opt = optimizers.adamw()
        step_fn = jax.jit(make_train_step(loss_fn, opt,
                                          schedules.constant(1e-3), pol,
                                          stats=scfg))
        p, ost = params, opt.init(params)
        hist = []
        for s in range(4):
            p, ost, bank, m = step_fn(p, ost, bank, batch, jnp.int32(s))
            hist.append(float(m["loss"]))
        losses[gm] = hist
    np.testing.assert_allclose(losses["payload"], losses["fig4"], rtol=0.05)


def test_attention_routes_through_policy():
    """models/blocks.py attention goes through the policy: payload mode
    takes the fused flash fast path (ONE qf bank node for the whole
    attention op — no [S, S] score round-trip), fig4 keeps the einsum
    pair as truncation sites — the same dataflow decision as every other
    bilinear op."""
    from repro.models.blocks import full_attention
    q = jax.random.normal(jax.random.PRNGKey(41), (2, 2, 2, 16, 32)) * 0.1
    k = jax.random.normal(jax.random.PRNGKey(42), (2, 2, 16, 32)) * 0.1
    v = jax.random.normal(jax.random.PRNGKey(43), (2, 2, 16, 32)) * 0.1
    outs = {}
    for gm in ("payload", "fig4"):
        pol = make_policy("s2fp8", backend="ref", gemm_mode=gm)
        outs[gm] = np.asarray(full_attention(q, k, v, causal=True,
                                             policy=pol))
    base = np.asarray(full_attention(q, k, v, causal=True))
    for gm, y in outs.items():
        assert y.shape == base.shape
        c = np.corrcoef(y.ravel(), base.ravel())[0, 1]
        assert c > 0.99, (gm, c)
    # discovery sees the fused attention as ONE flash bank node (the
    # einsum pair no longer appears as two qt GEMM nodes)
    pol = make_policy("s2fp8", backend="ref", gemm_mode="payload")
    sess = statsbank.Session(None, 0, CFG, discovery=True)
    statsbank._ACTIVE.session = sess
    try:
        jax.eval_shape(lambda q_, k_, v_: full_attention(
            q_, k_, v_, causal=True, policy=pol), q, k, v)
    finally:
        statsbank._ACTIVE.session = None
    assert sorted(sess.recorded) == ["qf0"]
    assert sess.recorded["qf0"]["dirs"] == statsbank.FLASH_DIRS
