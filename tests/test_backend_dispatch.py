"""Numerics-backend registry + shape-generalizing kernel dispatch.

Core acceptance property of the refactor: the pallas backend (interpret
mode off-TPU) is BITWISE-identical to the ref backend — on odd ranks,
ragged shapes, and degenerate tensors — because its default stats mode
shares the reference reduction and the fused kernel replays the exact
elementwise op sequence.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend as nbackend
from repro.core import s2fp8
from repro.core.policy import make_policy
from repro.kernels import dispatch, ops, ref

jax.config.update("jax_platform_name", "cpu")

ODD_SHAPES = [(257,), (130, 70), (3, 5, 7), (2, 3, 4, 5)]


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_contents():
    names = nbackend.available_backends()
    assert "ref" in names and "pallas" in names and "pallas_fused" in names
    assert nbackend.get_backend("ref").name == "ref"
    assert nbackend.get_backend("pallas").name == "pallas"
    # "auto"/None resolve to the platform default (ref on CPU)
    assert nbackend.get_backend("auto").name == nbackend.default_backend_name()
    assert nbackend.get_backend(None).name == nbackend.default_backend_name()


def test_registry_rejects_unknown_and_duplicate():
    with pytest.raises(KeyError):
        nbackend.get_backend("cuda")
    with pytest.raises(ValueError):
        nbackend.register_backend("ref", nbackend.RefBackend())


def test_policy_rejects_unknown_backend():
    with pytest.raises(ValueError):
        make_policy("s2fp8", backend="int4")


# ---------------------------------------------------------------------------
# ref vs pallas(interpret) equivalence — the acceptance criterion
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", ODD_SHAPES)
@pytest.mark.parametrize("scale", [1e-8, 1.0, 1e8])
def test_truncate_bitwise_identical_odd_shapes(shape, scale):
    x = jax.random.normal(jax.random.PRNGKey(0), shape) * scale
    r = np.asarray(nbackend.get_backend("ref").truncate(x))
    p = np.asarray(nbackend.get_backend("pallas").truncate(x))
    np.testing.assert_array_equal(p, r)


@pytest.mark.parametrize("fmt", ["e5m2", "e4m3"])
def test_truncate_bitwise_identical_both_formats(fmt):
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 48)) * 1e-4
    r = np.asarray(nbackend.get_backend("ref").truncate(x, fmt=fmt))
    p = np.asarray(nbackend.get_backend("pallas").truncate(x, fmt=fmt))
    np.testing.assert_array_equal(p, r)


def test_truncate_degenerate_tensors():
    pal = nbackend.get_backend("pallas")
    # all-zero: stays exactly zero
    z = np.asarray(pal.truncate(jnp.zeros((37, 5))))
    assert (z == 0).all()
    # constant-magnitude: pure shift, values survive to ~1%
    c = np.asarray(pal.truncate(jnp.full((33, 9), 3.14159)))
    np.testing.assert_allclose(c, 3.14159, rtol=1e-2)
    r = np.asarray(nbackend.get_backend("ref").truncate(jnp.full((33, 9), 3.14159)))
    np.testing.assert_array_equal(c, r)


def _payload_policy_matches_ref_chain(got, x, w, layout):
    """``got`` is the pallas payload policy's GEMM of ``x`` and ``w``.
    Its operand payloads dequantize to the ref truncations bit for bit;
    its raw GEMM lies within the f32 accumulation-order bound of the ref
    chain's XLA dot (the kernel sums K over a lane-padded tile); and
    ``got`` is the ref truncation of that raw GEMM under its own exact
    stats, bit for bit, hence inside the truncation bracket of the ref
    dot."""
    rb, pb = nbackend.get_backend("ref"), nbackend.get_backend("pallas")
    qx, qw = pb.quantize(x), pb.quantize(w)
    tx, tw = rb.truncate(x), rb.truncate(w)
    np.testing.assert_array_equal(np.asarray(pb.dequantize(qx)),
                                  np.asarray(tx))
    np.testing.assert_array_equal(np.asarray(pb.dequantize(qw)),
                                  np.asarray(tw))
    raw = pb.qmatmul(qx, qw, layout=layout)
    fig4 = jax.lax.dot_general(tx, tw, ref.GEMM_CONTRACT[layout],
                               preferred_element_type=jnp.float32)
    bound = ref.dot_error_bound(ref.GEMM_SPECS[layout], tx, tw)
    assert (np.abs(np.asarray(raw) - np.asarray(fig4)) <= bound).all()
    so = rb.compute_stats(raw)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(rb.truncate(raw, stats=so)))
    lo, hi = ref.truncation_bracket(
        fig4, bound, lambda y: rb.truncate(jnp.asarray(y), stats=so))
    assert ((lo <= got) & (got <= hi)).all()


@pytest.mark.parametrize("gemm_mode", ["fig4", "auto"])
def test_policy_s2fp8_pallas_bitwise_identical_to_ref(gemm_mode):
    """Policy(mode='s2fp8') routed through the pallas backend vs the ref
    backend: GEMM results and truncated cotangents, eager and jitted.

    ``fig4``: both sides take the Fig. 4 chain, so the GEMM between the
    truncations is the same XLA dot and every result is identical bit
    for bit.  ``auto`` resolves to the payload GEMM on pallas: identical
    to the ref chain bit for bit given its raw GEMM, which differs from
    the XLA dot only in summation order
    (:func:`_payload_policy_matches_ref_chain`)."""
    a = jax.random.normal(jax.random.PRNGKey(2), (66, 34)) * 1e-7
    b = jax.random.normal(jax.random.PRNGKey(3), (34, 18)) * 1e-7
    pr = make_policy("s2fp8", backend="ref")
    pp = make_policy("s2fp8", backend="pallas", gemm_mode=gemm_mode)
    assert pp.uses_payload_gemm == (gemm_mode == "auto")
    cot = jax.random.normal(jax.random.PRNGKey(4), (66, 18)) * 1e-9
    y = np.asarray(pp.dot(a, b))
    _, vr = jax.vjp(lambda a_: pr.dot(a_, b), a)
    _, vp = jax.vjp(lambda a_: pp.dot(a_, b), a)
    da = np.asarray(vp(cot)[0])
    f = jax.jit(lambda a_, b_: pp.dot(a_, b_))
    if gemm_mode == "fig4":
        np.testing.assert_array_equal(y, np.asarray(pr.dot(a, b)))
        np.testing.assert_array_equal(da, np.asarray(vr(cot)[0]))
    else:
        _payload_policy_matches_ref_chain(y, a, b, "nn")
        # dA = GEMM(g, B^T) on the truncated cotangent and B payload
        _payload_policy_matches_ref_chain(da, cot, b, "nt")
    # and under jit: the same kernels, the same bits
    np.testing.assert_array_equal(np.asarray(f(a, b)), y)


def test_fused_stats_mode_float_parity():
    """The two-phase in-kernel stats path: float-tolerance parity (the
    blocked reduction order differs from the monolithic one)."""
    x = jax.random.normal(jax.random.PRNGKey(5), (128, 96)) * 1e5
    r = np.asarray(nbackend.get_backend("ref").truncate(x))
    p = np.asarray(nbackend.get_backend("pallas_fused").truncate(x))
    # zero sets (flush-to-zero boundary) agree except at stats-rounding edges
    assert ((r == 0) == (p == 0)).mean() > 0.995
    nz = (r != 0) & (p != 0)
    np.testing.assert_allclose(p[nz], r[nz], rtol=1e-3)


# ---------------------------------------------------------------------------
# storage path: quant / dequant / qmatmul on ragged + odd-rank tensors
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", ODD_SHAPES)
def test_quant_dequant_odd_shapes(shape):
    x = jax.random.normal(jax.random.PRNGKey(6), shape) * 1e-4
    pal = nbackend.get_backend("pallas")
    t = pal.quantize(x)
    assert t.payload.shape == x.shape
    tr = s2fp8.quantize(x)
    np.testing.assert_allclose(float(t.alpha), float(tr.alpha), rtol=1e-4)
    np.testing.assert_allclose(float(t.beta), float(tr.beta),
                               rtol=1e-4, atol=1e-3)
    dk = np.asarray(pal.dequantize(t))
    dr = np.asarray(s2fp8.dequantize(tr))
    mask = (dk != 0) & (dr != 0)
    np.testing.assert_allclose(dk[mask], dr[mask], rtol=0.2)


def test_qmatmul_non_divisible_shapes():
    a = jax.random.normal(jax.random.PRNGKey(7), (130, 70))
    b = jax.random.normal(jax.random.PRNGKey(8), (70, 33))
    pal = nbackend.get_backend("pallas")
    ta, tb = pal.quantize(a), pal.quantize(b)
    out = np.asarray(pal.qmatmul(ta, tb))
    assert out.shape == (130, 33)
    exp = np.asarray(ref.s2fp8_matmul_ref(ta.payload, ta.alpha, ta.beta,
                                          tb.payload, tb.alpha, tb.beta))
    np.testing.assert_allclose(out, exp, rtol=1e-4, atol=1e-5)


def test_ops_wrappers_any_rank():
    x = jax.random.normal(jax.random.PRNGKey(9), (3, 5, 7)) * 1e-3
    # forced-pallas path must accept non-2-D now
    p, a, b = ops.s2fp8_quant(x, use_pallas=True)
    assert p.shape == x.shape
    d = ops.s2fp8_dequant(p, a, b, use_pallas=True)
    assert d.shape == x.shape
    t = ops.s2fp8_truncate(x, use_pallas=True)
    np.testing.assert_array_equal(
        np.asarray(t), np.asarray(nbackend.get_backend("ref").truncate(x)))


def test_policy_qdot_payload_domain_gemm():
    a = jax.random.normal(jax.random.PRNGKey(14), (66, 40)) * 1e-6
    b = jax.random.normal(jax.random.PRNGKey(15), (40, 24)) * 1e-6
    out = np.asarray(make_policy("s2fp8", backend="pallas").qdot(a, b))
    exact = np.asarray(jnp.dot(a, b))
    assert np.corrcoef(out.ravel(), exact.ravel())[0, 1] > 0.99
    # non-s2fp8 modes fall back to dot
    f32 = np.asarray(make_policy("fp32").qdot(a, b))
    np.testing.assert_array_equal(f32, np.asarray(jnp.dot(a, b)))
    # e4m3 storage parity: same path, e4m3 payloads (tests/test_qdot_train.py
    # covers the format in depth)
    out4 = np.asarray(make_policy("s2fp8_e4m3", backend="pallas").qdot(a, b))
    assert np.corrcoef(out4.ravel(), exact.ravel())[0, 1] > 0.99


def test_blocked_2d_roundtrip_exact():
    for shape in ODD_SHAPES:
        x = jax.random.normal(jax.random.PRNGKey(10), shape)
        x2 = dispatch.as_blocked_2d(x)
        assert x2.ndim == 2
        assert x2.shape[0] % min(256, x2.shape[0]) == 0
        back = dispatch.from_blocked_2d(x2, x.shape)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


def test_blocked_2d_non_dividing_block_width():
    """A block width that does not divide the flattened lane must not
    interleave padding mid-row — the lane widens instead, so the
    flatten-and-slice inverse stays exact and truncation matches the ref."""
    x = jax.random.normal(jax.random.PRNGKey(17), (3, 5, 701)) * 1e-4
    for block in [(256, 384), (8, 640), (1024, 512)]:
        x2 = dispatch.as_blocked_2d(x, block)
        back = dispatch.from_blocked_2d(x2, x.shape)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(x))
        out = dispatch.truncate_nd(x, block=block)
        ref_out = nbackend.get_backend("ref").truncate(x)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_out))


# ---------------------------------------------------------------------------
# delayed stats
# ---------------------------------------------------------------------------

def test_truncate_delayed_functional():
    be = nbackend.get_backend(None)
    x = jax.random.normal(jax.random.PRNGKey(11), (64, 32)) * 1e-5
    y0, stats = nbackend.truncate_delayed(x, None)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(be.truncate(x)))
    # reuse: same stats object threads through, output uses stale stats
    x2 = x * 1.01
    y1, stats1 = nbackend.truncate_delayed(x2, stats, refresh=False)
    assert stats1 is stats
    np.testing.assert_array_equal(
        np.asarray(y1), np.asarray(be.truncate(x2, stats=stats)))
    # refresh recomputes
    _, stats2 = nbackend.truncate_delayed(x2, stats, refresh=True)
    assert float(stats2[1]) != float(stats[1])


def test_delayed_stats_cache_refresh_cadence():
    cache = nbackend.DelayedStatsCache(backend="ref", refresh_every=4)
    x = jax.random.normal(jax.random.PRNGKey(12), (128,)) * 1e-6
    outs = [cache.truncate(x * (1 + 0.001 * i), "g", i) for i in range(9)]
    assert all(np.isfinite(np.asarray(o)).all() for o in outs)
    # steps 0..3 share the step-0 stats; step 4 refreshed
    assert cache._last_refresh["g"] == 8
    with pytest.raises(ValueError):
        nbackend.DelayedStatsCache(refresh_every=0)


def test_delayed_stats_saturate_not_overflow_on_narrow_distributions():
    """Narrow-distribution tensors get a huge alpha; stale stats after an
    upward drift would push the forward image past e5m2's max finite.
    The clamp must saturate (finite) rather than overflow to inf — on
    both backends, identically."""
    noise = 1.0 + 1e-3 * jax.random.normal(jax.random.PRNGKey(16), (64,))
    x = 3.0 * noise                                   # near-constant magnitude
    _, stats = nbackend.truncate_delayed(x, None)
    drifted = x * 1.02                                # 2% upward drift
    for name in ("ref", "pallas"):
        y, _ = nbackend.truncate_delayed(drifted, stats, refresh=False,
                                         backend=name)
        assert np.isfinite(np.asarray(y)).all(), name
    yr, _ = nbackend.truncate_delayed(drifted, stats, refresh=False,
                                      backend="ref")
    yp, _ = nbackend.truncate_delayed(drifted, stats, refresh=False,
                                      backend="pallas")
    np.testing.assert_array_equal(np.asarray(yp), np.asarray(yr))


def test_quantize_stale_stats_saturates_on_both_backends():
    """quantize(stats=...) with stale stats after upward drift must clamp
    the payload at e5m2 max finite (no inf) — identically on ref and
    pallas (the apply kernel mirrors the reference clamp)."""
    noise = 1.0 + 1e-3 * jax.random.normal(jax.random.PRNGKey(18), (64,))
    x = 3.0 * noise
    stats = nbackend.get_backend("ref").compute_stats(x)
    drifted = x * 1.02
    payloads = []
    for name in ("ref", "pallas"):
        t = nbackend.get_backend(name).quantize(drifted, stats=stats)
        p32 = np.asarray(t.payload).astype(np.float32)
        assert np.isfinite(p32).all(), name
        payloads.append(np.asarray(t.payload).view(np.uint8))
    np.testing.assert_array_equal(payloads[0], payloads[1])


def test_delayed_stats_accuracy_under_drift():
    """Stale-by-k stats on a slowly drifting tensor stay accurate — the
    premise that makes the amortization safe."""
    key = jax.random.PRNGKey(13)
    x = jax.random.normal(key, (512,)) * 1e-6
    _, stats = nbackend.truncate_delayed(x, None)
    drifted = x * 1.05                                # 5% scale drift
    y_stale, _ = nbackend.truncate_delayed(drifted, stats, refresh=False)
    xn, yn = np.asarray(drifted), np.asarray(y_stale)
    nz = yn != 0
    rel = np.abs(yn[nz] - xn[nz]) / np.abs(xn[nz])
    assert np.median(rel) < 0.06
