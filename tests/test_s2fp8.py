"""Core S2FP8 format tests: Eq. 1–5 invariants + hypothesis property tests.

The property tests need ``hypothesis``; when it is absent they skip
cleanly (a single placeholder reports the skip) so the deterministic
tier-1 tests always collect and run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # pragma: no cover - env-dependent
    given = settings = st = None

from repro.core import fp8, s2fp8

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# deterministic unit tests
# ---------------------------------------------------------------------------

def test_fp8_matches_paper_table_a1():
    # max normal (1 - 2^-3) * 2^16 = 57344; min subnormal 2^-16; eps 2^-3
    assert fp8.E5M2_MAX == (1 - 2.0 ** -3) * 2 ** 16
    # (1 + 2^-3 is an RNE tie — rounds to even; use the exact grid point 1.25)
    x = jnp.array([57344.0, 2.0 ** -16, 1.25], jnp.float32)
    t = fp8.truncate_e5m2(x)
    np.testing.assert_allclose(np.asarray(t), np.asarray(x))
    # overflow -> inf (raw FP8's failure mode, deliberately preserved)
    assert np.isinf(float(fp8.truncate_e5m2(jnp.float32(1e6))))
    # underflow of tiny values -> 0
    assert float(fp8.truncate_e5m2(jnp.float32(1e-30))) == 0.0


def _fp8_sweep(qdtype):
    """Random f32 bit patterns plus every grid point of ``qdtype``, every
    midpoint between neighbours (the ties) and the specials."""
    rng = np.random.default_rng(0)
    x = rng.integers(0, 2 ** 32, 200_000, dtype=np.uint64).astype(
        np.uint32).view(np.float32)
    grid = np.arange(256, dtype=np.uint8).view(qdtype).astype(np.float64)
    grid = np.unique(grid[np.isfinite(grid)])
    mids = (grid[1:] + grid[:-1]) / 2
    near = np.concatenate([np.nextafter(mids, -np.inf),
                           np.nextafter(mids, np.inf)])
    special = [0.0, -0.0, np.inf, -np.inf, np.nan, 61440.0, 464.0, 1e-30]
    return np.concatenate([x] + [np.asarray(a, np.float32)
                                 for a in (grid, mids, near, special)])


@pytest.mark.parametrize("fn,qdtype", [
    (fp8.truncate_e5m2, jnp.float8_e5m2),
    (fp8.truncate_e4m3, jnp.float8_e4m3fn),
])
def test_fp8_truncate_bitwise_equals_cast_round_trip(fn, qdtype):
    """The f32-arithmetic rounding is the FP8 cast round trip, bit for bit,
    over subnormals, ties, overflow, infinities and NaN (the sign of zero
    included)."""
    x = _fp8_sweep(qdtype)
    want = x.astype(qdtype).astype(np.float32)
    got = np.asarray(jax.jit(fn)(jnp.asarray(x)))
    nan = np.isnan(want)
    np.testing.assert_array_equal(np.isnan(got), nan)
    np.testing.assert_array_equal(got[~nan].view(np.uint32),
                                  want[~nan].view(np.uint32))


def test_stats_satisfy_eq2():
    """alpha/beta must give log2|Y| zero-mean and max exactly 15 (Eq. 2)."""
    key = jax.random.PRNGKey(0)
    for scale in [1e-8, 1.0, 1e6]:
        x = jax.random.normal(key, (4096,)) * scale
        alpha, beta = s2fp8.compute_stats(x)
        y = s2fp8._forward_map(x, alpha, beta)
        logy = np.log2(np.abs(np.asarray(y[y != 0])))
        assert abs(logy.mean()) < 1e-2
        np.testing.assert_allclose(logy.max(), 15.0, atol=1e-3)


def test_eq4_alpha_beta_closed_form():
    x = jnp.array([0.5, 2.0, 8.0], jnp.float32)
    logx = np.log2(np.abs(np.asarray(x)))
    mu, m = logx.mean(), logx.max()
    alpha, beta = s2fp8.compute_stats(x)
    np.testing.assert_allclose(float(alpha), 15.0 / (m - mu), rtol=1e-5)
    np.testing.assert_allclose(float(beta), -float(alpha) * mu, rtol=1e-5)


def test_roundtrip_error_law():
    """X-domain log2 error <= e5m2 worst-case log error / alpha."""
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (8192,)) * 1e-6
    alpha, _ = s2fp8.compute_stats(x)
    t = np.asarray(s2fp8.truncate_value(x))
    xn = np.asarray(x)
    nz = t != 0
    logerr = np.abs(np.log2(np.abs(t[nz])) - np.log2(np.abs(xn[nz])))
    # worst case in Y-log domain is 1 (denormal region); typical is 2^-3
    assert logerr.max() <= 1.05 / float(alpha)


def test_out_of_range_tensors_survive():
    """The paper's headline: tensors far outside FP8 range survive S2FP8."""
    for scale in [1e-30, 1e-12, 1e12, 1e30]:
        x = jax.random.normal(jax.random.PRNGKey(2), (1024,)) * scale
        t = np.asarray(s2fp8.truncate_value(x))
        xn = np.asarray(x)
        nz = t != 0
        assert nz.mean() > 0.9                       # almost nothing flushed
        rel = np.abs(t[nz] - xn[nz]) / np.abs(xn[nz])
        assert np.median(rel) < 0.05
        # raw FP8 destroys the same tensor
        raw = np.asarray(fp8.truncate_e5m2(x))
        destroyed = (~np.isfinite(raw)) | (raw == 0)
        assert destroyed.mean() > 0.9


def test_zeros_and_signs():
    x = jnp.array([0.0, -0.0, 1.5, -1.5, 0.0], jnp.float32)
    t = np.asarray(s2fp8.truncate_value(x))
    assert (t[[0, 1, 4]] == 0).all()
    assert t[2] > 0 and t[3] < 0
    np.testing.assert_allclose(t[2], -t[3])


def test_degenerate_constant_tensor():
    x = jnp.full((128,), 3.14159, jnp.float32)
    t = np.asarray(s2fp8.truncate_value(x))
    np.testing.assert_allclose(t, 3.14159, rtol=1e-2)


def test_all_zero_tensor():
    t = s2fp8.truncate_value(jnp.zeros((64,)))
    assert (np.asarray(t) == 0).all()


def test_quantize_dequantize_storage():
    x = jax.random.normal(jax.random.PRNGKey(3), (256, 128)) * 1e-4
    q = s2fp8.quantize(x)
    assert q.payload.dtype == jnp.float8_e5m2
    d = s2fp8.dequantize(q)
    direct = s2fp8.truncate_value(x)
    np.testing.assert_allclose(np.asarray(d), np.asarray(direct), rtol=1e-6)


def test_nbytes_payload_counts_stats_once():
    """Wire size = 1 byte per element + exactly 8 bytes for the single
    (alpha, beta) f32 pair — regardless of rank."""
    for shape in [(64, 32), (7,), (3, 4, 5)]:
        q = s2fp8.quantize(jnp.ones(shape))
        n_elems = int(np.prod(shape))
        assert q.nbytes_payload == n_elems + 8
        # the stats overhead is a fixed 8 bytes, not per-element or doubled
        assert q.nbytes_payload - n_elems == 8


def test_ste_gradient_identity():
    x = jax.random.normal(jax.random.PRNGKey(4), (64,))
    g = jax.grad(lambda v: jnp.sum(s2fp8.truncate_ste(v) * 3.0))(x)
    np.testing.assert_allclose(np.asarray(g), 3.0)


def test_bidir_gradient_is_truncated():
    x = jax.random.normal(jax.random.PRNGKey(5), (512,))
    cot = jax.random.normal(jax.random.PRNGKey(6), (512,)) * 1e-9
    _, vjp = jax.vjp(s2fp8.truncate_bidir, x)
    (g,) = vjp(cot)
    expect = s2fp8.truncate_value(cot)
    np.testing.assert_allclose(np.asarray(g), np.asarray(expect), rtol=1e-6)


# ---------------------------------------------------------------------------
# hypothesis property tests (skip cleanly when hypothesis is absent)
# ---------------------------------------------------------------------------

if st is None:
    def test_property_suite_requires_hypothesis():
        """Placeholder: reports the property suite as skipped."""
        pytest.importorskip("hypothesis")


_F32_BIG = 1.0000000200408773e+20     # exactly representable in f32
finite_arrays = st.lists(
    st.floats(min_value=-_F32_BIG, max_value=_F32_BIG, allow_nan=False,
              allow_infinity=False, width=32),
    min_size=2, max_size=256) if st is not None else None


if st is not None:
    @settings(max_examples=60, deadline=None)
    @given(finite_arrays)
    def test_prop_roundtrip_finite_and_sign_preserving(vals):
        x = jnp.asarray(vals, jnp.float32)
        t = np.asarray(s2fp8.truncate_value(x))
        assert np.isfinite(t).all()                   # S2FP8 never overflows
        xn = np.asarray(x)
        nz = (t != 0) & (xn != 0)
        assert (np.sign(t[nz]) == np.sign(xn[nz])).all()
        # magnitudes never exceed the tensor max (max maps to exactly 2^15 in Y)
        if nz.any():
            assert np.abs(t).max() <= np.abs(xn).max() * 1.2

    @settings(max_examples=60, deadline=None)
    @given(finite_arrays, st.floats(min_value=-30, max_value=30))
    def test_prop_scale_covariance(vals, log_scale):
        """S2FP8 is (approximately) scale-covariant: T(c*x) ~ c*T(x) for c=2^k.

        Power-of-two scaling shifts mu and m equally -> identical alpha,
        shifted beta -> identical quantization grid in the scaled domain.
        """
        c = float(2.0 ** round(log_scale))
        x = jnp.asarray(vals, jnp.float32)
        # guard in f32 (the model's arithmetic): scaling must not push any
        # element into f32 overflow or the subnormal flush region — those are
        # f32 edge effects, not properties of the S2FP8 format.
        xc32 = np.asarray(x, np.float32) * np.float32(c)
        if not np.isfinite(xc32).all():
            return
        nz = np.asarray(x) != 0
        if (np.abs(xc32[nz]) < 1e-30).any() or (np.abs(xc32[nz]) > 1e30).any():
            return
        t1 = np.asarray(s2fp8.truncate_value(x)) * c
        t2 = np.asarray(s2fp8.truncate_value(x * c))
        mask = np.isfinite(t1) & (np.abs(t1) > 0) & (t2 != 0)
        np.testing.assert_allclose(t1[mask], t2[mask], rtol=1e-3)

    @settings(max_examples=40, deadline=None)
    @given(finite_arrays)
    def test_prop_idempotent(vals):
        """Truncating an already-truncated tensor changes (almost) nothing.

        Exact idempotence does not hold (stats move once flushed values drop
        out), but surviving values must stay within one quantization step.
        """
        x = jnp.asarray(vals, jnp.float32)
        t1 = s2fp8.truncate_value(x)
        t2 = np.asarray(s2fp8.truncate_value(t1))
        t1 = np.asarray(t1)
        nz = (t1 != 0) & (t2 != 0)
        if nz.any():
            alpha, _ = s2fp8.compute_stats(t1)
            logerr = np.abs(np.log2(np.abs(t2[nz])) - np.log2(np.abs(t1[nz])))
            assert logerr.max() <= 1.1 / max(float(alpha), 1e-3)

    @settings(max_examples=40, deadline=None)
    @given(st.integers(min_value=0, max_value=2 ** 31 - 1),
           st.sampled_from([1e-12, 1e-4, 1.0, 1e4, 1e12]))
    def test_prop_relative_error_bounded_for_gaussians(seed, scale):
        x = jax.random.normal(jax.random.PRNGKey(seed), (512,)) * scale
        t = np.asarray(s2fp8.truncate_value(x))
        xn = np.asarray(x)
        nz = t != 0
        rel = np.abs(t[nz] - xn[nz]) / np.abs(xn[nz])
        assert np.median(rel) < 0.05
