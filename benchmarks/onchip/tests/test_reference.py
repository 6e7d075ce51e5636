"""The reference's own S2FP8 (paper Eq. 3-5), which ``control.py
--emulate`` puts at the program's sites: its FP8 rounding against the
float8_e5m2 cast of ml_dtypes, its statistics against the paper's
formula, and its delayed statistics handed out as its state's
cotangent."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

import tiny

ref = tiny.harness.load_module(tiny.ONCHIP / "configs" / "dense_decoder.py")


def test_e5m2_rounding_matches_the_cast():
    rng = np.random.default_rng(0)
    x = np.concatenate([
        rng.standard_normal(20000) * 2.0 ** rng.integers(-20, 16, 20000),
        [0.0, 2.0 ** -16, 3 * 2.0 ** -17, 57344.0, -57344.0, 2.0 ** -14,
         1.125, 1.375, -1.625]]).astype(np.float32)
    x = x[np.abs(x) <= 57344.0]          # the cast overflows past it
    want = x.astype(ml_dtypes.float8_e5m2).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(ref._e5m2(jnp.asarray(x))),
                                  want)
    # where stale statistics push a value past the largest, it saturates
    assert float(ref._e5m2(jnp.float32(-1e6))) == -57344.0


def test_stats_are_eq_3_4():
    x = jnp.asarray([0.0, 0.25, -1.0, 4.0, 16.0], jnp.float32)
    mu, m = np.mean(np.log2([0.25, 1.0, 4.0, 16.0])), 4.0
    alpha, beta = ref._s2fp8_stats(x)
    assert float(alpha) == np.float32(15.0 / (m - mu))
    np.testing.assert_allclose(float(beta), -15.0 / (m - mu) * mu,
                               rtol=1e-6)
    # the transformed largest magnitude sits at 2^15, zeros stay zero
    y = ref._s2fp8_round(x, alpha, beta)
    assert float(y[0]) == 0.0
    np.testing.assert_allclose(np.asarray(y[1:]), np.asarray(x[1:]),
                               rtol=2.0 ** -3)


def test_fresh_site_hands_back_its_stats_and_stale_site_keeps_them():
    x = jnp.linspace(-3.0, 5.0, 64)
    st = jnp.asarray([1.0, 0.0, 1.0, 0.0], jnp.float32)

    def f(x, st, refresh):
        return jnp.sum(ref._s2fp8_site(x, st, refresh) * x)

    _, new = jax.grad(f, (0, 1))(x, st, True)
    a, b = ref._s2fp8_stats(x)
    assert np.allclose(np.asarray(new[:2]), [float(a), float(b)])
    gx, none = jax.grad(f, (0, 1))(x, new, False)
    assert not np.any(np.asarray(none))
    # with the handed-back stats, a stale site truncates as a fresh one
    np.testing.assert_array_equal(
        np.asarray(ref._s2fp8_site(x, new, False)),
        np.asarray(ref._s2fp8_site(x, st, True)))
