"""The plain reference's logistic line search reads the objective's change
row by row, so it still sees a decrease that the f32 rounding of the
objective's total hides."""
import jax
import jax.numpy as jnp
import numpy as np

import reference


def test_change_is_exact_where_totals_lose_it():
    rng = np.random.default_rng(5)
    m, n = 100_000, 8
    A = rng.standard_normal((m, n)).astype(np.float32) / np.sqrt(m)
    x = (30.0 * rng.standard_normal(n)).astype(np.float32)
    b = np.sign(A @ x + 0.05 * rng.standard_normal(m)).astype(np.float32)
    d = (1e-3 * rng.standard_normal(n)).astype(np.float32)
    s, Ad = A @ x, A @ d
    ig = np.float32(0.02)

    def f64(xx):
        z = -b.astype(np.float64) * (A.astype(np.float64) @ xx)
        return np.sum(np.logaddexp(0.0, z)) + 0.5 * 0.02 * np.sum(xx * xx)

    for t in (1.0, 1e-2, 1e-4):
        want = f64(x + np.float64(t) * d) - f64(x.astype(np.float64))
        got = float(reference._change(jnp.asarray(s), jnp.asarray(b),
                                      jnp.asarray(Ad), jnp.asarray(x),
                                      jnp.asarray(d), jnp.float32(t), ig))
        totals = float(
            jnp.sum(jax.nn.softplus(-b * (s + t * Ad)))
            + 0.5 * ig * jnp.sum((x + t * d) ** 2)
            - jnp.sum(jax.nn.softplus(-b * s)) - 0.5 * ig * jnp.sum(x * x))
        assert abs(got - want) <= 1e-3 * abs(want), (t, got, want)
        if t == 1e-4:
            # the difference of two f32 totals is off by more than the
            # change itself
            assert abs(totals - want) > abs(want), (totals, want)
