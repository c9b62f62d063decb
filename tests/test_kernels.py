"""Per-kernel validation: shape/dtype sweeps + hypothesis property tests,
allclose against the pure-jnp oracles in repro.kernels.ref (interpret mode
executes the kernel body in Python on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st  # per-test skip when absent

from repro.kernels import ops, ref

jax.config.update("jax_enable_x64", False)


# --------------------------------------------------------------- gram ----
@pytest.mark.parametrize("m,n", [(64, 32), (100, 17), (513, 129), (8, 300)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gram_shapes(m, n, dtype):
    key = jax.random.PRNGKey(m * 1000 + n)
    a = jax.random.normal(key, (m, n), jnp.float32).astype(dtype)
    got = ops.gram(a, block_m=64, block_n=128, interpret=True)
    want = ref.gram_ref(a)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * 10)


def test_gram_xy_rect():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (200, 48))
    y = jax.random.normal(k2, (200, 80))
    got = ops.gram_xy(x, y, block_m=64, block_n=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.gram_xy_ref(x, y)),
                               rtol=1e-5, atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(m=st.integers(1, 200), n=st.integers(1, 150),
       seed=st.integers(0, 2**31 - 1))
def test_gram_property(m, n, seed):
    a = jax.random.normal(jax.random.PRNGKey(seed), (m, n))
    got = np.asarray(ops.gram(a, block_m=32, block_n=32, interpret=True))
    want = np.asarray(ref.gram_ref(a))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    # Gram matrices are symmetric PSD
    np.testing.assert_allclose(got, got.T, rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------ matvec ----
@pytest.mark.parametrize("m,n", [(64, 32), (100, 17), (513, 129), (8, 300)])
@pytest.mark.parametrize("k", [None, 3])
def test_matvec_shapes(m, n, k):
    key = jax.random.PRNGKey(m * 1000 + n)
    k1, k2, k3 = jax.random.split(key, 3)
    a = jax.random.normal(k1, (m, n), jnp.float32)
    x = jax.random.normal(k2, (n,) if k is None else (n, k), jnp.float32)
    y = jax.random.normal(k3, (m,) if k is None else (m, k), jnp.float32)
    got = ops.matvec(a, x, block_m=64, block_n=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.matvec_ref(a, x)),
                               rtol=1e-5, atol=1e-4)
    got_t = ops.rmatvec(a, y, block_m=64, block_n=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got_t),
                               np.asarray(ref.rmatvec_ref(a, y)),
                               rtol=1e-5, atol=1e-4)


def test_normal_matvec_scalar_and_vector_shift():
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    a = jax.random.normal(k1, (70, 45), jnp.float32)
    p = jax.random.normal(k2, (45,), jnp.float32)
    got = ops.normal_matvec(a, p, 1.5, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.normal_matvec_ref(a, p, 1.5)),
                               rtol=1e-4, atol=1e-3)
    shift = jnp.abs(jax.random.normal(k1, (45,))) + 0.1
    got_v = ops.normal_matvec(a, p, shift, interpret=True)
    np.testing.assert_allclose(np.asarray(got_v),
                               np.asarray(ref.normal_matvec_ref(a, p, shift)),
                               rtol=1e-4, atol=1e-3)


@settings(max_examples=15, deadline=None)
@given(m=st.integers(1, 160), n=st.integers(1, 130),
       seed=st.integers(0, 2**31 - 1))
def test_matvec_property(m, n, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.normal(k1, (m, n))
    x = jax.random.normal(k2, (n,))
    got = np.asarray(ops.matvec(a, x, block_m=32, block_n=64, interpret=True))
    np.testing.assert_allclose(got, np.asarray(ref.matvec_ref(a, x)),
                               rtol=1e-4, atol=1e-4)
    # adjoint identity: <A x, A x> == <x, A^T (A x)>
    ax = jnp.asarray(got)
    atax = np.asarray(ops.rmatvec(a, ax, block_m=32, block_n=64,
                                  interpret=True))
    np.testing.assert_allclose(float(jnp.vdot(ax, ax)),
                               float(jnp.vdot(x, jnp.asarray(atax))),
                               rtol=1e-3)


# ------------------------------------------------------- ladder stats ----
@pytest.mark.parametrize("n,B", [
    (100, 8), (4096, 32), (5000, 64), (1, 4),
    # non-aligned shapes: B = 1 (polish probes), B above one lane (pads to
    # 256), n straddling row/block boundaries, full-ladder B = 128
    (129, 1), (127, 128), (1025, 200), (8200, 128), (3, 3),
])
def test_ladder_stats(n, B):
    key = jax.random.PRNGKey(n + B)
    az = jnp.abs(jax.random.normal(key, (n,)))
    thetas = jnp.linspace(0.0, 2.0, B)
    got = ops.ladder_stats(az, thetas, interpret=True)
    assert got.shape == (2, B)
    want = ref.ladder_stats_ref(az, thetas)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-3)


def test_ladder_stats_unsorted_thetas_and_small_block():
    """Rung order must not matter, and the VMEM clamp (small block at big
    B) must not change results."""
    az = jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (3000,)))
    thetas = jax.random.uniform(jax.random.PRNGKey(1), (128,), maxval=2.0)
    got = ops.ladder_stats(az, thetas, interpret=True)
    got_small = ops.ladder_stats(az, thetas, block=8, interpret=True)
    want = ref.ladder_stats_ref(az, thetas)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(got_small), np.asarray(want),
                               rtol=1e-5, atol=1e-3)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 3000), B=st.sampled_from([4, 16, 33]),
       seed=st.integers(0, 2**31 - 1))
def test_ladder_property(n, B, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed))
    az = jnp.abs(jax.random.normal(ks[0], (n,)))
    thetas = jnp.sort(jnp.abs(jax.random.normal(ks[1], (B,))))
    got = np.asarray(ops.ladder_stats(az, thetas, interpret=True))
    want = np.asarray(ref.ladder_stats_ref(az, thetas))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
    # h is non-increasing in theta
    assert np.all(np.diff(got[0]) <= 1e-5)


# ---------------------------------------------------- flash attention ----
@pytest.mark.parametrize("B,S,Hq,Hkv,Dh", [
    (2, 128, 4, 2, 64), (1, 256, 8, 1, 32), (2, 100, 4, 4, 64),
    (1, 384, 6, 2, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_causal(B, S, Hq, Hkv, Dh, dtype):
    ks = jax.random.split(jax.random.PRNGKey(B * S), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, Dh), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh), jnp.float32).astype(dtype)
    got = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                              interpret=True)
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, S, Dh)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, Dh)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, Dh)
    want = ref.flash_attention_flat_ref(qf, kf, vf, causal=True)
    want = want.reshape(B, Hq, S, Dh).transpose(0, 2, 1, 3)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 5)


def test_flash_noncausal():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    B, Sq, Sk, H, Dh = 1, 128, 128, 2, 64
    q = jax.random.normal(ks[0], (B, Sq, H, Dh))
    k = jax.random.normal(ks[1], (B, Sk, H, Dh))
    v = jax.random.normal(ks[2], (B, Sk, H, Dh))
    got = ops.flash_attention(q, k, v, causal=False, block_q=64,
                              block_k=64, interpret=True)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, Dh)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Sk, Dh)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Sk, Dh)
    want = ref.flash_attention_flat_ref(qf, kf, vf, causal=False)
    want = want.reshape(B, H, Sq, Dh).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=1e-4)


def test_flash_matches_model_chunked_path():
    """Kernel agrees with the model zoo's pure-jnp chunked attention."""
    from repro.models.attention import _sdpa_chunked
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    B, S, Hq, Hkv, Dh = 1, 256, 4, 2, 64
    q = jax.random.normal(ks[0], (B, S, Hq, Dh))
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh))
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh))
    got = ops.flash_attention(q, k, v, causal=True, interpret=True)
    want = _sdpa_chunked(q, k, v, 64, 64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=1e-4)


# ------------------------------------------------------ operand layout ----
def test_kernel_operand_relays_out_other_dimension_orders():
    """The TPU row hands the Mosaic kernels a row-major A (one relayout
    when the device stores it otherwise); the default row is identity."""
    from jax.experimental.layout import Format, Layout
    from repro import runtime
    a = jax.random.normal(jax.random.PRNGKey(0), (2, 30, 20))
    at = jax.device_put(a, Format(Layout(major_to_minor=(0, 2, 1)),
                                  a.sharding))
    tpu_row = runtime.kernel("kernel_operand", "tpu")
    got = tpu_row(at)
    assert tuple(got.format.layout.major_to_minor) == (0, 1, 2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(a))
    assert tpu_row(a) is a
    assert runtime.kernel("kernel_operand", "default")(at) is at
