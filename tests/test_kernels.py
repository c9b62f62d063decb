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


# ---------------------------------------------- one-column VPU GEMV ----
def _pallas_names(fn, *args) -> list[str]:
    """Names of the pallas_calls ``fn`` traces to, nested jaxprs included."""
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(str(eqn.params["name"]))
            for v in eqn.params.values():
                sub = getattr(v, "jaxpr", v)
                if hasattr(sub, "eqns"):
                    walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names


def test_interpret_mode_fills_past_the_edge_with_nan():
    """The parity cases below rest on this: in interpret mode a block that
    reaches past an array's edge reads NaN there, so every ragged shape
    plants NaN past A's edge (and the vectors'), and a kernel that let it
    into a sum would return NaN."""
    from jax.experimental import pallas as pl

    def copy(a_ref, o_ref):
        o_ref[...] = a_ref[...]

    out = pl.pallas_call(
        copy, grid=(1,),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        interpret=True)(jnp.ones((5, 100), jnp.float32))
    out = np.asarray(out)
    assert np.isnan(out[5:]).all() and np.isnan(out[:, 100:]).all()
    assert (out[:5, :100] == 1).all()


# (m, n): ragged both ways; many row blocks with a part strip at the end
# (25001 = 24 x 1024 + 425); n = 2000 in one block with a part lane chunk;
# a contraction over n in two blocks (9000 = 8192 + 808)
_GEMV_SHAPES = [(70, 45), (25001, 130), (1003, 2000), (40, 9000)]
_GEMV_CASES = (
    [(op, m, n, col, "float32") for op in ("matvec", "rmatvec")
     for m, n in _GEMV_SHAPES for col in (False, True)]
    + [(op, m, n, False, "float32") for op in ("normal_scalar",
                                               "normal_vector")
       for m, n in _GEMV_SHAPES]
    + [(op, 70, 45, True, "float32") for op in ("normal_scalar",
                                                "normal_vector")]
    + [(op, m, n, col, "float32") for op in ("block_matvec", "block_rmatvec")
       for m, n in [(70, 45), (1003, 2000)] for col in (False, True)]
    # bf16 data: (16, 128) strips, upcast in the kernel, f32 sums
    + [(op, m, n, False, "bfloat16") for op in ("matvec", "rmatvec")
       for m, n in [(70, 45), (25001, 130)]])


@pytest.mark.parametrize("op,m,n,col,dtype", _GEMV_CASES)
def test_gemv_vector_path_matches_f64(op, m, n, col, dtype):
    """The one-column bodies against an f64 NumPy oracle: 1-D and (., 1)
    operands, ragged m and n (NaN past every edge, see above), the vmapped
    node-block products, f32 and bf16 data; every case runs the VPU
    body."""
    from repro import runtime
    rng = np.random.default_rng(m * 7 + n)
    M = 2 if op.startswith("block") else None
    lead = () if M is None else (M,)
    a = jnp.asarray(rng.standard_normal(lead + (m, n)), dtype)
    vec_len = m if op in ("rmatvec", "block_rmatvec") else n
    v = rng.standard_normal(lead + (vec_len,) + ((1,) if col else ()))
    v = v.astype(np.float32)
    a64, v64 = np.asarray(a, np.float64), v.astype(np.float64)
    if op == "matvec":
        fn, want = lambda a, v: ops.matvec(a, v, interpret=True), a64 @ v64
    elif op == "rmatvec":
        fn, want = lambda a, v: ops.rmatvec(a, v, interpret=True), a64.T @ v64
    elif op.startswith("normal"):
        # a scalar penalty, or a per-feature ridge diagonal shaped like p
        shift = (np.float32(1.5) if op == "normal_scalar" else
                 np.abs(rng.standard_normal(v.shape)).astype(np.float32) + 0.1)
        fn = lambda a, v: ops.normal_matvec(a, v, shift, interpret=True)
        want = a64.T @ (a64 @ v64) + np.float64(shift) * v64
    else:
        fn = runtime.kernel(op, "tpu")
        eq = "jmn,jn...->jm..." if op == "block_matvec" else "jmn,jm...->jn..."
        want = np.einsum(eq, a64, v64)
    with runtime.force_interpret(True):
        got = np.asarray(fn(a, jnp.asarray(v)), np.float64)
        names = _pallas_names(fn, a, v)
    assert names and set(names) <= {"gemv", "gemv_t"}
    assert got.shape == want.shape
    assert np.isfinite(got).all()
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < 1e-5, err


@pytest.mark.parametrize("kernel", ["matvec", "rmatvec"])
def test_wide_operand_keeps_the_mxu_body(kernel):
    """K = 4 columns (softmax's classes, the sub-solver's blocks) still
    run the MXU body; one column runs the VPU one."""
    a = jax.random.normal(jax.random.PRNGKey(5), (70, 45))
    vec = 45 if kernel == "matvec" else 70
    fn = lambda a, v: getattr(ops, kernel)(a, v, interpret=True)
    wide = jax.random.normal(jax.random.PRNGKey(6), (vec, 4))
    names = _pallas_names(fn, a, wide)
    assert len(names) == 1 and names[0] not in ("gemv", "gemv_t")
    want = ref.matvec_ref(a, wide) if kernel == "matvec" else \
        ref.rmatvec_ref(a, wide)
    np.testing.assert_allclose(np.asarray(fn(a, wide)), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    one = {"matvec": "gemv", "rmatvec": "gemv_t"}[kernel]
    assert _pallas_names(fn, a, wide[:, :1]) == [one]
    assert _pallas_names(fn, a, wide[:, 0]) == [one]


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
