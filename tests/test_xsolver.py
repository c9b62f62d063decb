"""Differential suite for the matrix-free x-update engine (NodeProxEngine).

The Woodbury and PCG backends are certified against the dense
Cholesky/eigh oracle at the prox level (one solve) and end-to-end (full
Bi-cADMM fits: identical supports and iteration counts), across m << n and
m >> n shapes, static and traced (path-engine) penalties, and the sharded
single-device engine. A jaxpr shape audit proves that large-d squared fits
— including the polish step — never materialize an n x n array at the
acceptance shape n = 1e5, m = 2e3.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BiCADMM, BiCADMMConfig, fit_path, prox
from repro.core.prox import NodeProxEngine
from repro.core.sharded import ShardedBiCADMM
from repro.data import (SyntheticSpec, make_sparse_classification,
                        make_sparse_regression, make_sparse_softmax)

jax.config.update("jax_enable_x64", False)


def _problem(m, n, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    A = jax.random.normal(k1, (m, n), jnp.float32) / np.sqrt(m)
    b = jax.random.normal(k2, (m,), jnp.float32)
    q = jax.random.normal(k3, (n,), jnp.float32)
    return A, b, q


# ------------------------------------------------------ prox-level solves --
@pytest.mark.parametrize("m,n", [(40, 120), (120, 40)])  # m << n and m >> n
def test_woodbury_prox_matches_dense(m, n):
    A, b, q = _problem(m, n)
    sigma, rho_c = 0.5, 1.0
    dense = prox.ridge_prox_factorized(
        prox.ridge_setup(A, b, sigma, rho_c), q, rho_c)
    wood = prox.woodbury_prox(
        prox.woodbury_setup(A, b, sigma, rho_c), q, rho_c)
    np.testing.assert_allclose(np.asarray(wood), np.asarray(dense),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("m,n", [(40, 120), (120, 40)])
def test_pcg_prox_matches_dense(m, n):
    A, b, q = _problem(m, n, seed=1)
    sigma, rho_c = 0.5, 1.0
    dense = prox.ridge_prox_factorized(
        prox.ridge_setup(A, b, sigma, rho_c), q, rho_c)
    got = prox.pcg_prox(prox.cg_setup(A, b, iters=400, tol=1e-7), q,
                        rho_c, sigma)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                               rtol=2e-4, atol=2e-5)


def test_dynamic_shift_backends_match_eigh_oracle():
    """Traced sigma/rho_c (the path-engine regime): the spectral Woodbury
    factors and the shift-at-solve-time PCG match the eigh ridge oracle."""
    A, b, q = _problem(60, 90, seed=2)
    eigh_f = prox.ridge_setup_eigh(A, b)
    wood_f = prox.woodbury_setup_eigh(A, b)
    cg_f = prox.cg_setup(A, b, iters=400, tol=1e-7)

    @jax.jit
    def solve_all(rho_c, sigma):
        return (prox.ridge_prox_eigh(eigh_f, q, rho_c, sigma),
                prox.woodbury_prox_eigh(wood_f, q, rho_c, sigma),
                prox.pcg_prox(cg_f, q, rho_c, sigma))

    for rho_c, sigma in [(0.25, 2.0), (1.0, 0.5), (4.0, 0.125)]:
        oracle, wood, cg = solve_all(jnp.float32(rho_c), jnp.float32(sigma))
        np.testing.assert_allclose(np.asarray(wood), np.asarray(oracle),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(cg), np.asarray(oracle),
                                   rtol=2e-4, atol=2e-5)


def test_warm_cg_equals_cold_cg_at_convergence():
    A, b, q = _problem(50, 80, seed=3)
    f = prox.cg_setup(A, b, iters=500, tol=1e-7)
    cold = prox.pcg_prox(f, q, 1.0, 0.5, x0=jnp.zeros_like(q))
    # warm start from the solution of a nearby prox center — the ADMM
    # steady-state situation
    near = prox.pcg_prox(f, q + 0.01, 1.0, 0.5, x0=jnp.zeros_like(q))
    warm = prox.pcg_prox(f, q, 1.0, 0.5, x0=near)
    np.testing.assert_allclose(np.asarray(warm), np.asarray(cold),
                               rtol=1e-4, atol=1e-5)


def test_auto_policy_regimes():
    ch = lambda m, n: NodeProxEngine.choose(m, n).kind
    assert ch(100, 500) == "dense"                       # small n
    assert ch(10_000, prox.DENSE_MAX_N) == "dense"
    assert ch(2_000, 100_000) == "woodbury"              # m << n
    assert ch(100_000, 100_000) == "pcg"                 # both large
    assert ch(prox.WOODBURY_MAX_M + 1, 10 ** 6) == "pcg"
    assert NodeProxEngine.choose(8, 8, x_solver="pcg").kind == "pcg"
    with pytest.raises(ValueError):
        NodeProxEngine.choose(8, 8, x_solver="qr")
    with pytest.raises(ValueError):
        BiCADMM("squared", BiCADMMConfig(kappa=4, x_solver="qr"))


# -------------------------------------------------------- end-to-end fits --
KW = dict(gamma=10.0, rho_c=1.0, alpha=0.5, max_iter=300, tol=1e-5)


@pytest.mark.parametrize("m_per_node", [120, 30])   # m >> n and m < n
def test_fit_backends_match_dense_oracle(m_per_node):
    spec = SyntheticSpec(2, m_per_node, 60, sparsity_level=0.75, noise=1e-3)
    As, bs, x_true = make_sparse_regression(1, spec)
    res = {}
    for xs in ("dense", "woodbury", "pcg"):
        cfg = BiCADMMConfig(kappa=spec.kappa, x_solver=xs, **KW)
        res[xs] = BiCADMM("squared", cfg).fit(As, bs)
    for xs in ("woodbury", "pcg"):
        # iteration counts must match the dense oracle (a +-1 slack only
        # for the razor-thin case where the residual lands within float
        # dust of the tolerance on the final iteration)
        assert abs(int(res[xs].iters) - int(res["dense"].iters)) <= 1, xs
        assert np.array_equal(np.array(res[xs].support),
                              np.array(res["dense"].support)), xs
        np.testing.assert_allclose(np.array(res[xs].z),
                                   np.array(res["dense"].z),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.array(res[xs].x),
                                   np.array(res["dense"].x),
                                   rtol=1e-3, atol=1e-3)


def test_path_traced_penalties_all_backends():
    """gamma/rho_c grids (traced shifts) through the path engine: the
    spectral Woodbury and PCG backends reproduce the dense eigh path."""
    spec = SyntheticSpec(2, 80, 48, sparsity_level=0.75, noise=1e-3)
    As, bs, _ = make_sparse_regression(5, spec)
    kappas = [16, 12, 8]
    gammas = [20.0, 10.0, 5.0]
    rho_cs = [1.0, 1.0, 2.0]
    paths = {}
    for xs in ("dense", "woodbury", "pcg"):
        cfg = BiCADMMConfig(kappa=spec.kappa, x_solver=xs, **KW)
        paths[xs] = fit_path(BiCADMM("squared", cfg), As, bs, kappas,
                             gammas=gammas, rho_cs=rho_cs)
    for xs in ("woodbury", "pcg"):
        assert np.array_equal(np.array(paths[xs].support),
                              np.array(paths["dense"].support)), xs
        np.testing.assert_allclose(np.array(paths[xs].z),
                                   np.array(paths["dense"].z),
                                   rtol=1e-4, atol=1e-4)
        # iteration counts track the oracle; warm starts compound the
        # fp differences of the (exact) solves, so allow boundary slack
        assert np.max(np.abs(np.array(paths[xs].iters, np.int64)
                             - np.array(paths["dense"].iters, np.int64))) \
            <= 2, xs


def test_nonsquared_losses_ignore_x_solver():
    """logistic / softmax(K>1) route through the kernel-backed Newton-CG:
    the x_solver policy must not perturb them (bitwise)."""
    spec = SyntheticSpec(2, 150, 30, sparsity_level=0.75, noise=0.0)
    As, bs, _ = make_sparse_classification(3, spec)
    cfg_kw = dict(kappa=spec.kappa, gamma=50.0, rho_c=0.5, alpha=0.5,
                  max_iter=120, tol=3e-4)
    r1 = BiCADMM("logistic", BiCADMMConfig(**cfg_kw)).fit(As, bs)
    r2 = BiCADMM("logistic",
                 BiCADMMConfig(**cfg_kw, x_solver="pcg")).fit(As, bs)
    assert int(r1.iters) == int(r2.iters)
    assert np.array_equal(np.array(r1.z), np.array(r2.z))

    sspec = SyntheticSpec(2, 150, 18, sparsity_level=0.7, noise=0.0,
                          n_classes=3)
    As3, bs3, x_true = make_sparse_softmax(6, sspec)
    kappa = int(jnp.sum(x_true != 0))
    s1 = BiCADMM("softmax", BiCADMMConfig(
        kappa=kappa, gamma=50.0, rho_c=0.5, alpha=0.5, max_iter=80,
        tol=5e-4), n_classes=3).fit(As3, bs3)
    s2 = BiCADMM("softmax", BiCADMMConfig(
        kappa=kappa, gamma=50.0, rho_c=0.5, alpha=0.5, max_iter=80,
        tol=5e-4, x_solver="woodbury"), n_classes=3).fit(As3, bs3)
    assert np.array_equal(np.array(s1.z), np.array(s2.z))


def test_sharded_single_device_cg_matches_reference_pcg():
    """(1,1) mesh with x_update="cg" vs BiCADMM(x_solver="pcg"): identical
    iteration counts; the setup statistics (colsq / A^T b) are mirrored
    bitwise, iterates agree to the CG recurrence's own rounding."""
    spec = SyntheticSpec(1, 80, 40, sparsity_level=0.75, noise=1e-3)
    As, bs, _ = make_sparse_regression(11, spec)
    # penalties exactly representable in f32 so the engines' constant
    # folding (python-double vs traced-f32) is rounding-identical
    kw = dict(kappa=spec.kappa, gamma=0.5, rho_c=1.0, alpha=0.5,
              max_iter=150, tol=1e-5, x_solver="pcg", cg_iters=120,
              cg_tol=1e-7)
    ref = BiCADMM("squared", BiCADMMConfig(**kw, polish=False)).fit(As, bs)
    mesh = jax.make_mesh((1, 1), ("nodes", "feat"))
    res = ShardedBiCADMM("squared", BiCADMMConfig(**kw, polish=False), mesh,
                         x_update="cg").fit(As.reshape(-1, 40),
                                            bs.reshape(-1))
    assert int(res.iters) == int(ref.iters)
    np.testing.assert_allclose(np.array(res.z), np.array(ref.z),
                               rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.array(res.support), np.array(ref.support))


def test_sharded_cg_mode_validation():
    mesh = jax.make_mesh((1, 1), ("nodes", "feat"))
    with pytest.raises(ValueError):
        ShardedBiCADMM("logistic", BiCADMMConfig(kappa=4), mesh,
                       x_update="cg")
    with pytest.raises(ValueError):
        ShardedBiCADMM("squared", BiCADMMConfig(kappa=4), mesh,
                       x_update="lobpcg")


# ----------------------------------------------- setup cache and donation --
def test_run_from_caches_setup_and_donates_state():
    spec = SyntheticSpec(2, 60, 40, sparsity_level=0.75, noise=1e-3)
    As, bs, _ = make_sparse_regression(7, spec)
    solver = BiCADMM("squared", BiCADMMConfig(kappa=spec.kappa, **KW))
    r1 = solver.run_from(As, bs, solver.init_state(As, bs))
    assert len(solver._setup_cache) == 1
    cached = next(iter(solver._setup_cache.values()))[-1][0]
    st = r1.state
    r2 = solver.run_from(As, bs, st, kappa=8)
    # same data => the factors object is reused, not recomputed
    assert solver._setup(As, bs)[0] is cached
    assert len(solver._setup_cache) == 1
    # donation: the consumed state's buffers were reused in place
    assert st.x.is_deleted() and st.u.is_deleted()
    assert not r2.state.x.is_deleted()


def test_fit_path_donates_initial_state_buffers():
    """Peak-memory probe for the donated scan driver: the fresh init state
    fed to a warm fit_path must not survive the call (its buffers are
    aliased into the scan carry), and the path still matches cold fits."""
    spec = SyntheticSpec(2, 60, 40, sparsity_level=0.75, noise=1e-3)
    As, bs, _ = make_sparse_regression(9, spec)
    solver = BiCADMM("squared", BiCADMMConfig(kappa=spec.kappa, **KW))
    before = {id(a) for a in jax.live_arrays()}
    res = solver.fit(As, bs)  # also exercises the donated while-loop driver
    path = fit_path(solver, As, bs, [16, 12, 8])
    # no stray copies of the (N, d) iterate buffers beyond the returned
    # result pytrees: every new live array is reachable from the results
    reachable = {id(a) for a in jax.tree.leaves((res, path))
                 if isinstance(a, jax.Array)}
    cache_arrays = {id(a) for entry in solver._setup_cache.values()
                    for a in jax.tree.leaves(entry)
                    if isinstance(a, jax.Array)}
    stray = [a for a in jax.live_arrays()
             if id(a) not in before and id(a) not in reachable
             and id(a) not in cache_arrays and a.size >= spec.n_features]
    assert not stray, f"{len(stray)} stray live arrays: {stray[:3]}"


def test_sharded_setup_cache_reused_across_fits():
    spec = SyntheticSpec(1, 60, 40, sparsity_level=0.75, noise=1e-3)
    As, bs, _ = make_sparse_regression(13, spec)
    A, b = As.reshape(-1, 40), bs.reshape(-1)
    mesh = jax.make_mesh((1, 1), ("nodes", "feat"))
    eng = ShardedBiCADMM("squared", BiCADMMConfig(
        kappa=spec.kappa, max_iter=60, **{k: v for k, v in KW.items()
                                          if k != "max_iter"}), mesh)
    r1 = eng.fit(A, b)
    assert len(eng._factor_cache) == 1
    fac1 = next(iter(eng._factor_cache.values()))[2]
    r2 = eng.fit(A, b, state=r1.state)
    assert next(iter(eng._factor_cache.values()))[2] is fac1
    # donated sharded state consumed
    assert r1.state.x.is_deleted()


# --------------------------------------------------------- shape audit ----
def _all_shapes(jaxpr, acc):
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            shape = getattr(aval, "shape", None)
            if shape is not None:
                acc.add(tuple(shape))
        for val in jax.tree.leaves(eqn.params, is_leaf=lambda x: hasattr(
                x, "eqns") or hasattr(x, "jaxpr")):
            if hasattr(val, "jaxpr"):        # ClosedJaxpr
                val = val.jaxpr
            if hasattr(val, "eqns"):         # Jaxpr
                _all_shapes(val, acc)
    return acc


def _assert_no_square(fn, big, *args):
    shapes = _all_shapes(jax.make_jaxpr(fn)(*args).jaxpr, set())
    offenders = [s for s in shapes
                 if sum(1 for d in s if d >= big) >= 2]
    assert not offenders, f"n x n-sized intermediates traced: {offenders}"


@pytest.mark.parametrize("x_solver", ["auto", "pcg"])
def test_large_d_fit_never_materializes_nxn(x_solver):
    """Acceptance shape: a full squared-loss fit (setup + while-loop +
    polish) at n = 1e5, m = 2e3 traces without any array having two axes
    >= n. 'auto' resolves to the Woodbury backend (m << n); 'pcg' is the
    fully matrix-free path. Tracing is abstract — nothing is executed."""
    N, m_per, n = 2, 1000, 100_000
    cfg = BiCADMMConfig(kappa=500, x_solver=x_solver, max_iter=50, **{
        k: v for k, v in KW.items() if k != "max_iter"})
    solver = BiCADMM("squared", cfg)
    As = jax.ShapeDtypeStruct((N, m_per, n), jnp.float32)
    bs = jax.ShapeDtypeStruct((N, m_per), jnp.float32)
    _assert_no_square(lambda a, b: solver.fit(a, b).x, n, As, bs)


def test_moderate_large_d_fit_runs_and_matches_woodbury_vs_pcg():
    """Above the dense threshold (n > DENSE_MAX_N) the auto engine must
    actually run — and the two matrix-free backends agree with each other."""
    spec = SyntheticSpec(2, 120, 3000, sparsity_level=0.99, noise=1e-3)
    As, bs, x_true = make_sparse_regression(21, spec)
    outs = {}
    for xs in ("auto", "pcg"):
        cfg = BiCADMMConfig(kappa=spec.kappa, x_solver=xs, gamma=10.0,
                            rho_c=1.0, alpha=0.5, max_iter=60, tol=1e-4)
        solver = BiCADMM("squared", cfg)
        assert solver._x_engine(120, 3000, False).kind == \
            ("woodbury" if xs == "auto" else "pcg")
        outs[xs] = solver.fit(As, bs)
    assert int(outs["auto"].iters) == int(outs["pcg"].iters)
    np.testing.assert_allclose(np.array(outs["auto"].z),
                               np.array(outs["pcg"].z),
                               rtol=1e-4, atol=1e-4)
