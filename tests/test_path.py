"""Differential tests for the warm-started hyperparameter-path engine.

Certification strategy: on graded-magnitude planted instances (the regime
where the best kappa-subset is unique and well separated — see
``repro.data.synthetic.make_graded_regression``) the warm-started path must
reproduce *independent cold fits* exactly: same support, same solution to
solver tolerance, and it must do so in fewer total iterations. The sharded
engine's path must agree with the reference engine's path iteration-for-
iteration on a single-device mesh (exact projection mode).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BiCADMM, BiCADMMConfig, SolverEngine, fit_grid,
                        fit_path, kappa_ladder)
from repro.core.sharded import ShardedBiCADMM
from repro.data import (SyntheticSpec, make_graded_classification,
                        make_graded_regression)

KAPPAS = [10, 8, 7, 6, 5, 4, 3, 2]           # descending: dense -> sparse


def _regression():
    spec = SyntheticSpec(2, 200, 40, sparsity_level=0.75, noise=1e-4)
    As, bs, x_true = make_graded_regression(1, spec)
    cfg = BiCADMMConfig(kappa=10, gamma=10.0, rho_c=1.0, alpha=0.5,
                        max_iter=300, tol=1e-5)
    return As, bs, x_true, cfg


def _classification():
    spec = SyntheticSpec(2, 300, 30, sparsity_level=0.8, noise=0.0)
    As, bs, x_true = make_graded_classification(2, spec)
    cfg = BiCADMMConfig(kappa=6, gamma=50.0, rho_c=0.5, alpha=0.5,
                        max_iter=250, tol=3e-4)
    return As, bs, x_true, cfg


# ------------------------------------------------- warm path == cold fits --
def test_warm_path_matches_independent_cold_fits_squared():
    As, bs, _, cfg = _regression()
    solver = BiCADMM("squared", cfg)
    res = fit_path(solver, As, bs, KAPPAS)
    total_warm, total_cold = 0, 0
    for i, k in enumerate(KAPPAS):
        cold = BiCADMM("squared", dataclasses.replace(cfg, kappa=k)).fit(As, bs)
        assert np.array_equal(np.array(res.support[i]),
                              np.array(cold.support)), f"kappa={k}"
        np.testing.assert_allclose(np.array(res.x[i]), np.array(cold.x),
                                   atol=1e-4)
        total_warm += int(res.iters[i])
        total_cold += int(cold.iters)
    # warm starts must pay off in total outer iterations
    assert total_warm < total_cold


def test_warm_path_matches_independent_cold_fits_logistic():
    As, bs, _, cfg = _classification()
    kappas = [6, 5, 4, 3]
    solver = BiCADMM("logistic", cfg)
    res = fit_path(solver, As, bs, kappas)
    for i, k in enumerate(kappas):
        cold = BiCADMM("logistic",
                       dataclasses.replace(cfg, kappa=k)).fit(As, bs)
        assert np.array_equal(np.array(res.support[i]),
                              np.array(cold.support)), f"kappa={k}"
        np.testing.assert_allclose(np.array(res.x[i]), np.array(cold.x),
                                   atol=5e-3)


def test_grid_vmap_matches_independent_cold_fits():
    As, bs, _, cfg = _regression()
    solver = BiCADMM("squared", cfg)
    grid = fit_grid(solver, As, bs, KAPPAS)
    for i, k in enumerate(KAPPAS):
        cold = BiCADMM("squared", dataclasses.replace(cfg, kappa=k)).fit(As, bs)
        # vmap batches the per-point linear algebra, which perturbs the
        # trajectory at the ulp level — iteration counts may shift by ~1
        assert abs(int(grid.iters[i]) - int(cold.iters)) <= 2
        assert np.array_equal(np.array(grid.support[i]),
                              np.array(cold.support))
        np.testing.assert_allclose(np.array(grid.x[i]), np.array(cold.x),
                                   atol=1e-5)


# ------------------------------------------------------- resumable states --
def test_run_from_state_equals_path_scan():
    """The public init_state/run_from chain is the same computation the
    scan-based path engine runs internally."""
    As, bs, _, cfg = _regression()
    solver = BiCADMM("squared", cfg)
    kappas = [10, 6, 3]
    res = fit_path(solver, As, bs, kappas)

    r = solver.run_from(As, bs, solver.init_state(As, bs), kappa=10)
    for i, k in enumerate(kappas):
        if i > 0:
            r = solver.run_from(As, bs, r.state, kappa=k)
        assert int(r.iters) == int(res.iters[i]), f"kappa={k}"
        np.testing.assert_allclose(np.array(r.x), np.array(res.x[i]),
                                   atol=1e-6)


def test_run_from_converged_state_stops_fast():
    As, bs, _, cfg = _regression()
    solver = BiCADMM("squared", cfg)
    first = solver.fit(As, bs)
    again = solver.run_from(As, bs, first.state)
    assert int(again.iters) <= 2
    np.testing.assert_allclose(np.array(again.x), np.array(first.x),
                               atol=1e-5)


# ------------------------------------------------------- penalty grids ----
def test_gamma_grid_dynamic_penalties():
    """Sweeping gamma exercises the spectral (eigh) ridge factors; the point
    matching the config's own gamma must agree with the plain fit."""
    As, bs, _, cfg = _regression()
    solver = BiCADMM("squared", cfg)
    gammas = [100.0, 10.0, 1.0]
    res = fit_path(solver, As, bs, [10, 10, 10], gammas=gammas)
    plain = solver.fit(As, bs)   # gamma = 10.0 == gammas[1]
    assert np.array_equal(np.array(res.support[1]), np.array(plain.support))
    np.testing.assert_allclose(np.array(res.x[1]), np.array(plain.x),
                               atol=1e-3)
    # stronger regularization (smaller gamma) => larger training loss
    assert float(res.train_loss[2]) >= float(res.train_loss[1]) - 1e-6


def test_feature_split_rejects_dynamic_penalties():
    As, bs, _, cfg = _regression()
    solver = BiCADMM("squared",
                     dataclasses.replace(cfg, n_feature_blocks=4))
    with pytest.raises(ValueError, match="feature-split"):
        fit_path(solver, As, bs, [10, 8], gammas=[10.0, 1.0])


# ------------------------------------------------- sharded path engine ----
def test_sharded_path_matches_reference_path():
    """Single-device mesh, exact projection: the sharded path must track the
    reference path iteration-for-iteration."""
    spec = SyntheticSpec(1, 120, 40, sparsity_level=0.75, noise=1e-4)
    As, bs, _ = make_graded_regression(3, spec)
    kw = dict(kappa=10, gamma=10.0, rho_c=1.0, alpha=0.5,
              max_iter=200, tol=1e-5, inner_iters=25)
    kappas = [10, 7, 5, 3]
    ref = fit_path(BiCADMM("squared", BiCADMMConfig(
        **kw, force_feature_split=True, polish=False)), As, bs, kappas)
    mesh = jax.make_mesh((1, 1), ("nodes", "feat"))
    sh = ShardedBiCADMM("squared", BiCADMMConfig(**kw, polish=False),
                        mesh).fit_path(
        As.reshape(-1, 40), bs.reshape(-1), kappas)
    np.testing.assert_array_equal(np.array(sh.iters), np.array(ref.iters))
    np.testing.assert_allclose(np.array(sh.z), np.array(ref.z), atol=2e-4)
    np.testing.assert_array_equal(np.array(sh.support), np.array(ref.support))
    assert sh.state is not None


def test_sharded_path_points_are_polished():
    """Each point of a sharded path is the ridge optimum restricted to its
    own support, as a polished fit at that kappa is. The loose tol leaves
    the consensus iterate visibly short of that optimum, so only the
    polish meets the bound."""
    spec = SyntheticSpec(1, 120, 40, sparsity_level=0.75, noise=1e-4)
    As, bs, _ = make_graded_regression(3, spec)
    gamma = 10.0
    mesh = jax.make_mesh((1, 1), ("nodes", "feat"))
    sh = ShardedBiCADMM("squared", BiCADMMConfig(
        kappa=10, gamma=gamma, max_iter=200, tol=1e-3, inner_iters=25),
        mesh).fit_path(As.reshape(-1, 40), bs.reshape(-1), [10, 6, 3])
    A = np.asarray(As, np.float64).reshape(-1, 40)
    b = np.asarray(bs, np.float64).reshape(-1)
    for p in range(3):
        S = np.flatnonzero(np.asarray(sh.support[p]))
        x = np.zeros(40)
        x[S] = np.linalg.solve(A[:, S].T @ A[:, S] + np.eye(S.size) / gamma,
                               A[:, S].T @ b)
        np.testing.assert_allclose(np.asarray(sh.coef[p, :, 0]), x,
                                   atol=1e-5 * np.max(np.abs(x)))


def test_sharded_warm_path_beats_cold_path():
    spec = SyntheticSpec(1, 120, 40, sparsity_level=0.75, noise=1e-4)
    As, bs, _ = make_graded_regression(3, spec)
    cfg = BiCADMMConfig(kappa=10, gamma=10.0, rho_c=1.0, alpha=0.5,
                        max_iter=200, tol=1e-5, inner_iters=25)
    mesh = jax.make_mesh((1, 1), ("nodes", "feat"))
    eng = ShardedBiCADMM("squared", cfg, mesh)
    A, b = As.reshape(-1, 40), bs.reshape(-1)
    kappas = [10, 8, 6, 5, 4, 3]
    warm = eng.fit_path(A, b, kappas)
    cold = eng.fit_path(A, b, kappas, warm_start=False)
    assert int(warm.iters.sum()) < int(cold.iters.sum())
    np.testing.assert_array_equal(np.array(warm.support),
                                  np.array(cold.support))


# ------------------------------------------------ remaining loss family ---
def test_path_runs_for_hinge_and_softmax():
    """Warm-started paths work for every loss the solver supports; for the
    non-differential losses we check convergence, budget feasibility and
    agreement of the first (cold) point with a plain fit."""
    As, bs, _, cfg = _classification()
    hinge_cfg = dataclasses.replace(cfg, max_iter=150)
    solver = BiCADMM("smoothed_hinge", hinge_cfg)
    res = fit_path(solver, As, bs, [6, 4, 3])
    assert np.all(np.array(res.cardinality) <= np.array([6, 4, 3]))
    plain = solver.fit(As, bs)
    assert np.array_equal(np.array(res.support[0]), np.array(plain.support))

    from repro.data import make_sparse_softmax
    spec = SyntheticSpec(2, 150, 12, sparsity_level=0.7, noise=0.0,
                         n_classes=3)
    As3, bs3, x3 = make_sparse_softmax(5, spec)
    kap = int(jnp.sum(x3 != 0))
    sm_cfg = BiCADMMConfig(kappa=kap, gamma=50.0, rho_c=0.5, alpha=0.5,
                           max_iter=120, tol=5e-4)
    sm = BiCADMM("softmax", sm_cfg, n_classes=3)
    res3 = fit_path(sm, As3, bs3, [kap, max(kap - 3, 2)])
    assert np.all(np.array(res3.cardinality)
                  <= np.array([kap, max(kap - 3, 2)]))
    assert res3.x.shape == (2, 12 * 3)


# --------------------------------------------------- SolverEngine facade --
def test_solver_engine_dispatch():
    As, bs, _, cfg = _regression()
    with pytest.warns(DeprecationWarning, match="SolverEngine"):
        eng = SolverEngine("squared", cfg)
    res = eng.fit(As, bs)
    path = eng.fit_path(As, bs, [10, 6, 3])
    assert int(path.iters[0]) == int(res.iters)
    mesh = jax.make_mesh((1, 1), ("nodes", "feat"))
    sh = SolverEngine("squared", dataclasses.replace(cfg, inner_iters=25),
                      engine="sharded", mesh=mesh)
    shp = sh.fit_path(As, bs, [10, 6, 3])
    np.testing.assert_array_equal(np.array(shp.support),
                                  np.array(path.support))
    with pytest.raises(ValueError, match="mesh"):
        SolverEngine("squared", cfg, engine="sharded")


def test_solver_engine_shim_bit_identical_to_estimator():
    """Satellite: the deprecated facade is a shim over repro.api — its
    results are bit-identical to the estimator's on the same fixture, and
    the one-call fit_sparse_model shim matches both."""
    from repro import api
    As, bs, _, cfg = _regression()
    with pytest.warns(DeprecationWarning, match="SolverEngine"):
        eng = SolverEngine("squared", cfg)
    res = eng.fit(As, bs)
    est = api.SparseLinearRegression(
        cfg.kappa, gamma=cfg.gamma, rho_c=cfg.rho_c, alpha=cfg.alpha,
        max_iter=cfg.max_iter, tol=cfg.tol).fit(As, bs)
    assert int(res.iters) == est.n_iter_
    np.testing.assert_array_equal(np.array(res.x), np.array(est.result_.x))
    np.testing.assert_array_equal(np.array(res.z), np.array(est.result_.z))

    from repro.core import fit_sparse_model
    with pytest.warns(DeprecationWarning, match="fit_sparse_model"):
        legacy = fit_sparse_model("squared", As, bs, kappa=cfg.kappa,
                                  gamma=cfg.gamma, rho_c=cfg.rho_c,
                                  alpha=cfg.alpha, max_iter=cfg.max_iter,
                                  tol=cfg.tol)
    assert int(legacy.iters) == est.n_iter_
    np.testing.assert_array_equal(np.array(legacy.x),
                                  np.array(est.result_.x))

    # the warm path through the shim == the estimator's path, bit for bit
    shim_path = eng.fit_path(As, bs, [10, 6, 3])
    est_path = est.fit_path(As, bs, [10, 6, 3])
    np.testing.assert_array_equal(np.array(shim_path.x),
                                  np.array(est_path.x))
    np.testing.assert_array_equal(np.array(shim_path.iters),
                                  np.array(est_path.iters))


def test_solver_engine_grid_reports_strategy():
    """Satellite: one grid entry point on both engines, honest about how
    it executed — vmap on the reference engine, cold-scan on sharded."""
    As, bs, _, cfg = _regression()
    with pytest.warns(DeprecationWarning):
        eng = SolverEngine("squared", cfg)
    grid = eng.fit_grid(As, bs, [10, 6])
    assert grid.strategy == "vmap"
    mesh = jax.make_mesh((1, 1), ("nodes", "feat"))
    with pytest.warns(DeprecationWarning):
        sh = SolverEngine("squared", dataclasses.replace(cfg, inner_iters=25),
                          engine="sharded", mesh=mesh)
    sgrid = sh.fit_grid(As, bs, [10, 6])
    assert sgrid.strategy == "cold-scan"
    # identical numerics to the warm facade's cold baseline
    cold = sh.fit_path(As, bs, [10, 6], warm_start=False)
    np.testing.assert_array_equal(np.array(sgrid.x), np.array(cold.x))


def test_kappa_ladder_properties():
    ks = kappa_ladder(100, 8)
    assert ks == sorted(ks, reverse=True)
    assert len(set(ks)) == len(ks)
    assert all(1 <= k <= 100 for k in ks)


# ------------------------------------------------ hypothesis properties ---
from hypothesis_compat import given, settings, st

_spec = SyntheticSpec(1, 80, 20, sparsity_level=0.6, noise=1e-4)
_As, _bs, _ = make_graded_regression(7, _spec)
_solver = BiCADMM("squared", BiCADMMConfig(
    kappa=8, gamma=10.0, rho_c=1.0, alpha=0.5, max_iter=120, tol=1e-4))


@settings(deadline=None, max_examples=8)
@given(st.lists(st.integers(1, 12), min_size=4, max_size=4, unique=True))
def test_path_cardinality_monotone_in_kappa(kappas):
    """For any kappa grid, the fitted cardinality is monotone in kappa
    (and never exceeds the budget)."""
    kappas = sorted(kappas, reverse=True)
    res = fit_path(_solver, _As, _bs, kappas)
    card = np.array(res.cardinality)
    assert np.all(card <= np.array(kappas))
    # descending kappas => non-increasing cardinality
    assert np.all(np.diff(card) <= 0)
