"""Both engines' spans, named device programs and inner-step
counters: ``FitResult.counters`` (x-update and polish CG steps), the
``repro.*`` host spans a fit opens (``repro.runtime.SPANS``), and the
``jit_repro_loop`` / ``jit_repro_polish`` program names."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, runtime
from repro.core import prox
from repro.core.bicadmm import BiCADMM, BiCADMMConfig, reset_for_resume

N, M, NF, KAPPA = 2, 48, 24, 4


def _data(seed=0, logistic=False):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((N, M, NF)).astype(np.float32)
    w = np.zeros(NF, np.float32)
    w[:KAPPA] = rng.uniform(1.0, 2.0, KAPPA)
    y = X @ w + 0.01 * rng.standard_normal((N, M)).astype(np.float32)
    if logistic:
        y = (y > 0).astype(np.float32)
    return jnp.asarray(X), jnp.asarray(y)


def _counts(res):
    return {k: int(v) for k, v in res.counters.items()}


@pytest.mark.parametrize("cg_iters", [5, 200])
def test_pcg_fit_counts_x_update_and_polish_steps(cg_iters):
    As, bs = _data()
    cfg = BiCADMMConfig(kappa=KAPPA, gamma=100.0, x_solver="pcg",
                        cg_iters=cg_iters, max_iter=40)
    res = BiCADMM("squared", cfg).fit(As, bs)
    c = _counts(res)
    iters = int(res.iters)
    assert set(c) == {"xupdate_cg_steps", "polish_cg_steps"}
    # every outer iteration runs one PCG per node, of at most cg_iters steps
    assert iters <= c["xupdate_cg_steps"] <= iters * N * cg_iters
    assert 0 < c["polish_cg_steps"] <= max(200, 2 * cg_iters)
    assert res.counters["xupdate_cg_steps"].dtype == jnp.int32


def test_pcg_counter_is_the_sum_of_the_node_solves():
    """One outer iteration from the zero state: the counter equals the
    steps of each node's PCG solve, run here by hand on the same factors."""
    As, bs = _data(1)
    cfg = BiCADMMConfig(kappa=KAPPA, gamma=100.0, x_solver="pcg",
                        max_iter=1)
    solver = BiCADMM("squared", cfg)
    res = solver.fit(As, bs)
    factors, n_nodes, _, _ = solver._setup(As, bs)
    params = solver._make_params(n_nodes)
    zero = jnp.zeros((NF,), jnp.float32)
    want = 0
    for i in range(n_nodes):
        f = jax.tree.map(lambda a, i=i: a[i], factors)
        _, steps = prox.pcg_prox_steps(f, zero, params.rho_c, params.sigma,
                                       x0=zero)
        want += int(steps)
    assert int(res.iters) == 1
    assert _counts(res)["xupdate_cg_steps"] == want > 0


def test_newton_cg_fit_counts_inner_cg_steps():
    As, bs = _data(2, logistic=True)
    cfg = BiCADMMConfig(kappa=KAPPA, gamma=10.0, rho_c=0.5, max_iter=25)
    res = BiCADMM("logistic", cfg).fit(As, bs)
    c = _counts(res)
    iters = int(res.iters)
    # per outer iteration and node: newton_iters Newton steps, each a CG
    # of at most 50 steps (newton_cg_prox's cap); the polish caps at 60
    assert iters <= c["xupdate_cg_steps"] <= iters * N * cfg.newton_iters * 50
    assert 0 < c["polish_cg_steps"] <= cfg.newton_iters * 60


def test_direct_x_update_counts_no_cg_steps():
    As, bs = _data(3)
    res = BiCADMM("squared", BiCADMMConfig(kappa=KAPPA, gamma=100.0,
                                           x_solver="dense",
                                           max_iter=20)).fit(As, bs)
    assert _counts(res) == {"xupdate_cg_steps": 0, "polish_cg_steps": 0}


def test_loop_and_history_scan_count_alike():
    """The while-loop and the history scan step the same state: the same
    support and the same CG-step count after the same iterations."""
    As, bs = _data(4)
    cfg = BiCADMMConfig(kappa=KAPPA, gamma=100.0, x_solver="pcg",
                        max_iter=60)
    a = BiCADMM("squared", cfg).fit(As, bs)
    b = BiCADMM("squared", cfg).fit_with_history(As, bs, int(a.iters))
    np.testing.assert_array_equal(a.support, b.support)
    assert int(a.state.cg_steps) == int(b.state.cg_steps)


def test_reset_for_resume_zeroes_the_counter():
    As, bs = _data(5)
    solver = BiCADMM("squared", BiCADMMConfig(kappa=KAPPA, gamma=100.0,
                                              x_solver="pcg", max_iter=10))
    res = solver.fit(As, bs)
    assert int(res.state.cg_steps) > 0
    st = reset_for_resume(res.state)
    assert int(st.cg_steps) == 0 and int(st.k) == 0
    again = solver.run_from(As, bs, res.state)
    # the resumed solve counts only its own steps
    assert int(again.counters["xupdate_cg_steps"]) <= \
        int(again.iters) * N * solver.cfg.cg_iters


def test_estimator_result_carries_counters():
    As, bs = _data(6)
    est = api.SparseLinearRegression(
        KAPPA, gamma=100.0,
        options=api.SolverOptions(x_solver="pcg", max_iter=30))
    est.fit(As, bs)
    assert set(_counts(est.result_)) == {"xupdate_cg_steps",
                                         "polish_cg_steps"}


def test_engines_without_the_counter_return_none():
    As, bs = _data(7)
    fleet = api.fit_many(api.SparseProblem("squared", kappa=KAPPA,
                                           gamma=100.0),
                         As[None].repeat(2, 0), bs[None].repeat(2, 0),
                         options=api.SolverOptions(max_iter=10))
    assert all(fleet[i].counters is None for i in range(len(fleet)))
    stream = api.SparseLinearRegression(
        KAPPA, gamma=100.0, options=api.SolverOptions(max_iter=10))
    stream.partial_fit(As[0], bs[0])
    assert stream.result_.counters is None


def test_programs_are_named():
    As, bs = _data(8)
    solver = BiCADMM("squared", BiCADMMConfig(kappa=KAPPA, gamma=100.0,
                                              x_solver="pcg"))
    factors, n_nodes, n, K = solver._setup(As, bs)
    params = solver._make_params(n_nodes)
    loop = solver._run_while_donated.lower(
        factors, As, bs, params, solver._init_state(As, bs, n, K))
    assert "@jit_repro_loop" in loop.as_text().splitlines()[0]
    polish = solver._polish_jit.lower(
        As, bs, jnp.ones((NF,), bool), jnp.zeros((NF,), jnp.float32), params)
    assert "@jit_repro_polish" in polish.as_text().splitlines()[0]


def test_span_names_are_checked():
    with runtime.span("repro.loop"):
        pass
    with pytest.raises(ValueError):
        runtime.span("repro.unknown")


def _host_events(tmp):
    from jax.profiler import ProfileData
    pb = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    assert pb
    events = []
    for plane in ProfileData.from_file(pb[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events
                           if e.name == "fit" or e.name.startswith("repro.")]
    return sorted(events, key=lambda e: (e[1], -e[2]))


def test_fit_spans_in_order_and_nested(tmp_path):
    """A traced fit opens every span of ``runtime.SPANS``, in that order,
    each inside the caller's ``fit`` span; the polish nests inside the
    finalize."""
    As, bs = _data(9)
    est = api.SparseLinearRegression(
        KAPPA, gamma=100.0,
        options=api.SolverOptions(x_solver="pcg", max_iter=20))
    est.fit(As, bs)                                   # compile outside
    jax.block_until_ready(est.result_.coef)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("fit"):
            est.fit(As, bs)
            jax.block_until_ready(est.result_.coef)
    events = _host_events(str(tmp_path))
    (fit,) = [e for e in events if e[0] == "fit"]
    spans = [e for e in events if e[0] != "fit"]
    first = []
    for name, *_ in spans:
        if name not in first:
            first.append(name)
    assert first == list(runtime.SPANS)
    assert all(fit[1] <= s <= e <= fit[2] for _, s, e in spans)
    (fin,) = [e for e in spans if e[0] == "repro.finalize"]
    (pol,) = [e for e in spans if e[0] == "repro.polish"]
    assert fin[1] <= pol[1] <= pol[2] <= fin[2]
    (loop,) = [e for e in spans if e[0] == "repro.loop"]
    assert loop[2] <= fin[1]


# ------------------------------------------------------ the sharded engine --
def _sharded(loss="squared", **opts):
    mesh = jax.make_mesh((1, 1), ("nodes", "feat"))
    cls = {"squared": api.SparseLinearRegression,
           "logistic": api.SparseLogisticRegression}[loss]
    return cls(KAPPA, gamma=100.0, options=api.SolverOptions(
        engine="sharded", mesh=mesh, max_iter=40, **opts))


@pytest.mark.parametrize("loss,x_update", [("squared", "cg"),
                                           ("squared", "subsolver"),
                                           ("logistic", "auto")])
def test_sharded_fit_carries_counters(loss, x_update):
    """The x-update's CG steps (the slowest node's; none outside the PCG
    x-update) and the polish's Newton steps (none for the direct solve)."""
    As, bs = _data(10, logistic=loss == "logistic")
    if loss == "logistic":
        bs = 2.0 * bs - 1.0
    est = _sharded(loss, x_update=x_update)
    est.fit(As, bs)
    c = _counts(est.result_)
    assert set(c) == {"xupdate_cg_steps", "polish_cg_steps"}
    assert all(v.dtype == jnp.int32 for v in est.result_.counters.values())
    iters = int(est.result_.iters)
    if x_update == "cg":
        assert iters <= c["xupdate_cg_steps"] <= iters * est.options.cg_iters
    else:
        assert c["xupdate_cg_steps"] == 0
    want = est.options.newton_iters if loss == "logistic" else 0
    assert c["polish_cg_steps"] == want


def test_sharded_programs_are_named():
    As, bs = _data(11)
    est = _sharded(x_update="cg")
    est.fit(As, bs)
    solver = est._adapters["sharded"].solver
    A_p, b_p, xfac = solver._prepare(As, bs)
    progs = {k[0]: f for k, f in solver._jit_cache.items()}
    state = solver.init_state(NF, N * M, jnp.float32)
    loop = progs["fit"].lower(A_p, b_p, xfac, state)
    assert "@jit_repro_loop" in loop.as_text().splitlines()[0]
    res = est.result_
    polish = progs["polish"].lower(A_p, b_p, res.coef.reshape(1, -1),
                                   res.support[None])
    assert "@jit_repro_polish" in polish.as_text().splitlines()[0]


def test_sharded_fit_spans_in_order_and_nested(tmp_path):
    """A traced sharded fit opens every span of ``runtime.SPANS``, in that
    order, inside the caller's ``fit`` span, the polish inside the
    finalize, after the loop."""
    As, bs = _data(12)
    est = _sharded(x_update="cg")
    est.fit(As, bs)                                   # compile outside
    jax.block_until_ready(est.result_.coef)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("fit"):
            est.fit(As, bs)
            jax.block_until_ready(est.result_.coef)
    events = _host_events(str(tmp_path))
    (fit,) = [e for e in events if e[0] == "fit"]
    spans = [e for e in events if e[0] != "fit"]
    first = []
    for name, *_ in spans:
        if name not in first:
            first.append(name)
    assert first == list(runtime.SPANS)
    assert all(fit[1] <= s <= e <= fit[2] for _, s, e in spans)
    (fin,) = [e for e in spans if e[0] == "repro.finalize"]
    (pol,) = [e for e in spans if e[0] == "repro.polish"]
    (loop,) = [e for e in spans if e[0] == "repro.loop"]
    assert loop[2] <= fin[1] <= pol[1] <= pol[2] <= fin[2]
