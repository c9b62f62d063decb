"""Fleet fitting: thousands of independent SML problems in one compiled call.

The estimator API fits one problem per compiled call, but the production
shape of this workload is fleets — per-user personalization models,
per-layer/per-head sparse probes over LM activations, per-SKU demand
models. This module batches B independent problems that share a shape
signature ``(N, m, n, K)`` through ONE vmapped Bi-cADMM driver:

* :func:`fit_many_stacked` — stacked data ``As (B, N, m, n)`` /
  ``bs (B, N, m)`` with per-problem ``kappa`` / ``gamma`` / ``rho_c``
  vectors, solved by the masked batched while-loop
  (``BiCADMM._run_while_fleet``): one compiled loop runs while any lane is
  active, converged lanes freeze their whole state behind a per-lane
  select. The masking is bit-identical to JAX's own ``while_loop``
  batching rule (a ``vmap`` of the solo loop), and each lane matches a
  solo fit on that problem exactly in iteration count and support —
  iterates agree to fp round-off (batched GEMMs accumulate in a
  different order than solo ones). ``tests/test_fleet.py`` certifies
  both contracts differentially.
* :func:`bucket_problems` / :func:`fit_many` — the bucketing layer above
  it: a heterogeneous list of problems is grouped by ``(N, n)`` signature
  and right-padded along the sample axis with zero rows to the largest
  ``m`` in each bucket, so an arbitrary fleet compiles into a few
  signatures instead of B programs. Zero-row padding is exact in exact
  arithmetic: a padded row has ``A``-row 0 and label 0, so its loss
  gradient is annihilated by ``A^T (.)`` for every loss in the registry
  and the squared-loss factors ``A^T A`` / ``A^T b`` are unchanged. In
  f32 the squared loss stays trajectory-stable (padding is absorbed once
  in the setup factors); iterative x-updates (Newton-CG losses) see
  reduction-order round-off from the longer sample axis, which can
  accumulate over many outer iterations on ill-conditioned problems —
  the returned iterate is still a solver output for the *unpadded*
  problem, just not bitwise the one a solo fit lands on. The reported
  ``train_loss`` always includes the padded rows' constant ``l(0, 0)``;
  :func:`corrected_train_losses` subtracts it exactly.

Per-problem hyperparameters ride the same machinery as the path engine
(``repro.core.path``): homogeneous penalties compile the static
(Cholesky) x-update factors exactly like a solo fit, while per-problem
``gamma`` / ``rho_c`` switch to the dynamic spectral (eigh) factors from
PR 3, with the shift applied at solve time. Per-problem ``kappa`` is
always traceable. The feature-split inner ADMM bakes penalties into its
cached factors and has stacked inner state; it is not supported in fleet
mode (``ValueError`` at setup).

The estimator front-end is :func:`repro.api.fit_many`; engines declare
fleet support through ``repro.api.Capabilities.fleet``.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from .bicadmm import (BiCADMM, BiCADMMState, SolveParams, _is_traced)
from .path import _point_outputs
from .results import FitResult, FleetResult, mark_aborted

Array = jax.Array


# --------------------------------------------------------------------------
# per-problem hyperparameter grids
# --------------------------------------------------------------------------
def _fleet_grids(solver: BiCADMM, B: int, kappas, gammas, rho_cs, dt):
    """Materialize the three (B,) per-problem hyperparameter vectors
    (config values fill the axes the caller did not vary) and report
    whether penalties are heterogeneous (=> dynamic spectral factors)."""
    cfg = solver.cfg
    dyn = gammas is not None or rho_cs is not None

    def fill(vals, default, name):
        arr = jnp.full((B,), default, dt) if vals is None \
            else jnp.asarray(vals, dt)
        if arr.shape != (B,):
            raise ValueError(f"{name} must be a (B,) = ({B},) vector, "
                             f"got shape {arr.shape}")
        return arr

    return (fill(kappas, cfg.kappa, "kappas"),
            fill(gammas, cfg.gamma, "gammas"),
            fill(rho_cs, cfg.rho_c, "rho_cs"), dyn)


def _fleet_params(solver: BiCADMM, N: int, kaps, gams, rhos,
                  dyn: bool) -> SolveParams:
    """(B,)-vector :class:`SolveParams`. The arithmetic mirrors
    ``BiCADMM._make_params`` exactly: homogeneous penalties are folded in
    Python double precision (as a solo fit folds them), heterogeneous ones
    elementwise in the grid dtype (as a solo ``run_from`` with an array
    ``gamma=`` / ``rho_c=`` override computes them) — so per-lane
    trajectories stay bit-comparable to solo fits in both regimes."""
    cfg = solver.cfg
    B = kaps.shape[0]
    dt = kaps.dtype
    if not dyn:
        return SolveParams(
            kappa=kaps,
            rho_c=jnp.full((B,), cfg.rho_c, dt),
            rho_b=jnp.full((B,), cfg.rho_b_eff, dt),
            sigma=jnp.full((B,), 1.0 / (N * cfg.gamma), dt))
    rho_b = (jnp.full((B,), cfg.rho_b, dt) if cfg.rho_b is not None
             else cfg.alpha * rhos)
    return SolveParams(kappa=kaps, rho_c=rhos, rho_b=rho_b,
                       sigma=1.0 / (N * gams))


# --------------------------------------------------------------------------
# batched setup / state
# --------------------------------------------------------------------------
def _fleet_setup(solver: BiCADMM, As: Array, bs: Array, dyn: bool):
    """Per-problem x-update factors, vmapped over the fleet axis and cached
    on the data arrays (repeated warm refits factorize once) — the fleet
    counterpart of ``BiCADMM._setup``."""
    cfg = solver.cfg
    B, N, m, n = As.shape
    if cfg.use_feature_split:
        raise ValueError(
            "the fleet driver does not support the feature-split "
            "sub-solver (stacked inner-ADMM state and penalty-baked "
            "per-block factors); use n_feature_blocks=1")
    cacheable = not _is_traced(As, bs)
    key = ("fleet", id(As), id(bs), As.shape, bs.shape, str(As.dtype),
           bool(dyn))
    if cacheable and key in solver._setup_cache:
        return solver._setup_cache[key][-1]
    if solver.loss.name == "squared":
        eng = solver._x_engine(m, n, dyn)
        sigma = 1.0 / (N * cfg.gamma)
        factors = jax.vmap(jax.vmap(
            lambda A, b: eng.setup(A, b, sigma, cfg.rho_c)))(As, bs)
    else:
        factors = None
    out = factors
    if cacheable:
        if len(solver._setup_cache) >= solver._SETUP_CACHE_MAX:
            solver._setup_cache.pop(next(iter(solver._setup_cache)))
        solver._setup_cache[key] = (As, bs, out)
    return out


def init_fleet_state(solver: BiCADMM, B: int, N: int, n: int,
                     dt) -> BiCADMMState:
    """A fresh zero state with a leading fleet axis B — every lane equals
    ``BiCADMM.init_state``'s zero state."""
    K = solver.loss.n_classes
    d = n * K
    return BiCADMMState(
        x=jnp.zeros((B, N, d), dt), u=jnp.zeros((B, N, d), dt),
        z=jnp.zeros((B, d), dt), t=jnp.zeros((B,), dt),
        s=jnp.zeros((B, d), dt), v=jnp.zeros((B,), dt),
        k=jnp.zeros((B,), jnp.int32), p_r=jnp.full((B,), jnp.inf, dt),
        d_r=jnp.full((B,), jnp.inf, dt), b_r=jnp.full((B,), jnp.inf, dt),
        inner=None, cg_steps=jnp.zeros((B,), jnp.int32))


def zero_lane_state(solver: BiCADMM, N: int, n: int, dt) -> BiCADMMState:
    """A solo-shaped zero state — the cold lane of a mixed warm/cold stack
    (``stack_states``); equal to ``BiCADMM.init_state``'s zero state."""
    return jax.tree.map(lambda a: a[0], init_fleet_state(solver, 1, N, n, dt))


def stack_states(states) -> BiCADMMState:
    """Stack B solo-shaped states (e.g. warm-pool entries plus
    :func:`zero_lane_state` cold lanes) into one fleet state with lane
    axis 0 — the inverse of ``FleetResult[i].state`` slicing."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *states)


def reset_fleet_for_resume(st: BiCADMMState) -> BiCADMMState:
    """Batched counterpart of ``bicadmm.reset_for_resume``: zero every
    lane's counters and residuals (fresh, non-aliased buffers so the state
    stays donatable), keep the iterates for the warm refit."""
    dt = st.z.dtype
    B = st.z.shape[0]
    return st._replace(k=jnp.zeros((B,), jnp.int32),
                       cg_steps=jnp.zeros((B,), jnp.int32),
                       p_r=jnp.full((B,), jnp.inf, dt),
                       d_r=jnp.full((B,), jnp.inf, dt),
                       b_r=jnp.full((B,), jnp.inf, dt))


# --------------------------------------------------------------------------
# the one compiled fleet program
# --------------------------------------------------------------------------
def _fleet_run_impl(solver, N, dyn, As, bs, params, factors, st0,
                    iter_caps):
    """Masked batched while-loop + per-lane finalization, as one jitted
    program (module-level jit: the compile cache persists across calls,
    keyed on solver instance + shapes, like the path engine's scan)."""
    st = solver._run_while_fleet(factors, As, bs, params, st0, iter_caps)
    outs = jax.vmap(
        lambda A, b, s, p: _point_outputs(solver, A, b, s, p))(
            As, bs, st, params)
    return st, outs


_fleet_run = jax.jit(_fleet_run_impl, static_argnums=(0, 1, 2))
# The donated variant reuses the incoming state's (B, N, d) iterate
# buffers in place as the while-loop carry — the peak live footprint of a
# warm fleet refit is one batched state, not two.
_fleet_run_donated = jax.jit(_fleet_run_impl, static_argnums=(0, 1, 2),
                             donate_argnums=(7,))


def fit_many_stacked(solver: BiCADMM, As: Array, bs: Array, *,
                     kappas=None, gammas=None, rho_cs=None,
                     states: BiCADMMState | None = None,
                     iter_caps=None) -> FleetResult:
    """Fit B stacked problems ``As (B, N, m, n)`` / ``bs (B, N, m)`` in one
    vmapped driver with per-problem hyperparameters and per-problem
    convergence.

    ``kappas`` / ``gammas`` / ``rho_cs`` are optional (B,) vectors; the
    solver config fills whichever the caller does not vary. ``states``
    warm-starts every lane from a previous :class:`FleetResult`'s
    ``.state`` (counters/residuals are reset, iterates kept; the state is
    donated — keep using the returned ``result.state``). ``iter_caps`` is
    an optional (B,) int vector of per-lane iteration budgets below the
    config's ``max_iter`` — the serving plane translates per-request
    deadlines into caps (a capped-out lane returns its best iterate so
    far, flagged by ``iters == cap`` with residuals above ``tol``), and a
    cap of 0 marks an inert batch-axis padding lane.
    """
    As, bs = jnp.asarray(As), jnp.asarray(bs)
    if As.ndim != 4:
        raise ValueError(f"As must be (B, N, m, n); got shape {As.shape}")
    B, N, m, n = As.shape
    bs = bs.reshape(B, N, m)
    kaps, gams, rhos, dyn = _fleet_grids(solver, B, kappas, gammas, rho_cs,
                                         As.dtype)
    if iter_caps is not None:
        iter_caps = jnp.asarray(iter_caps, jnp.int32)
        if iter_caps.shape != (B,):
            raise ValueError(f"iter_caps must be a (B,) = ({B},) vector, "
                             f"got shape {iter_caps.shape}")
    factors = _fleet_setup(solver, As, bs, dyn)
    params = _fleet_params(solver, N, kaps, gams, rhos, dyn)
    st0 = (init_fleet_state(solver, B, N, n, As.dtype) if states is None
           else reset_fleet_for_resume(states))
    run = _fleet_run if _is_traced(As, bs, st0) else _fleet_run_donated
    st, outs = run(solver, N, dyn, As, bs, params, factors, st0, iter_caps)
    coef = outs["x"].reshape(B, n, solver.loss.n_classes)
    status = outs["status"]
    if iter_caps is not None:
        # Lanes the external per-lane budget stopped (deadline caps, inert
        # cap-0 padding) exhausted a budget the *caller* set, not the
        # config's: reclassify their MAX_ITER as ABORTED. Eager
        # elementwise fixup — no extra sync.
        status = mark_aborted(status, outs["iters"], iter_caps,
                              solver.cfg.max_iter)
    return FleetResult(coef, outs["z"], outs["support"], outs["iters"],
                       outs["p_r"], outs["d_r"], outs["b_r"],
                       outs["cardinality"], kaps, gams, rhos,
                       train_loss=outs["train_loss"], state=st,
                       strategy="fleet-vmap", status=status)


# --------------------------------------------------------------------------
# bucketing-by-shape: heterogeneous fleets
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class FleetBucket:
    """One compiled signature of a heterogeneous fleet: the member
    problems' indices in the caller's order, their stacked (zero-padded)
    data, and each member's true row count (for the train-loss
    correction)."""
    signature: tuple       # (N, m_padded, n)
    indices: tuple[int, ...]
    As: Array              # (b, N, m_padded, n)
    bs: Array              # (b, N, m_padded)
    m_orig: tuple[int, ...]


def _normalize(X, y):
    """One problem's data to the paper's stacked (N, m, n) layout."""
    X, y = jnp.asarray(X), jnp.asarray(y)
    if X.ndim == 2:
        X, y = X[None], y.reshape(1, -1)
    if X.ndim != 3:
        raise ValueError(f"each problem must be (samples, n) or (N, m, n); "
                         f"got shape {X.shape}")
    return X, y.reshape(X.shape[0], X.shape[1])


def bucket_problems(problems) -> list[FleetBucket]:
    """Group a heterogeneous list of ``(X, y)`` problems by ``(N, n)``
    signature, zero-padding the sample axis to the largest ``m`` in each
    bucket — a few compiled signatures instead of one per problem.

    Zero-row padding changes nothing in exact arithmetic (zero ``A`` rows
    and zero labels contribute nothing through ``A^T (.)`` for every
    registry loss; see the module docstring for the f32 fine print); the
    summed ``train_loss`` picks up a constant ``l(0, 0)`` per padded row,
    which :func:`corrected_train_losses` subtracts.
    """
    norm = [_normalize(X, y) for X, y in problems]
    groups: dict[tuple, list[int]] = {}
    for i, (X, _) in enumerate(norm):
        N, _, n = X.shape
        groups.setdefault((N, n), []).append(i)
    buckets = []
    for (N, n), idxs in groups.items():
        m_pad = max(norm[i][0].shape[1] for i in idxs)
        As, bs, ms = [], [], []
        for i in idxs:
            X, y = norm[i]
            m = X.shape[1]
            ms.append(m)
            pad = ((0, 0), (0, m_pad - m), (0, 0))
            As.append(jnp.pad(X, pad))
            bs.append(jnp.pad(y, pad[:2]))
        buckets.append(FleetBucket((N, m_pad, n), tuple(idxs),
                                   jnp.stack(As), jnp.stack(bs), tuple(ms)))
    return buckets


def _pad_loss_unit(solver: BiCADMM) -> float:
    """The constant ``l(0, 0)`` one zero-padded row adds to a problem's
    summed train loss (0 for squared, log 2 for logistic, ...)."""
    loss = solver.loss
    K = loss.n_classes
    pred = jnp.zeros((1, K) if K > 1 else (1,), jnp.float32)
    b = jnp.zeros((1,), jnp.int32 if K > 1 else jnp.float32)
    return float(loss.value(pred, b))


def _subset(vals, idxs):
    if vals is None:
        return None
    return [vals[i] for i in idxs]


def fit_many(solver: BiCADMM, problems, *, kappas=None, gammas=None,
             rho_cs=None, on_bucket=None) -> list[FitResult]:
    """Fit a heterogeneous list of ``(X, y)`` problems: bucket by shape
    signature, solve each bucket with :func:`fit_many_stacked`, and
    scatter the per-problem :class:`FitResult` views back to the caller's
    order. ``kappas`` / ``gammas`` / ``rho_cs`` are optional per-problem
    sequences aligned with ``problems``.

    ``on_bucket`` is the batch-close hook: called with each
    :class:`FleetBucket` after it closes (data stacked and padded) and
    before it is solved — the serving plane's metrics layer observes batch
    composition through it."""
    problems = list(problems)
    for name, vals in (("kappas", kappas), ("gammas", gammas),
                       ("rho_cs", rho_cs)):
        if vals is not None and len(vals) != len(problems):
            raise ValueError(f"{name} must have one entry per problem "
                             f"({len(problems)}), got {len(vals)}")
    results: list[FitResult | None] = [None] * len(problems)
    for bucket in bucket_problems(problems):
        if on_bucket is not None:
            on_bucket(bucket)
        sub = fit_many_stacked(
            solver, bucket.As, bucket.bs,
            kappas=_subset(kappas, bucket.indices),
            gammas=_subset(gammas, bucket.indices),
            rho_cs=_subset(rho_cs, bucket.indices))
        for j, idx in enumerate(bucket.indices):
            results[idx] = sub[j]
    return results


def corrected_train_losses(solver: BiCADMM, fleet: FleetResult,
                           bucket: FleetBucket) -> Array:
    """Per-problem train losses of a padded bucket, corrected for the
    padded rows' constant ``l(0, 0)`` contribution: a padded row's
    prediction is exactly ``x . 0 = 0``, so each of the ``N * (m_pad - m)``
    padded rows adds exactly ``l(0, 0)`` to the summed loss — subtract it
    (exact up to one fp subtraction per problem)."""
    N, m_pad, _ = bucket.signature
    pad_rows = jnp.asarray([N * (m_pad - m) for m in bucket.m_orig],
                           fleet.train_loss.dtype)
    return fleet.train_loss - pad_rows * _pad_loss_unit(solver)
