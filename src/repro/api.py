"""PsFiT-style estimator API: declarative problems, capability-negotiated
engines, one result type.

The paper's deliverable is a *toolbox* — sparse linear / logistic / softmax
regression and sparse SVMs behind one interface — not a solver loop. This
module is that front-end for the repo's two Bi-cADMM engines:

* :class:`SparseProblem`  — WHAT to solve: the loss (a
  :class:`repro.core.losses.Loss` or its registry name), ``n_classes``, the
  sparsity budget ``kappa`` and the penalty weights ``gamma`` / ``rho_c`` /
  ``alpha`` / ``rho_b``.
* :class:`SolverOptions`  — HOW to solve it: engine selection (``"auto"`` /
  ``"reference"`` / ``"sharded"``), the device mesh, per-engine backend
  knobs (``x_solver`` / ``x_update`` / projection modes) and iteration
  budgets / tolerances.
* :class:`Capabilities`   — what a negotiated engine can actually do
  (dynamic penalties, per-solve overrides, penalty grids vs kappa-only
  sweeps, vmap-vs-scan grid strategy, gather-free collectives). The
  front-end validates requests against it up front with one
  :class:`CapabilityError` instead of per-engine ``ValueError`` mazes at
  call time, and ``engine="auto"`` picks the engine from mesh availability
  plus the data shape.
* One result type — :class:`repro.core.results.FitResult` /
  :class:`~repro.core.results.SparsePath` — from every engine and every
  entry point, so downstream code never special-cases field names.

The four paper models ship as estimators with ``fit`` / ``fit_path`` /
``fit_grid`` / ``predict`` / ``decision_function`` / ``score``:

>>> from repro.api import SparseLinearRegression
>>> model = SparseLinearRegression(kappa=20, gamma=10.0, tol=1e-5)
>>> model.fit(X, y).score(X, y)          # X: (samples, n) or (N, m, n)
>>> model.predict(X_new)
>>> path = model.fit_path(X, y, kappas=[40, 20, 10])   # warm-started sweep

Estimators wrap the engines without touching their numerics: a fit through
this layer is bit-identical to the corresponding raw
``BiCADMM(...).fit(...)`` / ``ShardedBiCADMM(...).fit(...)`` call
(``tests/test_api.py`` certifies this bit-for-bit). The legacy
``repro.core.SolverEngine`` and ``repro.core.fit_sparse_model`` entry
points are deprecation shims over this module.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax.numpy as jnp

from . import runtime
from .core.bicadmm import BiCADMM, BiCADMMConfig, BiCADMMState, _is_traced
from .core.fleet import fit_many as _ref_fit_many
from .core.fleet import fit_many_stacked as _ref_fit_many_stacked
from .core.losses import Loss, get_loss
from .core.path import fit_grid as _ref_fit_grid
from .core.path import fit_path as _ref_fit_path
from .core.prox import DENSE_MAX_N, XSOLVERS
from .core.recovery import (RecoveryAttempt, RecoveryPolicy, SolveDiverged,
                            sanitize_state)
from .core.results import FitResult, FleetResult, SolveStatus, SparsePath
from .core.sharded import X_UPDATE_MODES, ShardedBiCADMM
from .core.streaming import StreamingBiCADMM

__all__ = [
    "CapabilityError",
    "Capabilities",
    "FitResult",
    "FittingService",
    "FleetResult",
    "RecoveryPolicy",
    "ServeOptions",
    "SolveDiverged",
    "SolveStatus",
    "SolverOptions",
    "SparseEstimator",
    "SparseLinearRegression",
    "SparseLogisticRegression",
    "SparsePath",
    "SparseProblem",
    "SparseSVM",
    "SparseSoftmaxRegression",
    "StreamingSolver",
    "engine_capabilities",
    "fit_many",
    "recover",
    "select_engine",
    "serve",
    "solve",
    "solve_grid",
    "solve_path",
    "split_legacy_config",
    "stream",
    "validate_data",
]

# The serving layer is re-exported lazily: ``repro.serve`` imports this
# module at its own import time, so a top-level import here would cycle.
_SERVE_EXPORTS = ("FittingService", "ServeOptions")


def __getattr__(name: str):
    """Lazy re-export of the serving-layer types named in ``__all__``."""
    if name in _SERVE_EXPORTS:
        from . import serve as _serve
        return getattr(_serve, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

ENGINES = ("auto", "reference", "sharded")
SHARDED_PROJECTIONS = ("ladder_exact", "exact", "batched", "bisect")


class CapabilityError(ValueError):
    """A request the negotiated engine cannot honor (the capability is
    reported in :class:`Capabilities`), raised by the front-end before any
    engine code runs."""


# --------------------------------------------------------------------------
# declarative problem / solver options
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SparseProblem:
    """WHAT to solve: ``min_x sum_i l_i(A_i x, b_i) + 1/(2 gamma) ||x||^2``
    s.t. ``||x||_0 <= kappa`` — the loss and the problem-level weights,
    with no engine knobs mixed in."""
    loss: Loss | str
    kappa: int
    n_classes: int = 1
    gamma: float = 1.0
    rho_c: float = 1.0
    alpha: float = 0.5          # rho_b = alpha * rho_c unless rho_b is set
    rho_b: float | None = None

    def __post_init__(self):
        if self.kappa < 1:
            raise ValueError(f"kappa must be >= 1, got {self.kappa}")
        if self.n_classes < 1:
            raise ValueError("n_classes must be >= 1")
        if self.gamma <= 0 or self.rho_c <= 0:
            raise ValueError("gamma and rho_c must be positive")
        if isinstance(self.loss, Loss):
            # a Loss instance carries its own class count: adopt it when
            # n_classes was left at the default, reject a contradiction
            if self.n_classes not in (1, self.loss.n_classes):
                raise ValueError(
                    f"n_classes={self.n_classes} contradicts the loss "
                    f"instance's n_classes={self.loss.n_classes}")
            object.__setattr__(self, "n_classes", self.loss.n_classes)
        name = self.loss if isinstance(self.loss, str) else self.loss.name
        if name.startswith("softmax") and self.n_classes < 2:
            raise ValueError("softmax needs n_classes >= 2")

    def resolve_loss(self) -> Loss:
        """The registry :class:`Loss` this problem names (pass-through
        when constructed with a ``Loss`` instance directly)."""
        if isinstance(self.loss, Loss):
            return self.loss
        return get_loss(self.loss, self.n_classes)


@dataclasses.dataclass(frozen=True)
class SolverOptions:
    """HOW to solve it: engine selection plus every solver-level knob.
    Defaults match :class:`repro.core.bicadmm.BiCADMMConfig`, so a problem
    solved with default options is bit-identical to the raw engines."""
    engine: str = "auto"            # "auto" | "reference" | "sharded"
    mesh: Any = None                # jax Mesh (sharded / auto)
    # iteration budgets / tolerances (both engines)
    max_iter: int = 300
    tol: float = 1e-4
    zt_iters: int = 120
    # x-update backends
    x_solver: str = "auto"          # reference squared loss: NodeProxEngine
    x_update: str = "auto"          # sharded: "auto" | "subsolver" | "cg"
    n_feature_blocks: int = 1
    inner_iters: int = 15
    rho_l: float = 1.0
    newton_iters: int = 12
    cg_iters: int = 200
    cg_tol: float = 1e-6
    force_feature_split: bool = False
    # projection modes
    projection: str = "ladder"      # full-vector engine: "ladder" | "sort"
    sharded_projection: str = "ladder_exact"
    # misc
    polish: bool = True
    over_relax: float = 1.0
    # mixed-precision policy: a preset name ("fp32" | "bf16" | "fp16" |
    # "fp64_polish") or a repro.runtime.PrecisionPolicy. Engines negotiate
    # support through Capabilities.precisions.
    precision: Any = "fp32"
    # residual level past which the in-loop probes declare a solve
    # DIVERGED and exit early (isfinite failures always trip them)
    divergence_tol: float = 1e12
    # divergence recovery: a repro.core.recovery.RecoveryPolicy makes
    # api.solve rerun DIVERGED fits through the escalation ladder
    # (retry -> rho restart -> precision escalation -> x-solver fallback),
    # logging each attempt in FitResult.recovery. None (default) reports
    # DIVERGED without retrying.
    recovery: Any = None
    # mesh axis naming (sharded)
    nodes_axis: str | tuple[str, ...] = "nodes"
    feat_axis: str = "feat"

    def __post_init__(self):
        object.__setattr__(self, "precision",
                           runtime.resolve_precision(self.precision))
        if self.engine not in ENGINES:
            raise ValueError(f"unknown engine {self.engine!r}; expected one "
                             f"of {ENGINES}")
        if self.engine == "sharded" and self.mesh is None:
            raise ValueError("engine='sharded' requires a mesh")
        if self.engine == "reference" and self.mesh is not None:
            raise ValueError("a mesh requires engine='sharded' (or 'auto', "
                             "which selects the sharded engine from it)")
        if self.projection not in ("ladder", "sort"):
            raise ValueError(f"unknown projection mode {self.projection!r}")
        if self.sharded_projection not in SHARDED_PROJECTIONS:
            raise ValueError(
                f"unknown sharded projection {self.sharded_projection!r}; "
                f"expected one of {SHARDED_PROJECTIONS}")
        if self.x_solver not in XSOLVERS:
            raise ValueError(f"unknown x_solver {self.x_solver!r}; expected "
                             f"one of {XSOLVERS}")
        if self.x_update not in X_UPDATE_MODES:
            raise ValueError(f"unknown x_update mode {self.x_update!r}; "
                             f"expected one of {X_UPDATE_MODES}")
        if self.divergence_tol <= 0:
            raise ValueError("divergence_tol must be positive")
        if self.recovery is not None and not isinstance(self.recovery,
                                                        RecoveryPolicy):
            raise TypeError("recovery must be a RecoveryPolicy or None, "
                            f"got {type(self.recovery).__name__}")
        if self.mesh is not None:
            names = set(self.mesh.axis_names)
            nodes = (self.nodes_axis if isinstance(self.nodes_axis, tuple)
                     else (self.nodes_axis,))
            missing = (set(nodes) | {self.feat_axis}) - names
            if missing:
                raise ValueError(f"mesh lacks the axis name(s) "
                                 f"{sorted(missing)}; has {sorted(names)}")

    @property
    def use_feature_split(self) -> bool:
        """Whether these options activate the feature-split inner ADMM
        (which bakes penalties into cached per-block factors — see the
        footnotes on :func:`engine_capabilities`)."""
        return self.n_feature_blocks > 1 or self.force_feature_split


def build_config(problem: SparseProblem, options: SolverOptions
                 ) -> BiCADMMConfig:
    """Fold a (problem, options) pair into the engines' internal config."""
    return BiCADMMConfig(
        kappa=problem.kappa, gamma=problem.gamma, rho_c=problem.rho_c,
        alpha=problem.alpha, rho_b=problem.rho_b,
        max_iter=options.max_iter, tol=options.tol,
        zt_iters=options.zt_iters,
        n_feature_blocks=options.n_feature_blocks,
        inner_iters=options.inner_iters, rho_l=options.rho_l,
        newton_iters=options.newton_iters, polish=options.polish,
        over_relax=options.over_relax,
        force_feature_split=options.force_feature_split,
        projection=options.projection, x_solver=options.x_solver,
        cg_iters=options.cg_iters, cg_tol=options.cg_tol,
        precision=options.precision,
        divergence_tol=options.divergence_tol)


# --------------------------------------------------------------------------
# capability negotiation
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Capabilities:
    """What a negotiated engine can actually do. The front-end checks
    requests against this once, up front, instead of each engine raising
    its own ``ValueError`` mid-call.

    ``grid_strategy`` documents the vmap-vs-scan split for ``fit_grid``:
    the reference engine vmap-batches independent cold fits (``"vmap"``,
    all grid points concurrent in one compiled call); the sharded engine
    runs a sequential cold scan with a shared compile (``"cold-scan"``,
    identical numerics, no cross-point batching). The executed strategy is
    also recorded on every returned :class:`SparsePath`.

    Both engines honour ``SolverOptions(polish=True)``, the default: the
    coefficients are re-fit on the recovered support. The reference
    engine polishes every loss; the sharded engine polishes the squared
    and logistic losses (a kappa x kappa system summed over its nodes) and
    returns the thresholded consensus iterate for softmax and smoothed
    hinge.
    """
    engine: str
    distributed: bool          # runs under shard_map on a device mesh
    dynamic_penalties: bool    # traced gamma/rho_c (spectral ridge factors)
    per_solve_overrides: bool  # fit(kappa=..., gamma=..., rho_c=...)
    penalty_grids: bool        # gammas=/rho_cs= sweeps; False => kappa-only
    grid_strategy: str         # "vmap" | "cold-scan"
    gather_free: bool          # O(B)-collective projections, no O(d) gather
    warm_start: bool = True    # resumable state / warm-started paths
    fleet: bool = False        # fit_many: vmapped batch of B problems
    serve: bool = False        # FittingService micro-batching (needs fleet)
    stream: bool = False       # partial_fit: incremental setup-state updates
    # reduced-precision data dtypes the engine certifies (fp64-oracle
    # differential suite); "float32" (no cast) is always supported
    precisions: tuple = ("float32", "bfloat16", "float16")


def engine_capabilities(engine: str, options: SolverOptions | None = None
                        ) -> Capabilities:
    """The :class:`Capabilities` descriptor of ``engine`` under
    ``options`` (defaults when omitted)."""
    options = options if options is not None else SolverOptions()
    if engine == "reference":
        # the feature-split inner ADMM bakes penalties into its cached
        # per-block factors, so only kappa may be traced through it
        dyn = not options.use_feature_split
        return Capabilities(engine="reference", distributed=False,
                            dynamic_penalties=dyn, per_solve_overrides=True,
                            penalty_grids=dyn, grid_strategy="vmap",
                            gather_free=False, fleet=dyn, serve=dyn,
                            stream=dyn)
    if engine == "sharded":
        # fp16's narrow exponent underflows the psum'd ladder statistics on
        # badly scaled shards; only bf16 is certified for the sharded engine
        return Capabilities(
            engine="sharded", distributed=True, dynamic_penalties=False,
            per_solve_overrides=False, penalty_grids=False,
            grid_strategy="cold-scan",
            gather_free=options.sharded_projection != "exact",
            precisions=("float32", "bfloat16"))
    raise ValueError(f"unknown engine {engine!r}")


def _mesh_sizes(options: SolverOptions) -> tuple[int, int]:
    ax = dict(zip(options.mesh.axis_names, options.mesh.devices.shape))
    nodes = (options.nodes_axis if isinstance(options.nodes_axis, tuple)
             else (options.nodes_axis,))
    N = 1
    for a in nodes:
        N *= ax[a]
    return N, ax[options.feat_axis]


def select_engine(options: SolverOptions, *, n_samples: int | None = None,
                  n_features: int | None = None) -> str:
    """Resolve ``options.engine``. ``"auto"`` picks the sharded engine when
    a mesh with real parallelism is available AND the data shape fits its
    layout (rows divisible over the node axis, at least one feature column
    per device); otherwise the reference engine."""
    if options.engine != "auto":
        return options.engine
    if options.mesh is None:
        return "reference"
    N, M = _mesh_sizes(options)
    if N * M == 1:
        return "reference"      # a 1-device mesh adds overhead, not speed
    if n_samples is not None and n_samples % N != 0:
        return "reference"      # rows don't tile the node axis
    if n_features is not None and n_features < M:
        return "reference"      # fewer columns than feature shards
    return "sharded"


def _check_sweep(caps: Capabilities, gammas, rho_cs) -> None:
    if (gammas is not None or rho_cs is not None) and not caps.penalty_grids:
        raise CapabilityError(
            f"the {caps.engine!r} engine (as configured) supports "
            "kappa-only sweeps: penalty-dependent factors are baked in at "
            "setup, so gammas=/rho_cs= grids are unavailable "
            "(Capabilities.penalty_grids=False)")


def _check_fleet(caps: Capabilities) -> None:
    if not caps.fleet:
        raise CapabilityError(
            f"the {caps.engine!r} engine (as configured) does not support "
            "fleet fitting (Capabilities.fleet=False): fit_many needs the "
            "vmapped masked batched driver — use the reference engine "
            "with n_feature_blocks=1")


def _check_precision(caps: Capabilities, options: SolverOptions) -> None:
    pol = options.precision
    data = pol.data if pol.data is not None else "float32"
    if data not in caps.precisions:
        raise CapabilityError(
            f"the {caps.engine!r} engine does not certify data dtype "
            f"{data!r} (precision policy {runtime.precision_name(pol)!r}); "
            f"certified dtypes: {caps.precisions} "
            "(Capabilities.precisions)")


def _check_serve(caps: Capabilities) -> None:
    if not caps.serve:
        raise CapabilityError(
            f"the {caps.engine!r} engine (as configured) cannot back the "
            "fitting service (Capabilities.serve=False): micro-batching "
            "dispatches through the vmapped fleet driver — use the "
            "reference engine with n_feature_blocks=1")


def _check_stream(caps: Capabilities) -> None:
    if not caps.stream:
        raise CapabilityError(
            f"the {caps.engine!r} engine (as configured) cannot stream "
            "(Capabilities.stream=False): partial_fit maintains the "
            "x-update factors incrementally, which needs the reference "
            "engine with n_feature_blocks=1")


# --------------------------------------------------------------------------
# engine adapters — one uniform surface over the two engines
# --------------------------------------------------------------------------
def validate_data(X, y) -> None:
    """One clear ``ValueError`` for data the solvers cannot fit — empty or
    mismatched shapes, non-finite entries — raised at the api boundary
    (``solve`` / the estimators / ``submit_fit``) before anything is
    traced or compiled. Inside an enclosing trace the finiteness check is
    skipped (values are abstract there); shapes are still checked."""
    if X.size == 0:
        raise ValueError(f"X is empty (shape {tuple(X.shape)}); there is "
                         "nothing to fit")
    n_rows = X.shape[0] if X.ndim == 2 else X.shape[0] * X.shape[1]
    if y.size != n_rows:
        raise ValueError(
            f"y has {y.size} targets but X has {n_rows} sample rows "
            f"(X shape {tuple(X.shape)}, y shape {tuple(y.shape)})")
    if _is_traced(X, y):
        return
    if jnp.issubdtype(X.dtype, jnp.inexact) and not bool(
            jnp.all(jnp.isfinite(X))):
        raise ValueError("X contains non-finite entries (NaN or Inf); "
                         "clean or impute the data before fitting")
    if jnp.issubdtype(y.dtype, jnp.inexact) and not bool(
            jnp.all(jnp.isfinite(y))):
        raise ValueError("y contains non-finite entries (NaN or Inf); "
                         "clean or impute the targets before fitting")


def _stack(X, y):
    """Accept (samples, n) flat or (N, m, n) node-stacked data; return the
    paper's stacked layout (validated — see :func:`validate_data`)."""
    X, y = jnp.asarray(X), jnp.asarray(y)
    if X.ndim not in (2, 3):
        raise ValueError(f"X must be (samples, n) or (N, m, n); "
                         f"got shape {X.shape}")
    with runtime.span("repro.validate"):
        validate_data(X, y)
    if X.ndim == 2:
        X, y = X[None], y.reshape(1, -1)
    return X, y.reshape(X.shape[0], X.shape[1])


class _ReferenceAdapter:
    """The single-process oracle engine behind the uniform surface."""
    name = "reference"

    def __init__(self, problem: SparseProblem, options: SolverOptions):
        self.caps = engine_capabilities("reference", options)
        _check_precision(self.caps, options)
        self.solver = BiCADMM(problem.resolve_loss(),
                              build_config(problem, options))

    def fit(self, As, bs, *, kappa=None, gamma=None, rho_c=None,
            state=None) -> FitResult:
        """One solve; overrides / ``state`` route through ``run_from``."""
        overrides = dict(kappa=kappa, gamma=gamma, rho_c=rho_c)
        if state is None and all(v is None for v in overrides.values()):
            return self.solver.fit(As, bs)
        state = state if state is not None else self.solver.init_state(As, bs)
        return self.solver.run_from(As, bs, state, **overrides)

    def fit_path(self, As, bs, kappas, *, gammas=None, rho_cs=None,
                 warm_start=True) -> SparsePath:
        """Warm-started hyperparameter path (one compiled scan)."""
        _check_sweep(self.caps, gammas, rho_cs)
        return _ref_fit_path(self.solver, As, bs, kappas, gammas=gammas,
                             rho_cs=rho_cs, warm_start=warm_start)

    def fit_grid(self, As, bs, kappas, *, gammas=None, rho_cs=None
                 ) -> SparsePath:
        """Independent cold fits of the grid, vmap-batched."""
        _check_sweep(self.caps, gammas, rho_cs)
        return _ref_fit_grid(self.solver, As, bs, kappas, gammas=gammas,
                             rho_cs=rho_cs)

    def fit_many_stacked(self, As, bs, *, kappas=None, gammas=None,
                         rho_cs=None, states=None,
                         iter_caps=None) -> FleetResult:
        """Stacked fleet fit (capability-checked adapter entry)."""
        _check_fleet(self.caps)
        return _ref_fit_many_stacked(self.solver, As, bs, kappas=kappas,
                                     gammas=gammas, rho_cs=rho_cs,
                                     states=states, iter_caps=iter_caps)

    def fit_many(self, problems, *, kappas=None, gammas=None,
                 rho_cs=None, on_bucket=None) -> list[FitResult]:
        """Heterogeneous fleet fit (capability-checked adapter entry)."""
        _check_fleet(self.caps)
        return _ref_fit_many(self.solver, problems, kappas=kappas,
                             gammas=gammas, rho_cs=rho_cs,
                             on_bucket=on_bucket)


class _ShardedAdapter:
    """The shard_map production engine behind the uniform surface. The
    (N, m, n) node stack goes to the engine as the caller holds it: the
    engine shards its leading axis over `nodes` without a copy."""
    name = "sharded"

    def __init__(self, problem: SparseProblem, options: SolverOptions):
        self.caps = engine_capabilities("sharded", options)
        _check_precision(self.caps, options)
        self.solver = ShardedBiCADMM(
            problem.resolve_loss(), build_config(problem, options),
            options.mesh, nodes_axis=options.nodes_axis,
            feat_axis=options.feat_axis,
            projection=options.sharded_projection,
            x_update=options.x_update)

    def fit(self, As, bs, *, kappa=None, gamma=None, rho_c=None,
            state=None, **kw) -> FitResult:
        """One sharded solve (no per-solve hyperparameter overrides)."""
        if not (kappa is None and gamma is None and rho_c is None):
            raise CapabilityError(
                "per-solve kappa/gamma/rho_c overrides are unavailable on "
                "the sharded engine (Capabilities.per_solve_overrides="
                "False): penalties are baked into its cached per-device "
                "factors — use fit_path for kappa sweeps, or a new problem")
        return self.solver.fit(As, bs, state=state, **kw)

    def fit_path(self, As, bs, kappas, *, gammas=None, rho_cs=None,
                 warm_start=True, **kw) -> SparsePath:
        """Warm-started kappa path: one shard_map + scan call."""
        _check_sweep(self.caps, gammas, rho_cs)
        return self.solver.fit_path(As, bs, kappas, warm_start=warm_start,
                                    **kw)

    def fit_grid(self, As, bs, kappas, *, gammas=None, rho_cs=None
                 ) -> SparsePath:
        """Independent cold fits of the grid. The sharded engine has no
        vmap lane over grid points — this executes as a sequential cold
        scan (shared compile, identical numerics), and the returned path
        says so in ``.strategy`` ("cold-scan")."""
        _check_sweep(self.caps, gammas, rho_cs)
        return self.solver.fit_path(As, bs, kappas, warm_start=False)

    def fit_many_stacked(self, As, bs, **kw) -> FleetResult:
        """Fleet fitting is a reference-engine capability: the sharded
        engine's mesh axes are spent on one problem's rows/features."""
        _check_fleet(self.caps)

    def fit_many(self, problems, **kw) -> list[FitResult]:
        """Unsupported on the sharded engine; raises ``CapabilityError``."""
        _check_fleet(self.caps)


def make_adapter(problem: SparseProblem, options: SolverOptions,
                 engine: str | None = None):
    """Construct the engine adapter (and its solver — all configuration
    validation happens here, at construction time)."""
    engine = engine if engine is not None else select_engine(options)
    if engine == "reference":
        return _ReferenceAdapter(problem, options)
    if engine == "sharded":
        if options.mesh is None:
            raise ValueError("engine='sharded' requires a mesh")
        return _ShardedAdapter(problem, options)
    raise ValueError(f"unknown engine {engine!r}; expected one of {ENGINES}")


# --------------------------------------------------------------------------
# functional entry points (the estimators and legacy shims share these)
# --------------------------------------------------------------------------
def _negotiate(problem, options, As):
    N, m, n = As.shape
    return make_adapter(problem, options,
                        engine=select_engine(options, n_samples=N * m,
                                             n_features=n))


def solve(problem: SparseProblem, X, y, *,
          options: SolverOptions | None = None, state=None) -> FitResult:
    """Solve one :class:`SparseProblem` instance on ``(X, y)``.

    With ``SolverOptions(recovery=RecoveryPolicy(...))`` a solve that
    ends ``SolveStatus.DIVERGED`` is automatically rerun through the
    escalation ladder (see :func:`recover`); every attempt is logged in
    the returned ``FitResult.recovery``.
    """
    options = options if options is not None else SolverOptions()
    As, bs = _stack(X, y)
    res = _negotiate(problem, options, As).fit(As, bs, state=state)
    if (options.recovery is not None and res.status is not None
            and int(res.status) == int(SolveStatus.DIVERGED)):
        res = _run_ladder(problem, options, As, bs, failed=res,
                          policy=options.recovery)
    return res


# --------------------------------------------------------------------------
# divergence recovery — the escalation ladder
# --------------------------------------------------------------------------
def _ladder_plan(problem: SparseProblem, options: SolverOptions,
                 policy: RecoveryPolicy, n: int, overrides: dict):
    """The rungs to try, in order: ``(stage, detail, problem, options)``
    tuples, truncated to ``policy.max_attempts``. Each rung bakes its fix
    into the problem/options pair so the rung's solver genuinely runs the
    changed configuration (and the fault-injection harness can target it
    by config)."""
    plan = []
    if policy.retry:
        plan.append(("retry", "same configuration", problem, options))
    if policy.rho_restart:
        base = overrides.get("rho_c") or problem.rho_c
        rho = base * policy.rho_scale
        plan.append(("rho_restart", f"rho_c={rho:g}",
                     dataclasses.replace(problem, rho_c=rho), options))
    if policy.precision_escalation:
        for preset in runtime.escalation_ladder(options.precision):
            plan.append(("precision", preset, problem,
                         dataclasses.replace(options, precision=preset)))
    if policy.solver_fallback and problem.resolve_loss().name == "squared":
        fallback = "dense" if n <= DENSE_MAX_N else "woodbury"
        if fallback != options.x_solver:
            plan.append(("x_solver", fallback, problem,
                         dataclasses.replace(options, x_solver=fallback)))
    return plan[:policy.max_attempts]


def _ladder_adapter(problem: SparseProblem, options: SolverOptions,
                    cache: dict | None):
    """A reference-engine adapter for one ladder rung, optionally memoized
    (the serve plane passes a per-service cache so quarantined-lane
    retries never pay a second trace for the same rung)."""
    if cache is None:
        return make_adapter(problem, options, engine="reference")
    key = (problem.kappa, problem.gamma, problem.rho_c, problem.alpha,
           problem.rho_b, problem.n_classes,
           getattr(problem.loss, "name", problem.loss), options.x_solver,
           runtime.precision_name(options.precision), options.max_iter,
           options.tol, options.divergence_tol)
    if key not in cache:
        cache[key] = make_adapter(problem, options, engine="reference")
    return cache[key]


def _run_ladder(problem: SparseProblem, options: SolverOptions, As, bs, *,
                failed: FitResult | None, policy: RecoveryPolicy,
                overrides: dict | None = None,
                adapter_cache: dict | None = None) -> FitResult:
    """Execute the recovery ladder on stacked data. Returns the first
    non-DIVERGED attempt's result (with the attempt log in ``.recovery``),
    or the last attempt's result — still DIVERGED — when every rung
    failed. ``overrides`` are per-solve kappa/gamma/rho_c values (the
    serve plane's per-request hyperparameters)."""
    overrides = {k: v for k, v in (overrides or {}).items() if v is not None}
    attempts: list[RecoveryAttempt] = []
    state = None
    result = failed
    if failed is not None:
        attempts = list(failed.recovery or ())
        state = failed.state
        if not isinstance(state, BiCADMMState):
            state = None      # e.g. a sharded-engine state: cold-restart
        state = sanitize_state(state)
    plan = _ladder_plan(problem, options, policy, As.shape[2], overrides)
    for idx, (stage, detail, prob, opts) in enumerate(plan):
        if policy.backoff_s > 0:
            time.sleep(policy.backoff_s * (2 ** idx))
        over = dict(overrides)
        if stage == "rho_restart":
            over.pop("rho_c", None)   # the restarted rho is baked in
        adapter = _ladder_adapter(prob, opts, adapter_cache)
        res = adapter.fit(As, bs, state=state, **over)
        attempts.append(RecoveryAttempt(stage, detail, int(res.status),
                                        int(res.iters)))
        result = res._replace(recovery=tuple(attempts))
        if int(res.status) != int(SolveStatus.DIVERGED):
            return result
        state = sanitize_state(res.state)
    return result


def recover(problem: SparseProblem, X, y, *,
            options: SolverOptions | None = None,
            failed: FitResult | None = None,
            policy: RecoveryPolicy | None = None,
            kappa=None, gamma=None, rho_c=None) -> FitResult:
    """Run the divergence-recovery escalation ladder for ``problem``.

    Rungs, in order (each enabled by the corresponding
    :class:`~repro.core.recovery.RecoveryPolicy` flag): a plain **retry**
    from the sanitized last-finite state of ``failed``; a **rho restart**
    with ``rho_c`` scaled into the provably convergent regime; a
    **precision escalation** (bf16/fp16 → fp32 → fp64 polish when x64 is
    on); and an **x-solver fallback** from iterative pcg to a direct
    woodbury/dense factorization. Execution is on the reference engine.

    Returns the first attempt that does not end DIVERGED (or the last,
    still-DIVERGED, attempt). The attempt log rides
    ``FitResult.recovery``; callers that must not ship garbage raise
    :class:`~repro.core.recovery.SolveDiverged` on a still-DIVERGED
    result (the serve plane does).
    """
    options = options if options is not None else SolverOptions()
    policy = (policy if policy is not None
              else options.recovery or RecoveryPolicy())
    As, bs = _stack(X, y)
    return _run_ladder(problem, options, As, bs, failed=failed,
                       policy=policy,
                       overrides=dict(kappa=kappa, gamma=gamma, rho_c=rho_c))


def solve_path(problem: SparseProblem, X, y, kappas, *,
               options: SolverOptions | None = None, gammas=None,
               rho_cs=None, warm_start: bool = True) -> SparsePath:
    """Warm-started hyperparameter path in one compiled call."""
    options = options if options is not None else SolverOptions()
    As, bs = _stack(X, y)
    return _negotiate(problem, options, As).fit_path(
        As, bs, kappas, gammas=gammas, rho_cs=rho_cs, warm_start=warm_start)


def _stack_many(Xs, ys):
    """Stacked fleet data to the (B, N, m, n) / (B, N, m) layout: accept
    ``(B, samples, n)`` flat (N = 1) or ``(B, N, m, n)`` node-stacked."""
    Xs, ys = jnp.asarray(Xs), jnp.asarray(ys)
    if Xs.ndim == 3:
        Xs = Xs[:, None]
    if Xs.ndim != 4:
        raise ValueError(f"stacked fleet data must be (B, samples, n) or "
                         f"(B, N, m, n); got shape {Xs.shape}")
    validate_data(Xs.reshape(-1, Xs.shape[-1]), ys)
    return Xs, ys.reshape(Xs.shape[0], Xs.shape[1], Xs.shape[2])


def fit_many(problem: SparseProblem, Xs, ys, *, kappas=None, gammas=None,
             rho_cs=None, options: SolverOptions | None = None,
             states=None, iter_caps=None) -> FleetResult | list[FitResult]:
    """Fit a FLEET of B independent instances of ``problem`` — one vmapped
    masked Bi-cADMM driver instead of B compiled calls.

    Two input shapes:

    * stacked arrays — ``Xs (B, samples, n)`` (or node-stacked
      ``(B, N, m, n)``) with matching ``ys``: one shape signature, one
      compiled program; returns a :class:`FleetResult` (``result[i]`` is
      problem i's :class:`FitResult` view). ``states`` warm-starts every
      lane from a previous fleet's ``.state``.
    * a sequence — ``Xs`` / ``ys`` are lists of per-problem arrays with
      possibly mixed shapes: problems are bucketed by ``(N, n)`` signature
      (zero-padded along the sample axis — exact in exact arithmetic; see
      ``repro.core.fleet``) and each bucket runs as one compiled fleet;
      returns a list of :class:`FitResult` in input order.

    ``kappas`` / ``gammas`` / ``rho_cs`` are optional per-problem vectors;
    heterogeneous penalties ride the dynamic (spectral-factor) x-update
    backends exactly like a hyperparameter path. Per-problem convergence
    is masked: each lane matches a solo ``fit`` of that problem exactly in
    iteration count and support, with iterates equal to fp round-off
    (``tests/test_fleet.py``). ``iter_caps`` (stacked input only) caps
    each lane's iteration budget below ``max_iter`` — the serving plane's
    per-lane deadline abort.

    Fleet fitting is capability-negotiated (``Capabilities.fleet``): it
    runs on the reference engine; ``engine="sharded"`` raises
    :class:`CapabilityError`.
    """
    options = options if options is not None else SolverOptions()
    engine = "reference" if options.engine == "auto" else options.engine
    adapter = make_adapter(problem, options, engine=engine)
    if isinstance(Xs, (list, tuple)):
        if not isinstance(ys, (list, tuple)) or len(ys) != len(Xs):
            raise ValueError("sequence input needs per-problem ys of the "
                             "same length as Xs")
        if states is not None or iter_caps is not None:
            raise ValueError("states=/iter_caps= require stacked-array "
                             "input (one shape signature)")
        return adapter.fit_many(list(zip(Xs, ys)), kappas=kappas,
                                gammas=gammas, rho_cs=rho_cs)
    As, bs = _stack_many(Xs, ys)
    return adapter.fit_many_stacked(As, bs, kappas=kappas, gammas=gammas,
                                    rho_cs=rho_cs, states=states,
                                    iter_caps=iter_caps)


def serve(problem: SparseProblem, *, options: SolverOptions | None = None,
          serve_options=None, clock=None):
    """Construct the always-on :class:`~repro.serve.FittingService` for
    ``problem`` — the request-level entry point over the fleet engine.

    The service accepts fit / predict requests (``await service.fit(X, y,
    client_id=..., deadline=...)``), micro-batches compatible requests by
    ``(N, n, loss)`` shape signature into one fleet-driver call, caches
    compiled drivers per signature, and warm-starts returning clients
    from an LRU state pool. Start it with ``async with service:`` (or
    ``await service.start()``); see ``docs/serving.md`` for the operator
    runbook.

    Serving is capability-negotiated (``Capabilities.serve``): it needs
    the vmapped fleet driver, so the reference engine backs it and
    ``engine="sharded"`` (or the feature-split sub-solver) raises
    :class:`CapabilityError` here, before any service machinery spins up.
    """
    options = options if options is not None else SolverOptions()
    engine = "reference" if options.engine == "auto" else options.engine
    _check_serve(engine_capabilities(engine, options))
    from .serve import FittingService
    kw = {} if clock is None else {"clock": clock}
    return FittingService(problem, options, serve_options, **kw)


# --------------------------------------------------------------------------
# streaming — minibatch partial_fit over incrementally maintained factors
# --------------------------------------------------------------------------
class StreamingSolver:
    """Stateful streaming front-end over :class:`~repro.core.streaming.
    StreamingBiCADMM`: one growing (or sliding-window) dataset, fitted
    chunk by chunk through :meth:`partial_fit`.

    Each call absorbs the chunk into the regime's incremental accumulators
    (rank-k Cholesky up/downdates — never a refactorization from data),
    warm-starts the refit from the previous state, and returns a standard
    :class:`FitResult`. ``window`` bounds the replay window in chunks
    (``None`` = keep everything, ``0`` = keep no rows, dense regime only);
    ``drift_tol`` tunes the support-drift re-projection probe.

    With ``SolverOptions(recovery=...)``, a refit that stays DIVERGED
    after the engine's own full-refactorization rung escalates through
    the standard recovery ladder on the replay-window data.
    """

    name = "streaming"

    def __init__(self, problem: SparseProblem,
                 options: SolverOptions | None = None, *,
                 window: int | None = None, drift_tol: float = 0.5):
        options = options if options is not None else SolverOptions()
        engine = "reference" if options.engine == "auto" else options.engine
        self.caps = engine_capabilities(engine, options)
        _check_stream(self.caps)
        _check_precision(self.caps, options)
        self.problem = problem
        self.options = options
        self.engine = StreamingBiCADMM(
            problem.resolve_loss(), build_config(problem, options),
            window=window, drift_tol=drift_tol)

    @property
    def result(self) -> FitResult | None:
        """The latest refit's result (None before the first chunk)."""
        return self.engine.result

    @property
    def m_seen(self) -> int:
        """Total rows absorbed over the stream's lifetime."""
        return self.engine.m_seen

    @property
    def mode(self) -> str | None:
        """The resolved incremental regime (dense/woodbury/pcg/direct)."""
        return self.engine.mode

    def partial_fit(self, X, y, *, kappa=None, gamma=None,
                    rho_c=None) -> FitResult:
        """Absorb one ``(rows, n)`` chunk and refit warm-started.

        Per-call ``kappa`` / ``gamma`` / ``rho_c`` override the problem for
        this refit only (penalty overrides run the maintained-Gram eigh
        fallback — still no recompute from data).
        """
        X, y = jnp.asarray(X), jnp.asarray(y)
        if X.ndim != 2:
            raise ValueError(f"streaming chunks must be (rows, n); "
                             f"got shape {tuple(X.shape)}")
        validate_data(X, y)
        res = self.engine.partial_fit(X, y, kappa=kappa, gamma=gamma,
                                      rho_c=rho_c)
        if (self.options.recovery is not None and res.status is not None
                and int(res.status) == int(SolveStatus.DIVERGED)
                and self.engine._chunks):
            A_win, y_win = self.engine._window_data()
            res = _run_ladder(self.problem, self.options,
                              A_win[None], y_win.reshape(1, -1),
                              failed=res, policy=self.options.recovery,
                              overrides=dict(kappa=kappa, gamma=gamma,
                                             rho_c=rho_c))
            self.engine.adopt(res)
        return res


def stream(problem: SparseProblem, *,
           options: SolverOptions | None = None,
           window: int | None = None,
           drift_tol: float = 0.5) -> StreamingSolver:
    """Open a :class:`StreamingSolver` for ``problem`` — the minibatch
    entry point (``Capabilities.stream``).

    >>> s = stream(SparseProblem(loss="squared", kappa=10, gamma=10.0))
    >>> for X_t, y_t in chunks:
    ...     res = s.partial_fit(X_t, y_t)     # incremental factor updates

    Streaming is capability-negotiated: it maintains the x-update factors
    across chunks, so the reference engine backs it and ``engine="sharded"``
    (or the feature-split sub-solver) raises :class:`CapabilityError` here.
    """
    return StreamingSolver(problem, options, window=window,
                           drift_tol=drift_tol)


def solve_grid(problem: SparseProblem, X, y, kappas, *,
               options: SolverOptions | None = None, gammas=None,
               rho_cs=None) -> SparsePath:
    """Independent cold fits of every grid point — the one grid entry
    point for both engines. How the grid actually executed (vmap-batched
    on the reference engine, a sequential cold scan on the sharded one) is
    recorded in the returned path's ``.strategy``."""
    options = options if options is not None else SolverOptions()
    As, bs = _stack(X, y)
    return _negotiate(problem, options, As).fit_grid(
        As, bs, kappas, gammas=gammas, rho_cs=rho_cs)


# --------------------------------------------------------------------------
# estimators — the four paper models
# --------------------------------------------------------------------------
class SparseEstimator:
    """Base estimator: a declarative :class:`SparseProblem` plus negotiated
    engine, with sklearn-shaped ``fit`` / ``predict`` / ``score``.

    Data may be flat ``(samples, n)`` or the paper's node-stacked
    ``(N, m, n)``; targets match (``(samples,)`` or ``(N, m)``). Solver
    knobs go in ``options=SolverOptions(...)`` or as keyword shorthand
    (``tol=1e-5, mesh=mesh, engine="auto"``).
    """
    _loss_name: str = "squared"
    _score_kind: str = "r2"           # "r2" | "accuracy"

    def __init__(self, kappa: int, *, gamma: float = 1.0,
                 rho_c: float = 1.0, alpha: float = 0.5,
                 rho_b: float | None = None, n_classes: int = 1,
                 options: SolverOptions | None = None, **option_kw):
        if options is not None and option_kw:
            raise ValueError("pass options=SolverOptions(...) or option "
                             "keywords, not both")
        self.problem = SparseProblem(
            loss=self._loss_name, kappa=kappa, n_classes=n_classes,
            gamma=gamma, rho_c=rho_c, alpha=alpha, rho_b=rho_b)
        self.options = (options if options is not None
                        else SolverOptions(**option_kw))
        self._adapters: dict = {}
        if self.options.engine != "auto":
            # explicit engine: build (and validate) it at construction
            self._adapter_named(self.options.engine)
        self.result_: FitResult | None = None
        self._stream: StreamingSolver | None = None

    # -- engine negotiation --------------------------------------------------
    def _adapter_named(self, name: str):
        ad = self._adapters.get(name)
        if ad is None:
            ad = make_adapter(self.problem, self.options, engine=name)
            self._adapters[name] = ad
        return ad

    def _adapter(self, As):
        N, m, n = As.shape
        return self._adapter_named(select_engine(
            self.options, n_samples=N * m, n_features=n))

    # -- fitting -------------------------------------------------------------
    # (after a fit, ``capabilities_`` holds the executed engine's
    # Capabilities; pre-fit introspection goes through the module-level
    # ``engine_capabilities`` / ``select_engine``)
    def fit(self, X, y, *, state=None) -> "SparseEstimator":
        """Fit on ``(X, y)``; ``state=`` warm-starts from a previous
        result's ``.state``. Returns ``self`` (sklearn convention). With
        ``options=SolverOptions(recovery=...)`` a DIVERGED fit reruns
        through the recovery ladder, like :func:`solve`."""
        As, bs = _stack(X, y)
        adapter = self._adapter(As)
        res = adapter.fit(As, bs, state=state)
        if (self.options.recovery is not None and res.status is not None
                and int(res.status) == int(SolveStatus.DIVERGED)):
            res = _run_ladder(self.problem, self.options, As, bs,
                              failed=res, policy=self.options.recovery)
        self._stream = None       # a full fit resets any open stream
        self._set_fitted(adapter, res)
        return self

    def partial_fit(self, X, y, *, window: int | None = None
                    ) -> "SparseEstimator":
        """Absorb one ``(rows, n)`` chunk and refit incrementally.

        The first call opens a :class:`StreamingSolver` (``window=``
        bounds its replay window in chunks and is honored on that call
        only); subsequent calls stream into it — rank-k factor updates
        plus a warm-started refit, never a from-scratch factorization. A
        later full :meth:`fit` resets the stream. Returns ``self``.
        """
        stream_ = getattr(self, "_stream", None)
        if stream_ is None:
            stream_ = StreamingSolver(self.problem, self.options,
                                      window=window)
            self._stream = stream_
        res = stream_.partial_fit(X, y)
        self._set_fitted(stream_, res)
        return self

    def fit_path(self, X, y, kappas, *, gammas=None, rho_cs=None,
                 warm_start: bool = True) -> SparsePath:
        """Warm-started sweep; the estimator is left fitted on the LAST
        grid point (the sparsest, for descending kappa ladders)."""
        As, bs = _stack(X, y)
        adapter = self._adapter(As)
        path = adapter.fit_path(As, bs, kappas, gammas=gammas,
                                rho_cs=rho_cs, warm_start=warm_start)
        self._set_fitted(adapter, self._last_point(path))
        return path

    def fit_grid(self, X, y, kappas, *, gammas=None, rho_cs=None
                 ) -> SparsePath:
        """Independent cold fits; ``path.strategy`` reports how the grid
        actually executed (``"vmap"`` / ``"cold-scan"``)."""
        As, bs = _stack(X, y)
        adapter = self._adapter(As)
        path = adapter.fit_grid(As, bs, kappas, gammas=gammas,
                                rho_cs=rho_cs)
        self._set_fitted(adapter, self._last_point(path))
        return path

    @staticmethod
    def _last_point(path: SparsePath) -> FitResult:
        status = None if path.status is None else path.status[-1]
        return FitResult(path.coef[-1], path.z[-1], path.support[-1],
                         path.iters[-1], path.p_r[-1], path.d_r[-1],
                         path.b_r[-1], state=path.state, status=status)

    def _set_fitted(self, adapter, res: FitResult) -> None:
        self.result_ = res
        K = self.problem.n_classes
        self.coef_ = res.coef[:, 0] if K == 1 else res.coef
        self.support_ = res.support
        self.n_iter_ = int(res.iters)
        self.engine_ = adapter.name
        self.capabilities_ = adapter.caps

    # -- inference -----------------------------------------------------------
    def _scores(self, X):
        if self.result_ is None:
            raise RuntimeError("estimator is not fitted; call fit() first")
        X = jnp.asarray(X)
        if X.ndim == 3:
            X = X.reshape(-1, X.shape[-1])
        scores = X @ self.result_.coef            # (samples, K)
        return scores[:, 0] if self.problem.n_classes == 1 else scores

    def decision_function(self, X):
        """Raw decision values: residual fit / margins / ``(m, C)``
        logits, per the loss's ``decision`` map."""
        return self.problem.resolve_loss().decision(self._scores(X))

    def predict(self, X):
        """Predicted targets: response (regression), {-1, +1} labels
        (margin losses) or argmax class labels (softmax)."""
        return self.problem.resolve_loss().predict(self._scores(X))

    def score(self, X, y) -> float:
        """R^2 for regression, accuracy for classification."""
        y = jnp.asarray(y).reshape(-1)
        yhat = self.predict(X)
        if self._score_kind == "accuracy":
            return float(jnp.mean(yhat == y))
        ss_res = jnp.sum((y - yhat) ** 2)
        ss_tot = jnp.sum((y - jnp.mean(y)) ** 2)
        return float(1.0 - ss_res / jnp.maximum(ss_tot, 1e-30))


class SparseLinearRegression(SparseEstimator):
    """SLR: exact-l0 least squares (the paper's SLS experiments)."""
    _loss_name = "squared"
    _score_kind = "r2"


class SparseLogisticRegression(SparseEstimator):
    """SLogR: exact-l0 logistic regression, labels in {-1, +1}."""
    _loss_name = "logistic"
    _score_kind = "accuracy"


class SparseSVM(SparseEstimator):
    """SSVM: exact-l0 support vector machine. Defaults to the Huberized
    (smoothed) hinge the solver converges fastest with; pass
    ``hinge="plain"`` for the non-smooth hinge prox."""
    _loss_name = "smoothed_hinge"
    _score_kind = "accuracy"

    def __init__(self, kappa: int, *, hinge: str = "smoothed", **kw):
        if hinge not in ("smoothed", "plain"):
            raise ValueError(f"hinge must be 'smoothed' or 'plain', "
                             f"got {hinge!r}")
        self._loss_name = "smoothed_hinge" if hinge == "smoothed" else "hinge"
        super().__init__(kappa, **kw)


class SparseSoftmaxRegression(SparseEstimator):
    """SSR: exact-l0 softmax (multinomial logistic) regression over C
    classes; ``coef_`` is ``(n, C)`` and ``kappa`` budgets the flattened
    ``(n*C,)`` coefficient vector, exactly as in the paper."""
    _loss_name = "softmax"
    _score_kind = "accuracy"

    def __init__(self, kappa: int, n_classes: int, **kw):
        super().__init__(kappa, n_classes=n_classes, **kw)


# --------------------------------------------------------------------------
# legacy-config bridge (deprecation shims in repro.core call these)
# --------------------------------------------------------------------------
_PROBLEM_KEYS = ("gamma", "rho_c", "alpha", "rho_b")
_SHARDED_KEY_MAP = {"projection": "sharded_projection",
                    "x_update": "x_update", "nodes_axis": "nodes_axis",
                    "feat_axis": "feat_axis"}


def split_legacy_config(loss, *, kappa: int, n_classes: int = 1,
                        engine: str = "reference", mesh=None, **cfg_kw
                        ) -> tuple[SparseProblem, SolverOptions]:
    """Split flat ``BiCADMMConfig``-style kwargs into the declarative
    (problem, options) pair — the bridge the deprecated
    ``fit_sparse_model`` entry point runs through."""
    prob_kw = {k: cfg_kw.pop(k) for k in _PROBLEM_KEYS if k in cfg_kw}
    problem = SparseProblem(loss=loss, kappa=kappa, n_classes=n_classes,
                            **prob_kw)
    options = SolverOptions(engine=engine, mesh=mesh, **cfg_kw)
    return problem, options


def from_config(loss, cfg: BiCADMMConfig, *, n_classes: int = 1,
                engine: str = "reference", mesh=None, **sharded_kw
                ) -> tuple[SparseProblem, SolverOptions]:
    """Lift a legacy ``(loss, BiCADMMConfig, engine kwargs)`` triple into
    the declarative (problem, options) pair — the bridge the deprecated
    ``SolverEngine`` front-end runs through."""
    problem = SparseProblem(loss=loss, kappa=cfg.kappa, n_classes=n_classes,
                            gamma=cfg.gamma, rho_c=cfg.rho_c,
                            alpha=cfg.alpha, rho_b=cfg.rho_b)
    opt_kw = {}
    for key, val in sharded_kw.items():
        if key not in _SHARDED_KEY_MAP:
            raise TypeError(f"unknown sharded option {key!r}")
        opt_kw[_SHARDED_KEY_MAP[key]] = val
    options = SolverOptions(
        engine=engine, mesh=mesh, max_iter=cfg.max_iter, tol=cfg.tol,
        zt_iters=cfg.zt_iters, x_solver=cfg.x_solver,
        n_feature_blocks=cfg.n_feature_blocks, inner_iters=cfg.inner_iters,
        rho_l=cfg.rho_l, newton_iters=cfg.newton_iters,
        cg_iters=cfg.cg_iters, cg_tol=cfg.cg_tol,
        force_feature_split=cfg.force_feature_split,
        projection=cfg.projection, polish=cfg.polish,
        over_relax=cfg.over_relax, precision=cfg.precision, **opt_kw)
    return problem, options
