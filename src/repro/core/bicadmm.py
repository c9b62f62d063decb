"""Bi-cADMM — Algorithm 1 of the paper (single-process reference engine).

Solves      min_x  sum_i l_i(A_i x, b_i) + 1/(2 gamma) ||x||^2
            s.t.   ||x||_0 <= kappa

via the bi-linear consensus reformulation (3) and the ADMM splitting (7):

  (7a) x_i  <- prox of the local loss           [per node, data-local]
  (7b) (z,t)<- QP over the l1-epigraph cone     [FISTA + exact cone projection]
  (7c) s    <- closed form over S^kappa         [repro.core.bilinear.s_update]
  (7d) u_i  <- u_i + x_i - z                    [scaled consensus dual]
  (7e) v    <- v + g(z, s, t)                   [scaled bi-linear dual]

Residuals (14) drive termination. The x-update runs either through the
direct prox engines (repro.core.prox) or the paper's feature-split inner
ADMM (repro.core.subsolver) selected by ``n_feature_blocks > 1``.

Note on signs: the paper's eq (4) writes ``y_i^T (z - x_i)`` but its scaled
updates (8)-(9) follow the standard Boyd consensus form; we follow (8)-(9),
under which the (z,t) data-fidelity center is ``w = mean_i (x_i + u_i)``.

Resumable-state API
-------------------
The while-loop state is first-class, which makes warm starts (and the
hyperparameter-path engine in ``repro.core.path``) possible:

* ``init_state(As, bs)``    — build a fresh :class:`BiCADMMState`.
* ``run_from(As, bs, state, kappa=..., gamma=..., rho_c=...)`` — reset the
  iteration counter / residuals of ``state``, run the (jitted) while-loop
  from it, and return a :class:`BiCADMMResult` whose ``.state`` field is the
  final solver state — feed it back into ``run_from`` to warm-start the next
  solve (e.g. the next kappa on a sparsity path).
* ``fit(As, bs)``           — ``run_from`` from ``init_state`` (unchanged
  one-shot behavior).

``kappa`` / ``gamma`` / ``rho_c`` overrides may be traced scalars, so whole
hyperparameter grids run inside one ``lax.scan`` / ``vmap`` (see
``repro.core.path``). The squared-loss x-update runs through the
:class:`repro.core.prox.NodeProxEngine` backends selected by
``cfg.x_solver`` ("auto" picks dense Cholesky for small n, the m x m
Woodbury dual factor when m << n, matrix-free warm-started PCG when both
axes are large — no n x n array exists off the dense path). Dynamic
``gamma`` / ``rho_c`` switch the factorization backends to their spectral
(eigh) variants whose shift is applied at solve time; the feature-split
inner ADMM bakes the penalties into its per-block factors and therefore
only supports dynamic ``kappa``. Setup factors are cached on the data
arrays so repeated ``run_from`` calls factorize once.

The distributed (shard_map) engine with identical semantics lives in
``repro.core.sharded``; this module is the oracle it is tested against.

Tracing: a fit opens the host spans ``repro.layout``, ``repro.setup``,
``repro.loop``, ``repro.finalize`` and ``repro.polish``
(``repro.runtime.SPANS``); the outer loop and the polish run as the
device programs ``jit_repro_loop`` and ``jit_repro_polish``; the state
counts the x-update's CG steps (``cg_steps``) and the result carries it
with the polish's in ``FitResult.counters``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from . import bilinear, prox
from .losses import Loss, get_loss
from .results import FitResult, classify_status, divergence_probe
from .prox import (NodeProxEngine, newton_cg_prox, x_solve)
from .subsolver import (SubsolverFactors, SubsolverState, node_prox_feature_split,
                        subsolver_init, subsolver_setup)
from .. import faults, runtime
from ..kernels.ops import (gram_auto, kernel_operand, matvec_auto,
                           normal_matvec_auto, rmatvec_auto)

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class BiCADMMConfig:
    kappa: int
    gamma: float = 1.0
    rho_c: float = 1.0
    alpha: float = 0.5              # paper: rho_b = alpha * rho_c, alpha in (0,1]
    rho_b: float | None = None
    max_iter: int = 300
    tol: float = 1e-4               # applied to p_r / d_r / b_r
    # residual level past which a run is declared DIVERGED in-loop (the
    # isfinite probe fires regardless); see repro.core.results.
    divergence_tol: float = 1e12
    zt_iters: int = 120             # FISTA iterations for step (7b)
    n_feature_blocks: int = 1       # M (Algorithm 2) ; 1 => direct prox
    inner_iters: int = 15           # inner ADMM iterations per x-update
    rho_l: float = 1.0              # inner ADMM penalty
    newton_iters: int = 12          # direct Newton-CG prox iterations
    polish: bool = True             # debias on the recovered support
    over_relax: float = 1.0         # 1.0 = paper-faithful; 1.5-1.8 typical
    force_feature_split: bool = False  # use Algorithm 2 even when M == 1
    projection: str = "ladder"      # "ladder" (sort-free exact) | "sort"
    # squared-loss x-update backend (repro.core.prox.NodeProxEngine):
    # "auto" picks dense Cholesky/eigh for small n, the m x m Woodbury dual
    # factor when m << n, matrix-free Jacobi-PCG when both axes are large.
    x_solver: str = "auto"          # "auto" | "dense" | "woodbury" | "pcg"
    cg_iters: int = 200             # PCG max iterations per x-update
    cg_tol: float = 1e-6            # PCG relative-residual tolerance
    # Mixed-precision policy (repro.runtime.PrecisionPolicy): data storage
    # dtype, accumulation dtype for factors/Grams, solver-state dtype, and
    # an optional fp64 KKT-polish dtype for the ladder refinement. Accepts
    # a preset name ("fp32", "bf16", "fp16", "fp64_polish") or a policy.
    precision: "runtime.PrecisionPolicy | str" = "fp32"

    def __post_init__(self):
        object.__setattr__(self, "precision",
                           runtime.resolve_precision(self.precision))
        if self.divergence_tol <= 0:
            raise ValueError("divergence_tol must be positive")

    @property
    def rho_b_eff(self) -> float:
        return self.rho_b if self.rho_b is not None else self.alpha * self.rho_c

    @property
    def use_feature_split(self) -> bool:
        return self.n_feature_blocks > 1 or self.force_feature_split


class SolveParams(NamedTuple):
    """Per-solve hyperparameters. Entries may be Python floats (static) or
    traced scalars (dynamic, e.g. the scan/vmap axes of the path engine)."""
    kappa: Array | float
    rho_c: Array | float
    rho_b: Array | float
    sigma: Array | float      # 1 / (N * gamma)


class BiCADMMState(NamedTuple):
    x: Array          # (N, n*K) local estimates
    u: Array          # (N, n*K) scaled consensus duals
    z: Array          # (n*K,)
    t: Array          # ()
    s: Array          # (n*K,)
    v: Array          # () scaled bi-linear dual
    k: Array          # iteration counter
    p_r: Array
    d_r: Array
    b_r: Array
    inner: Any        # SubsolverState pytree stacked over nodes (or None)
    cg_steps: Array   # () int32 x-update CG steps, over nodes and iterations


# Both engines return the engine-agnostic result type; the old name is kept
# as an alias for pre-redesign imports.
BiCADMMResult = FitResult


def _is_traced(*pytrees) -> bool:
    """True when any leaf is a tracer — i.e. we are inside an enclosing
    jit/vmap/scan trace, where buffer donation is unusable."""
    return any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree.leaves(pytrees))


def reset_for_resume(st: BiCADMMState) -> BiCADMMState:
    """Zero the counters and residuals so a (possibly converged) state
    re-enters the while-loop; the iterates (x,u,z,t,s,v) are kept.

    Each residual gets its own buffer (no aliasing) so the state stays a
    valid donation argument for the jitted while-loop drivers."""
    dt = st.z.dtype
    return st._replace(k=jnp.asarray(0), cg_steps=jnp.zeros((), jnp.int32),
                       p_r=jnp.asarray(jnp.inf, dt),
                       d_r=jnp.asarray(jnp.inf, dt),
                       b_r=jnp.asarray(jnp.inf, dt))


def _zt_update(z0: Array, t0: Array, w: Array, s: Array, v: Array,
               N: float, rho_c: float, rho_b: float, iters: int, *,
               ops: bilinear.LadderOps | None = None,
               projection: str = "ladder", rounds: int | None = None,
               polish_dtype=None) -> tuple[Array, Array]:
    """Step (7b): min over {(z,t): ||z||_1 <= t} of
        (N rho_c / 2) ||z - w||^2 + (rho_b / 2) (s^T z - t + v)^2
    by FISTA with the exact cone projection — sort-free (ladder-refinement)
    by default, ``projection="sort"`` for the retired oracle.

    ``ops`` makes every reduction injectable: the reference engine passes
    the replicated defaults, ``repro.core.sharded`` passes psum/pmax over
    the ``feat`` axis — the SAME code then runs on local shards with O(B)
    collectives per projection, and on a single device the two engines are
    bit-identical. The fused ladder path computes |y| of the gradient step
    once per FISTA iteration and reuses it for the refinement passes and
    the final soft-threshold; no sort, no O(n) gather.
    """
    ops = bilinear.DEFAULT_OPS if ops is None else ops
    a = N * rho_c
    L = a + rho_b * (ops.sum_fn(s * s) + 1.0)  # ||Hessian||_2 upper bound
    step = 1.0 / L

    if projection == "sort":
        project = bilinear.project_l1_epigraph_sort
    else:
        project = partial(bilinear.project_l1_epigraph, ops=ops,
                          rounds=rounds, polish_dtype=polish_dtype)

    def grads(z, t):
        r = ops.sum_fn(s * z) - t + v
        return a * (z - w) + rho_b * r * s, -rho_b * r

    def body(_, carry):
        z, t, zy, ty, tk = carry
        gz, gt = grads(zy, ty)
        z_new, t_new = project(zy - step * gz, ty - step * gt)
        tk_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * tk * tk))
        beta = (tk - 1.0) / tk_new
        zy_new = z_new + beta * (z_new - z)
        ty_new = t_new + beta * (t_new - t)
        return z_new, t_new, zy_new, ty_new, tk_new

    z0p, t0p = project(z0, t0)
    z, t, *_ = jax.lax.fori_loop(
        0, iters, body, (z0p, t0p, z0p, t0p, jnp.asarray(1.0, z0.dtype)))
    return z, t


class BiCADMM:
    """Reference Bi-cADMM solver. Data: stacked (N, m, n) features and
    (N, m) targets — the paper's equal sample decomposition."""

    _SETUP_CACHE_MAX = 4

    def __init__(self, loss: Loss | str, cfg: BiCADMMConfig, *,
                 n_classes: int = 1):
        self.loss = get_loss(loss, n_classes) if isinstance(loss, str) else loss
        if cfg.projection not in ("ladder", "sort"):
            raise ValueError(f"unknown projection mode {cfg.projection!r}")
        if cfg.x_solver not in prox.XSOLVERS:
            raise ValueError(f"unknown x_solver {cfg.x_solver!r}; expected "
                             f"one of {prox.XSOLVERS}")
        runtime.check_x64(cfg.precision)
        self.cfg = cfg
        # fault-injection hook (repro.faults): None outside an inject()
        # context — the compiled programs are then exactly the healthy
        # ones. Captured once at construction so a hook stays pinned to
        # this instance's jit caches and never leaks across solvers.
        self._fault_hook = faults.active_hook(self)
        # memoized policy data casts keyed on the incoming array ids, so
        # repeated calls hand back the SAME cast arrays and the id-keyed
        # setup cache below still hits across warm-started run_from calls.
        self._cast_cache: dict = {}
        self._layout_cache: dict = {}
        # setup factors (Gram / Cholesky / eigh / Woodbury) keyed on the
        # data arrays, so repeated warm-started run_from calls — the
        # resumable-state workflow — pay the factorization once. Entries
        # hold strong references to the keyed arrays, which keeps their
        # ids valid for the lifetime of the entry.
        self._setup_cache: dict = {}
        # per-INSTANCE jitted while-loop driver for run_from (built lazily):
        # a module-level jit with the solver as a static argument would pin
        # every instance — and its data-holding setup cache — in the global
        # jit cache forever; a closure stored on self dies with the solver.
        # The incoming state pytree is donated, so XLA reuses the iterate
        # buffers (x, u, z, ...) in place instead of copying them — the
        # peak live footprint of a resumed solve is one state, not two.
        # The function names name the device programs in a profile.
        def repro_loop(factors, As, bs, params, st0):
            return self._run_while(factors, As, bs, params, st0)
        self._run_while_donated = jax.jit(repro_loop, donate_argnums=(4,))

        # the polish runs as one program too: reshaping the node stack to
        # (N*m, n) inside it is free, where an eager reshape copies A.
        # ``_polish`` hands its CG steps out through a list, read in the
        # same trace, so that it keeps returning the coefficients alone.
        def repro_polish(As, bs, support, z0, params):
            steps = []
            x = self._polish(As, bs, support, z0, params, steps)
            return x, steps[0]
        self._polish_jit = jax.jit(repro_polish)

    def _cast(self, As: Array, bs: Array) -> tuple[Array, Array]:
        """Apply the precision policy's data cast (no-op for data=None)
        and lay A out as the kernels read it (``kernel_operand``: a no-op
        unless the device stores A in another dimension order)."""
        pol = self.cfg.precision
        if _is_traced(As, bs):
            return pol.cast_data(As), pol.cast_data(bs)
        with runtime.span("repro.layout"):
            key = (id(As), id(bs))
            hit = self._cast_cache.get(key)
            if hit is None:
                A2 = self._kernel_operand(pol.cast_data(As))
                if pol.data is None and A2 is As:
                    return As, bs
                if len(self._cast_cache) >= self._SETUP_CACHE_MAX:
                    self._cast_cache.pop(next(iter(self._cast_cache)))
                hit = (As, bs, A2, pol.cast_data(bs))
                self._cast_cache[key] = hit
            return hit[2], hit[3]

    def _kernel_operand(self, As: Array) -> Array:
        """``kernel_operand(As)``, memoized on A alone: refits on the same
        A with new targets reuse one relaid-out copy."""
        hit = self._layout_cache.get(id(As))
        if hit is None:
            hit = (As, kernel_operand(As))
            if hit[1] is As:
                return As
            if len(self._layout_cache) >= self._SETUP_CACHE_MAX:
                self._layout_cache.pop(next(iter(self._layout_cache)))
            self._layout_cache[id(As)] = hit
        return hit[1]

    def _x_engine(self, m: int, n: int, dynamic: bool) -> NodeProxEngine:
        cfg = self.cfg
        return NodeProxEngine.choose(m, n, x_solver=cfg.x_solver,
                                     dynamic=dynamic, cg_iters=cfg.cg_iters,
                                     cg_tol=cfg.cg_tol)

    # -- setup ---------------------------------------------------------------
    def _setup(self, As: Array, bs: Array, *, dynamic_penalties: bool = False):
        with runtime.span("repro.setup"):
            return self._setup_factors(As, bs, dynamic_penalties)

    def _setup_factors(self, As: Array, bs: Array, dynamic_penalties: bool):
        cfg = self.cfg
        N, m, n = As.shape
        sigma = 1.0 / (N * cfg.gamma)
        K = self.loss.n_classes
        cacheable = not (isinstance(As, jax.core.Tracer)
                         or isinstance(bs, jax.core.Tracer))
        key = (id(As), id(bs), As.shape, bs.shape, str(As.dtype),
               bool(dynamic_penalties))
        if cacheable and key in self._setup_cache:
            return self._setup_cache[key][-1]
        if cfg.use_feature_split:
            if dynamic_penalties:
                raise ValueError(
                    "dynamic gamma/rho_c are not supported with the "
                    "feature-split sub-solver (penalties are baked into its "
                    "cached per-block factors); sweep kappa only, or use "
                    "n_feature_blocks=1")
            factors = jax.vmap(
                lambda A: subsolver_setup(A, sigma, cfg.rho_c, cfg.rho_l,
                                          cfg.n_feature_blocks))(As)
        elif self.loss.name == "squared":
            eng = self._x_engine(m, n, dynamic_penalties)
            factors = jax.vmap(
                lambda A, b: eng.setup(A, b, sigma, cfg.rho_c))(As, bs)
        else:
            factors = None
        out = (factors, N, n, K)
        if cacheable:
            if len(self._setup_cache) >= self._SETUP_CACHE_MAX:
                self._setup_cache.pop(next(iter(self._setup_cache)))
            self._setup_cache[key] = (As, bs, out)
        return out

    def _make_params(self, N: int, *, kappa=None, gamma=None, rho_c=None
                     ) -> SolveParams:
        cfg = self.cfg
        kappa = cfg.kappa if kappa is None else kappa
        gamma = cfg.gamma if gamma is None else gamma
        rho_c = cfg.rho_c if rho_c is None else rho_c
        rho_b = cfg.rho_b if cfg.rho_b is not None else cfg.alpha * rho_c
        return SolveParams(kappa=kappa, rho_c=rho_c, rho_b=rho_b,
                           sigma=1.0 / (N * gamma))

    def _x_update(self, factors, params: SolveParams, As, bs, q, x_prev,
                  inner):
        """q: (N, n*K) prox centers, x_prev: (N, n*K) previous outer
        iterates (PCG warm start) -> (N, n*K), new inner state, CG steps
        summed over nodes (() int32; 0 for the direct solves)."""
        cfg, loss = self.cfg, self.loss
        N, m, n = As.shape
        K = loss.n_classes

        if cfg.use_feature_split:
            def one(f, b, qi, st):
                x, st = node_prox_feature_split(
                    loss, f, b, qi.reshape(n, K), cfg.inner_iters, st)
                return x.reshape(-1), st
            x, inner = jax.vmap(one)(factors, bs, q, inner)
            return x, inner, jnp.zeros((), jnp.int32)

        if loss.name == "squared":
            def one(f, qi, xi):
                return x_solve(f, qi, params.rho_c, params.sigma, x0=xi)
            x, steps = jax.vmap(one)(factors, q, x_prev)
            return x, inner, jnp.sum(steps, dtype=jnp.int32)

        def one(A, b, qi):
            qx = qi.reshape(n, K) if K > 1 else qi
            x, steps = newton_cg_prox(loss, A, b, qx, params.sigma,
                                      params.rho_c,
                                      newton_iters=cfg.newton_iters)
            return x.reshape(-1), steps
        x, steps = jax.vmap(one)(As, bs, q)
        return x, inner, jnp.sum(steps, dtype=jnp.int32)

    # -- one iteration ---------------------------------------------------------
    def _step(self, factors, As, bs, params: SolveParams,
              st: BiCADMMState) -> BiCADMMState:
        cfg = self.cfg
        N = As.shape[0]
        rho_c, rho_b = params.rho_c, params.rho_b

        q = st.z[None] - st.u                              # (N, d)
        x_new, inner, cg_steps = self._x_update(factors, params, As, bs, q,
                                                st.x, st.inner)

        if cfg.over_relax != 1.0:                          # optional relaxation
            x_eff = cfg.over_relax * x_new + (1.0 - cfg.over_relax) * st.z[None]
        else:
            x_eff = x_new

        w = jnp.mean(x_eff + st.u, axis=0)                 # consensus center
        z_new, t_new = _zt_update(st.z, st.t, w, st.s, st.v,
                                  float(N), rho_c, rho_b, cfg.zt_iters,
                                  projection=cfg.projection,
                                  polish_dtype=cfg.precision.kkt_polish)
        s_new = bilinear.s_update(
            z_new, t_new, st.v, params.kappa,
            method=("sort" if cfg.projection == "sort" else "ladder"))
        u_new = st.u + x_eff - z_new[None]
        gval = bilinear.g(z_new, s_new, t_new)
        v_new = st.v + gval

        p_r = jnp.sum(jnp.linalg.norm(x_new - z_new[None], axis=1))
        d_r = jnp.sqrt(float(N)) * rho_c * jnp.linalg.norm(z_new - st.z)
        b_r = jnp.abs(gval)
        return BiCADMMState(x_new, u_new, z_new, t_new, s_new, v_new,
                            st.k + 1, p_r, d_r, b_r, inner,
                            st.cg_steps + cg_steps)

    def _init_state(self, As, bs, n, K) -> BiCADMMState:
        cfg = self.cfg
        N, m, _ = As.shape
        d = n * K
        # solver-state dtype: with reduced-precision data the iterates stay
        # in the policy's state dtype (f32 by default) — only the A-products
        # touch the narrow storage.
        dt = jnp.dtype(cfg.precision.state_dtype(As.dtype))
        inner = None
        if cfg.use_feature_split:
            M = cfg.n_feature_blocks
            nb = -(-n // M)
            inner = SubsolverState(
                x_blocks=jnp.zeros((N, M, nb, K), dt),
                nu=jnp.zeros((N, m, K), dt),
                omega_bar=jnp.zeros((N, m, K), dt))
        return BiCADMMState(
            x=jnp.zeros((N, d), dt), u=jnp.zeros((N, d), dt),
            z=jnp.zeros((d,), dt), t=jnp.asarray(0.0, dt),
            s=jnp.zeros((d,), dt), v=jnp.asarray(0.0, dt),
            k=jnp.asarray(0), p_r=jnp.asarray(jnp.inf, dt),
            d_r=jnp.asarray(jnp.inf, dt), b_r=jnp.asarray(jnp.inf, dt),
            inner=inner, cg_steps=jnp.zeros((), jnp.int32))

    # -- drivers ---------------------------------------------------------------
    def init_state(self, As: Array, bs: Array) -> BiCADMMState:
        """Public resumable-state entry point: a fresh zero state."""
        As, bs = self._cast(As, bs)
        return self._init_state(As, bs, As.shape[2], self.loss.n_classes)

    def _run_while(self, factors, As, bs, params: SolveParams,
                   st0: BiCADMMState) -> BiCADMMState:
        cfg = self.cfg

        def cond(st: BiCADMMState):
            converged = ((st.p_r < cfg.tol) & (st.d_r < cfg.tol)
                         & (st.b_r < cfg.tol))
            diverged = divergence_probe(st, cfg.divergence_tol)
            return (~converged) & (~diverged) & (st.k < cfg.max_iter)

        step = partial(self._step, factors, As, bs, params)
        step = self._with_fault_hook(step)
        return jax.lax.while_loop(cond, step, st0)

    def _with_fault_hook(self, step):
        """``step`` composed with the instance's fault hook (identity when
        no injection was active at construction — the common case)."""
        if self._fault_hook is None:
            return step
        hook = self._fault_hook
        return lambda st: hook(step(st))

    # -- fleet (batched-problem) driver ------------------------------------
    def _fleet_active(self, st: BiCADMMState, iter_caps=None) -> Array:
        """(B,) mask of lanes still iterating: not converged, budget left.
        The per-lane predicate is exactly the solo driver's ``cond``;
        ``iter_caps`` (an optional (B,) int vector) tightens the iteration
        budget per lane — the serving plane translates request deadlines
        into caps, and zero-cap lanes never run (batch-axis padding)."""
        cfg = self.cfg
        converged = ((st.p_r < cfg.tol) & (st.d_r < cfg.tol)
                     & (st.b_r < cfg.tol))
        diverged = divergence_probe(st, cfg.divergence_tol)
        budget = (cfg.max_iter if iter_caps is None
                  else jnp.minimum(iter_caps, cfg.max_iter))
        return (~converged) & (~diverged) & (st.k < budget)

    def _run_while_fleet(self, factors, As, bs, params: SolveParams,
                         st0: BiCADMMState, iter_caps=None) -> BiCADMMState:
        """Masked-step batched while-loop: every argument carries a leading
        problem axis B (data, factors, per-problem ``SolveParams`` entries,
        and the state). One compiled loop runs while ANY lane is active;
        converged lanes freeze — their iterates, residuals, and iteration
        counters are held by a per-lane select, so each lane's final state
        is bit-identical to a solo :meth:`run_from` on that problem
        (certified in ``tests/test_fleet.py``). The wasted step compute of
        frozen lanes is the price of one fused program; for fleets of
        similar problems the slowest lane dominates anyway.

        ``iter_caps`` caps each lane's iteration budget below the config's
        ``max_iter`` (per-lane deadline abort); a cap of 0 makes the lane
        inert from step one, which is how the serving micro-batcher pads
        the batch axis to a cached compile shape at zero solver cost.
        """
        step = jax.vmap(self._step, in_axes=(0, 0, 0, 0, 0))
        hook = self._fault_hook

        def cond(st: BiCADMMState):
            return jnp.any(self._fleet_active(st, iter_caps))

        def body(st: BiCADMMState):
            active = self._fleet_active(st, iter_caps)
            new = step(factors, As, bs, params, st)
            if hook is not None:
                new = hook(new)

            def freeze(n, o):
                mask = active.reshape(active.shape + (1,) * (n.ndim - 1))
                return jnp.where(mask, n, o)
            return jax.tree.map(freeze, new, st)

        return jax.lax.while_loop(cond, body, st0)

    def run_from(self, As: Array, bs: Array, state: BiCADMMState, *,
                 kappa=None, gamma=None, rho_c=None) -> BiCADMMResult:
        """Run until residual tolerances or max_iter, warm-starting from
        ``state`` (counter/residuals are reset first; iterates are kept).

        ``kappa`` / ``gamma`` / ``rho_c`` override the config per-solve and
        may be traced scalars — this is the primitive the path engine scans.

        The setup factors are cached on the data arrays (repeated
        warm-started calls factorize once) and the while-loop runs as one
        jitted program whose state input is donated — ``state`` is
        consumed: its buffers are reused for the result iterates, so keep
        using the returned ``result.state``, not the object passed in.
        """
        dyn = gamma is not None or rho_c is not None
        As, bs = self._cast(As, bs)
        factors, N, n, K = self._setup(As, bs, dynamic_penalties=dyn)
        params = self._make_params(N, kappa=kappa, gamma=gamma, rho_c=rho_c)
        st0 = reset_for_resume(state)
        with runtime.span("repro.loop"):
            if _is_traced(As, bs, st0):
                # Inside an outer trace (vmap/jit/scan — e.g. the sparsify
                # path vmaps whole fits): the state leaves are tracers,
                # which cannot be donated — the jitted donating driver
                # would emit "Some donated buffers were not usable"
                # UserWarnings on every call. Inline the while-loop into
                # the enclosing trace instead; the outer jit owns buffer
                # reuse there.
                st = self._run_while(factors, As, bs, params, st0)
            else:
                st = self._run_while_donated(factors, As, bs, params, st0)
        return self._finalize(As, bs, st, params, history=None)

    def fit(self, As: Array, bs: Array) -> BiCADMMResult:
        """Run until residual tolerances or max_iter (jitted while_loop)."""
        return self.run_from(As, bs, self.init_state(As, bs))

    def fit_with_history(self, As: Array, bs: Array,
                         iters: int | None = None) -> BiCADMMResult:
        """Fixed-iteration scan recording residual traces (Fig. 1)."""
        As, bs = self._cast(As, bs)
        factors, N, n, K = self._setup(As, bs)
        params = self._make_params(N)
        iters = iters or self.cfg.max_iter
        st0 = self._init_state(As, bs, n, K)
        step = self._with_fault_hook(partial(self._step, factors, As, bs,
                                             params))

        def body(st, _):
            st = step(st)
            return st, dict(p_r=st.p_r, d_r=st.d_r, b_r=st.b_r,
                            card=jnp.sum(jnp.abs(st.z) > 1e-6))
        st, hist = jax.lax.scan(body, st0, None, length=iters)
        return self._finalize(As, bs, st, params, history=hist)

    def _finalize(self, As, bs, st: BiCADMMState, params: SolveParams,
                  history) -> FitResult:
        cfg = self.cfg
        with runtime.span("repro.finalize"):
            z_sparse = bilinear.hard_threshold(st.z, params.kappa)
            support = jnp.abs(z_sparse) > 0
            if cfg.polish:
                with runtime.span("repro.polish"):
                    x_final, polish_steps = self._polish_jit(
                        As, bs, support, z_sparse, params)
            else:
                x_final, polish_steps = z_sparse, jnp.zeros((), jnp.int32)
            coef = x_final.reshape(As.shape[2], self.loss.n_classes)
            status = classify_status(st.k, st.p_r, st.d_r, st.b_r,
                                     tol=cfg.tol,
                                     divergence_tol=cfg.divergence_tol)
        return FitResult(coef, st.z, support, st.k,
                         st.p_r, st.d_r, st.b_r, history, st,
                         status=status,
                         counters={"xupdate_cg_steps": st.cg_steps,
                                   "polish_cg_steps": polish_steps})

    def _polish(self, As, bs, support: Array, z0: Array,
                params: SolveParams, steps: list | None = None) -> Array:
        """Debias: re-fit restricted to the recovered support (masked ridge).

        Implemented as the full regularized problem plus a large quadratic
        penalty off-support — keeps shapes static under jit. For the
        squared loss the dense masked-ridge solve is kept only while the
        n x n Gram is small (the ``dense`` x-solver regime); beyond that
        the solve is matrix-free Jacobi-PCG on (A^T A + diag(pen + sigma)),
        warm-started at the thresholded iterate — no n x n array exists
        anywhere in a large-d fit. ``steps``, where given, receives the
        solve's CG steps (() int32).
        """
        cfg, loss = self.cfg, self.loss

        def done(x, k):
            if steps is not None:
                steps.append(jnp.asarray(k, jnp.int32))
            return jnp.where(support, x, 0.0)

        N, m, n = As.shape
        K = loss.n_classes
        sigma = N * params.sigma         # full-problem l2 weight = 1 / gamma
        BIG = 1e8
        pen = jnp.where(support, 0.0, BIG)

        A_all = As.reshape(N * m, n)
        b_all = bs.reshape(-1)
        if loss.name == "squared":
            if n <= prox.DENSE_MAX_N and cfg.x_solver in ("auto", "dense"):
                acc = cfg.precision.accum_dtype(A_all.dtype)
                G = gram_auto(A_all, out_dtype=acc)
                H = G + jnp.diag((pen + sigma).astype(acc))
                x = jnp.linalg.solve(H, rmatvec_auto(A_all, b_all,
                                                     out_dtype=acc))
                return done(x, 0)
            shift = pen + sigma
            inv = 1.0 / (prox.col_sumsq(A_all) + shift)
            x, k = prox.pcg(
                lambda p: normal_matvec_auto(A_all, p, shift),
                rmatvec_auto(A_all, b_all), z0, lambda r: inv * r,
                max(200, 2 * cfg.cg_iters), cfg.cg_tol)
            return done(x, k)

        # Newton-CG on the masked problem (penalty keeps off-support ~ 0)
        xshape = (n, K) if K > 1 else (n,)

        def obj_grad(xf):
            x = xf.reshape(xshape)
            pred = matvec_auto(A_all, x)
            g = rmatvec_auto(A_all, loss.grad(pred, b_all))
            return (g + sigma * x).reshape(-1) + pen * xf

        def hvp(xf, p):
            x = xf.reshape(xshape)
            pv = p.reshape(xshape)
            pred = matvec_auto(A_all, x)
            _, dlg = jax.jvp(lambda pr: loss.grad(pr, b_all), (pred,),
                             (matvec_auto(A_all, pv),))
            return (rmatvec_auto(A_all, dlg) + sigma * pv).reshape(-1) + pen * p

        def body(_, carry):
            xf, total = carry
            g = obj_grad(xf)
            step, k = prox._cg(lambda p: hvp(xf, p), g, 60)
            return xf - step, total + k
        xf, total = jax.lax.fori_loop(0, cfg.newton_iters, body,
                                      (z0, jnp.asarray(0)))
        return done(xf, total)


def fit_sparse_model(loss: str, As: Array, bs: Array, kappa: int,
                     n_classes: int = 1, **cfg_kw) -> FitResult:
    """Deprecated one-call API — use the :mod:`repro.api` estimators.

    Kept as a thin shim over the declarative layer: the kwargs are split
    into a :class:`repro.api.SparseProblem` and
    :class:`repro.api.SolverOptions` and solved through the same adapter
    the estimators use, so the result is bit-identical to both the old
    direct ``BiCADMM(...).fit(...)`` call and the new estimators.
    """
    import warnings

    from .. import api
    warnings.warn("fit_sparse_model is deprecated; use the repro.api "
                  "estimators (SparseLinearRegression, ...)",
                  DeprecationWarning, stacklevel=2)
    problem, options = api.split_legacy_config(
        loss, kappa=kappa, n_classes=n_classes, **cfg_kw)
    return api.solve(problem, As, bs, options=options)
