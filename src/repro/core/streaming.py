"""Streaming Bi-cADMM: minibatch fits with incrementally maintained factors.

The batch engine (:mod:`repro.core.bicadmm`) assumes the full local dataset
is resident before the sample decomposition runs; every refit pays the full
setup factorization. :class:`StreamingBiCADMM` instead absorbs data in row
chunks via :meth:`~StreamingBiCADMM.partial_fit` and keeps the (7a) x-update
*exact under growth* by maintaining the setup state incrementally:

* **dense regime** (``n <= DENSE_MAX_N``): the n x n Gram ``G = A^T A``, its
  shifted Cholesky factor ``L = chol(G + c I)``, the accumulators ``A^T b``
  and ``b^T b`` — a new chunk is a rank-k Cholesky *update*
  (:func:`repro.core.prox.chol_update`), an evicted chunk a rank-k
  *downdate*. No chunk is ever revisited; with ``window=0`` the engine
  holds no rows at all.
* **woodbury regime** (``m <= WOODBURY_MAX_M``, ``m < n``): the raw m x m
  dual Gram ``W = A A^T`` and its shifted factor grow by a *bordered*
  Cholesky append (:func:`repro.core.prox.chol_append`); evicting the
  oldest rows drops the leading block and repairs the trailing factor with
  one rank-p update (``M22 = L21 L21^T + L22 L22^T``).
* **pcg regime** (large m and n): the Jacobi preconditioner
  ``diag(A^T A)`` and ``A^T b`` accumulate per chunk; the matrix-free solve
  streams over the replay window.
* **direct regime** (non-squared losses): the Newton-CG x-update needs the
  data itself, so refits warm-start :meth:`BiCADMM.run_from` on the replay
  window (the window is the only state).

All accumulators live in the precision policy's accumulation dtype (f32
under bf16/fp16 data), the solver state stays pinned to the policy state
dtype, and dynamic per-refit penalties (``gamma`` / ``rho_c`` overrides)
fall back to an eigendecomposition of the *maintained* Gram — never a
recompute from data.

Every refit warm-starts from the previous :class:`BiCADMMState`; a drift
probe (one cached-factor x-solve) detects when a new chunk shifts the
S^kappa ladder and re-projects the consensus block before iterating.

Failure routing: a failed downdate or a non-finite accumulator triggers the
**full-refactorization recovery rung** — the accumulators are rebuilt from
the replay window and the event is logged as a
:class:`~repro.core.recovery.RecoveryAttempt` with ``stage="refactorize"``
on the result. A refit that still ends ``DIVERGED`` after refactorization
is surfaced to the API layer, which escalates through the standard
recovery ladder on the window data.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from . import bilinear, prox
from .bicadmm import BiCADMM, BiCADMMState, SolveParams, reset_for_resume
from .recovery import RecoveryAttempt, SolveDiverged, sanitize_state
from .results import FitResult, SolveStatus, classify_status

Array = jax.Array

_static = dict(metadata=dict(static=True))

__all__ = [
    "CGStreamAccum",
    "DenseStreamAccum",
    "StreamingBiCADMM",
    "WoodburyStreamAccum",
]


# ------------------------------------------------------ accumulators ----
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DenseStreamAccum:
    """Dense-regime sufficient statistics: everything a refit (and its
    KKT polish) needs, with no raw rows required."""

    G: Array      # (n, n) Gram A^T A over the window, accumulation dtype
    L: Array      # (n, n) lower chol(G + c I), maintained by up/downdates
    Atb: Array    # (n,)
    yty: Array    # () b^T b — closed-form train loss without data


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class WoodburyStreamAccum:
    """Woodbury-regime statistics: the raw dual Gram (for the traced-penalty
    eigh fallback) plus its shifted factor, grown/shrunk incrementally."""

    W: Array      # (m, m) raw A A^T over the window
    L: Array      # (m, m) lower chol(W + c I)
    Atb: Array    # (n,)
    yty: Array    # ()


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CGStreamAccum:
    """Matrix-free-regime statistics: the Jacobi preconditioner diagonal
    and the right-hand-side accumulator."""

    colsq: Array  # (n,) diag(A^T A) over the window
    Atb: Array    # (n,)
    yty: Array    # ()


@jax.jit
def _dense_absorb(acc: DenseStreamAccum, X: Array, y: Array
                  ) -> DenseStreamAccum:
    Xa = X.astype(acc.G.dtype)
    ya = y.astype(acc.G.dtype)
    return DenseStreamAccum(
        G=acc.G + Xa.T @ Xa,
        L=prox.chol_update(acc.L, Xa.T),
        Atb=acc.Atb + Xa.T @ ya,
        yty=acc.yty + ya @ ya)


@jax.jit
def _dense_evict(acc: DenseStreamAccum, X: Array, y: Array):
    Xa = X.astype(acc.G.dtype)
    ya = y.astype(acc.G.dtype)
    L, ok = prox.chol_downdate(acc.L, Xa.T)
    return DenseStreamAccum(
        G=acc.G - Xa.T @ Xa, L=L,
        Atb=acc.Atb - Xa.T @ ya,
        yty=acc.yty - ya @ ya), ok


@jax.jit
def _wood_absorb(acc: WoodburyStreamAccum, A_win: Array, X: Array,
                 y: Array, c: Array) -> WoodburyStreamAccum:
    dt = acc.W.dtype
    Xa = X.astype(dt)
    ya = y.astype(dt)
    C = A_win.astype(dt) @ Xa.T                    # (m_old, k) cross block
    D = Xa @ Xa.T                                  # (k, k)
    k = X.shape[0]
    W = jnp.concatenate([
        jnp.concatenate([acc.W, C], axis=1),
        jnp.concatenate([C.T, D], axis=1)], axis=0)
    L = prox.chol_append(acc.L, C, D + c * jnp.eye(k, dtype=dt))
    return WoodburyStreamAccum(W=W, L=L, Atb=acc.Atb + Xa.T @ ya,
                               yty=acc.yty + ya @ ya)


@jax.jit
def _wood_evict(acc: WoodburyStreamAccum, X: Array, y: Array
                ) -> WoodburyStreamAccum:
    dt = acc.W.dtype
    Xa = X.astype(dt)
    ya = y.astype(dt)
    p = X.shape[0]
    # Dropping the leading p rows of the bordered factor [[L11,0],[L21,L22]]
    # leaves L22 with M22 - L21 L21^T; one rank-p *update* with the cross
    # block restores chol(M22) exactly — no downdate, cannot fail.
    L = prox.chol_update(acc.L[p:, p:], acc.L[p:, :p])
    return WoodburyStreamAccum(W=acc.W[p:, p:], L=L,
                               Atb=acc.Atb - Xa.T @ ya,
                               yty=acc.yty - ya @ ya)


@jax.jit
def _cg_absorb(acc: CGStreamAccum, X: Array, y: Array) -> CGStreamAccum:
    dt = acc.Atb.dtype
    Xa = X.astype(dt)
    ya = y.astype(dt)
    return CGStreamAccum(colsq=acc.colsq + jnp.einsum("mn,mn->n", Xa, Xa),
                         Atb=acc.Atb + Xa.T @ ya,
                         yty=acc.yty + ya @ ya)


@jax.jit
def _cg_evict(acc: CGStreamAccum, X: Array, y: Array) -> CGStreamAccum:
    dt = acc.Atb.dtype
    Xa = X.astype(dt)
    ya = y.astype(dt)
    return CGStreamAccum(colsq=acc.colsq - jnp.einsum("mn,mn->n", Xa, Xa),
                         Atb=acc.Atb - Xa.T @ ya,
                         yty=acc.yty - ya @ ya)


# ---------------------------------------------------------- the engine ----
class StreamingBiCADMM:
    """Minibatch Bi-cADMM over an incrementally maintained setup state.

    Feed row chunks through :meth:`partial_fit`; each call absorbs the
    chunk into the regime's accumulators, evicts chunks that fall out of
    the bounded replay ``window``, and refits warm-started from the
    previous state. See the module docstring for the per-regime update
    algebra.

    ``window`` bounds the replay window in *chunks*: ``None`` keeps
    everything (pure growth), an int ``w >= 1`` keeps the last ``w``
    chunks (sliding-window fits via downdates), and ``0`` keeps no rows
    at all — legal only in the dense regime, whose refits and polish run
    entirely from ``G`` / ``A^T b``.

    ``solver`` shares an existing :class:`BiCADMM` instance (and with it
    the compiled while-loop drivers and jit caches) across many streams —
    the serving plane passes its cached per-signature solver so thousands
    of client streams compile once.

    Like :meth:`BiCADMM.run_from`, each refit *consumes* the previous
    state's buffers (donated to the compiled loop); keep using the
    returned ``result.state``, never a stale reference.
    """

    def __init__(self, loss, cfg, *, n_classes: int = 1,
                 window: int | None = None, drift_tol: float = 0.5,
                 solver: BiCADMM | None = None):
        if solver is None:
            solver = BiCADMM(loss, cfg, n_classes=n_classes)
        self.solver = solver
        self.cfg = solver.cfg
        self.loss = solver.loss
        if self.cfg.use_feature_split:
            raise ValueError(
                "streaming requires n_feature_blocks=1: the feature-split "
                "sub-solver bakes penalties into per-block factors that "
                "cannot be incrementally updated")
        if window is not None and window < 0:
            raise ValueError("window must be None (unbounded) or >= 0")
        self.window = window
        self.drift_tol = float(drift_tol)
        if not 0.0 <= self.drift_tol <= 1.0:
            raise ValueError("drift_tol must be in [0, 1]")
        self._chunks: list[tuple[Array, Array]] = []
        self._win_cache: tuple[Array, Array] | None = None
        self._fcache: tuple | None = None
        self._acc = None
        self._mode: str | None = None
        self._m = 0                    # rows currently inside the window
        self.m_seen = 0                # rows absorbed over the stream's life
        self.n_features: int | None = None
        self._data_dtype = None
        self._state: BiCADMMState | None = None
        self._result: FitResult | None = None
        self.refactorizations = 0
        self.drift_reprojections = 0

    # -- bookkeeping -------------------------------------------------------
    @property
    def _c(self) -> float:
        """Factor shift sigma + rho_c baked into L (N = 1 per stream)."""
        return 1.0 / self.cfg.gamma + self.cfg.rho_c

    @property
    def mode(self) -> str | None:
        """Resolved regime: dense | woodbury | pcg | direct (None = no data)."""
        return self._mode

    @property
    def m_window(self) -> int:
        """Rows currently inside the replay window / accumulators."""
        return self._m

    @property
    def result(self) -> FitResult | None:
        """The most recent refit's result (None before the first chunk)."""
        return self._result

    @property
    def nbytes(self) -> int:
        """Device bytes held by the stream's mutable setup state: the
        accumulators plus the replay window (the solver state is accounted
        separately by whoever stores it — e.g. the serve warm pool)."""
        leaves = jax.tree.leaves((self._acc, self._chunks))
        return int(sum(getattr(l, "nbytes", 0) for l in leaves))

    def _admit(self, X, y) -> tuple[Array, Array]:
        X = jnp.asarray(X)
        y = jnp.asarray(y)
        if X.ndim != 2:
            raise ValueError(f"X chunk must be 2-D (rows, features), "
                             f"got shape {X.shape}")
        if y.shape != (X.shape[0],):
            raise ValueError(f"y chunk must be ({X.shape[0]},), "
                             f"got {y.shape}")
        if X.shape[0] == 0:
            raise ValueError("empty chunk: X has no rows")
        pol = self.cfg.precision
        X = pol.cast_data(X)
        if jnp.issubdtype(y.dtype, jnp.floating):
            y = pol.cast_data(y)
        if self.n_features is None:
            self.n_features = int(X.shape[1])
            self._data_dtype = X.dtype
            n = self.n_features
            self._empty_As = jnp.zeros((1, 0, n), X.dtype)
            self._empty_bs = jnp.zeros((1, 0), y.dtype)
        elif X.shape[1] != self.n_features:
            raise ValueError(f"chunk has {X.shape[1]} features; this stream "
                             f"is fitted on {self.n_features}")
        return X, y

    def _resolve_mode(self, m_total: int) -> str:
        if self.loss.name != "squared":
            return "direct"
        eng = self.solver._x_engine(m_total, self.n_features, False)
        return eng.kind

    def _window_data(self) -> tuple[Array, Array]:
        if self._win_cache is None:
            if not self._chunks:
                raise RuntimeError("no rows inside the replay window")
            if len(self._chunks) == 1:
                self._win_cache = self._chunks[0]
            else:
                self._win_cache = (
                    jnp.concatenate([c[0] for c in self._chunks], axis=0),
                    jnp.concatenate([c[1] for c in self._chunks], axis=0))
        return self._win_cache

    def _fresh_accum(self, mode: str):
        n = self.n_features
        acc = self.cfg.precision.accum_dtype(self._data_dtype)
        zAtb = jnp.zeros((n,), acc)
        zero = jnp.zeros((), acc)
        if mode == "dense":
            L0 = jnp.sqrt(jnp.asarray(self._c, acc)) * jnp.eye(n, dtype=acc)
            return DenseStreamAccum(G=jnp.zeros((n, n), acc), L=L0,
                                    Atb=zAtb, yty=zero)
        if mode == "pcg":
            return CGStreamAccum(colsq=jnp.zeros((n,), acc), Atb=zAtb,
                                 yty=zero)
        if mode == "woodbury":
            return WoodburyStreamAccum(W=jnp.zeros((0, 0), acc),
                                       L=jnp.zeros((0, 0), acc),
                                       Atb=zAtb, yty=zero)
        return None

    # -- incremental updates ----------------------------------------------
    def _absorb_one(self, X: Array, y: Array) -> None:
        """Fold one chunk into the accumulators (window NOT yet appended —
        the woodbury cross block needs the pre-chunk window)."""
        mode = self._mode
        self._fcache = None
        if mode in (None, "direct"):
            return
        if mode == "dense":
            self._acc = _dense_absorb(self._acc, X, y)
        elif mode == "pcg":
            self._acc = _cg_absorb(self._acc, X, y)
        else:  # woodbury
            dt = self._acc.Atb.dtype
            if self._acc.W.shape[0] == 0:
                Xa = X.astype(dt)
                ya = y.astype(dt)
                W = Xa @ Xa.T
                L = jnp.linalg.cholesky(
                    W + self._c * jnp.eye(W.shape[0], dtype=dt))
                self._acc = WoodburyStreamAccum(
                    W=W, L=L, Atb=self._acc.Atb + Xa.T @ ya,
                    yty=self._acc.yty + ya @ ya)
            else:
                A_win, _ = self._window_data()
                self._acc = _wood_absorb(self._acc, A_win, X, y,
                                         jnp.asarray(self._c, dt))

    def _evict_oldest(self) -> list[str]:
        """Downdate the oldest chunk out of the window; a downdate that
        loses positive-definiteness routes to the refactorize rung."""
        Xe, ye = self._chunks.pop(0)
        self._win_cache = None
        self._fcache = None
        self._m -= Xe.shape[0]
        mode = self._mode
        if mode == "dense":
            new, ok = _dense_evict(self._acc, Xe, ye)
            if bool(ok):
                self._acc = new
                return []
            self.refactorizations += 1
            self._rebuild()
            return ["cholesky downdate lost positive-definiteness"]
        if mode == "pcg":
            self._acc = _cg_evict(self._acc, Xe, ye)
        elif mode == "woodbury":
            self._acc = _wood_evict(self._acc, Xe, ye)
        return []

    def _rebuild(self) -> None:
        """Full refactorization: rebuild every accumulator from the replay
        window (the recovery rung, also used on regime transitions)."""
        mode = self._mode
        self._fcache = None
        if mode in (None, "direct"):
            return
        self._acc = self._fresh_accum(mode)
        if not self._chunks:
            return
        dt = self.cfg.precision.accum_dtype(self._data_dtype)
        A_win, y_win = self._window_data()
        Aa = A_win.astype(dt)
        ya = y_win.astype(dt)
        if mode == "dense":
            G = Aa.T @ Aa
            L = jnp.linalg.cholesky(
                G + self._c * jnp.eye(G.shape[0], dtype=dt))
            self._acc = DenseStreamAccum(G=G, L=L, Atb=Aa.T @ ya,
                                         yty=ya @ ya)
        elif mode == "woodbury":
            W = Aa @ Aa.T
            L = jnp.linalg.cholesky(
                W + self._c * jnp.eye(W.shape[0], dtype=dt))
            self._acc = WoodburyStreamAccum(W=W, L=L, Atb=Aa.T @ ya,
                                            yty=ya @ ya)
        else:
            self._acc = CGStreamAccum(colsq=jnp.einsum("mn,mn->n", Aa, Aa),
                                      Atb=Aa.T @ ya, yty=ya @ ya)

    def _accum_finite(self) -> bool:
        if self._acc is None:
            return True
        return all(bool(jnp.all(jnp.isfinite(l)))
                   for l in jax.tree.leaves(self._acc))

    # -- absorb (steps shared with the serve update path) -------------------
    def absorb(self, X, y) -> list[str]:
        """Absorb one chunk *without* refitting: validate, fold into the
        accumulators, evict past the window bound, and route accumulator
        corruption through the refactorize rung. Returns the rung reasons
        to attach to the next refit's recovery log (usually empty).

        The serving plane calls this per lane, then batch-solves many
        streams in one fleet dispatch; :meth:`partial_fit` is
        ``absorb`` + warm refit in one call.
        """
        X, y = self._admit(X, y)
        k = int(X.shape[0])
        rungs: list[str] = []
        new_mode = self._resolve_mode(self._m + k)
        if self.window == 0 and new_mode != "dense":
            raise ValueError(
                f"window=0 (no replay rows) is only valid in the dense "
                f"regime; this stream resolves to {new_mode!r}")
        self.m_seen += k
        if new_mode != self._mode:
            # regime transition (e.g. woodbury -> pcg as m outgrows the
            # dual factor): rebuild the new regime's accumulators from the
            # window, new chunk included. With window=0 there is nothing
            # to replay (dense only, first chunk): absorb incrementally
            # into fresh accumulators instead.
            self._mode = new_mode
            if self.window == 0:
                if self._acc is None:
                    self._acc = self._fresh_accum(new_mode)
                self._absorb_one(X, y)
                self._m += k
            else:
                self._chunks.append((X, y))
                self._win_cache = None
                self._m += k
                self._rebuild()
        else:
            self._absorb_one(X, y)
            if self.window != 0:
                self._chunks.append((X, y))
                self._win_cache = None
            self._m += k
        while self.window not in (None, 0) and len(self._chunks) > self.window:
            rungs += self._evict_oldest()
        if not self._accum_finite():
            rungs.append("non-finite streaming accumulator")
            self.refactorizations += 1
            self._rebuild()
            if not self._accum_finite():
                raise SolveDiverged(
                    "streaming accumulators are non-finite even after full "
                    "refactorization: the replay window itself is poisoned",
                    result=self._result)
        return rungs

    # -- factors -----------------------------------------------------------
    def solo_factors(self, dyn: bool = False):
        """Unbatched x-update factors over the current accumulators.

        ``dyn=True`` is the traced-penalty fallback: spectral factors from
        an eigendecomposition of the *maintained* Gram (G or W), so
        per-refit ``gamma``/``rho_c`` overrides never trigger a recompute
        from data. Memoized until the next absorb/evict.
        """
        key = (id(self._acc), id(self._win_cache), bool(dyn))
        if self._fcache is not None and self._fcache[0] == key:
            return self._fcache[1]
        acc = self._acc
        mode = self._mode
        cfg = self.cfg
        if mode == "dense":
            if dyn:
                evals, V = jnp.linalg.eigh(acc.G)
                f = prox.EighRidgeFactors(V, evals, acc.Atb)
            else:
                f = prox.RidgeFactors(acc.L, acc.Atb, self._c)
        elif mode == "woodbury":
            A_win, _ = self._window_data()
            if dyn:
                evals, U = jnp.linalg.eigh(acc.W)
                f = prox.WoodburyEighFactors(A_win, U, evals, acc.Atb)
            else:
                f = prox.WoodburyFactors(A_win, acc.L, acc.Atb, self._c)
        elif mode == "pcg":
            A_win, _ = self._window_data()
            f = prox.CGFactors(A_win, acc.Atb, acc.colsq, cfg.cg_iters,
                               cfg.cg_tol)
        else:
            f = None
        self._fcache = (key, f)
        return f

    def _seed_setup(self, As: Array, bs: Array, dyn: bool, f_solo) -> None:
        """Pre-fill the solver's data-keyed setup cache with the maintained
        factors so ``run_from`` on the window data skips its own
        factorization — the whole point of the incremental updates."""
        solver = self.solver
        key = (id(As), id(bs), As.shape, bs.shape, str(As.dtype), bool(dyn))
        if key in solver._setup_cache:
            return
        factors = jax.tree.map(lambda a: a[None], f_solo)
        out = (factors, 1, self.n_features, self.loss.n_classes)
        if len(solver._setup_cache) >= solver._SETUP_CACHE_MAX:
            solver._setup_cache.pop(next(iter(solver._setup_cache)))
        solver._setup_cache[key] = (As, bs, out)

    # -- warm start + drift probe -----------------------------------------
    def warm_state(self) -> BiCADMMState:
        """The refit's starting state: the previous result's state, or a
        fresh zero state for a new stream."""
        if self._state is not None:
            return self._state
        return self.solver._init_state(self._empty_As, self._empty_bs,
                                       self.n_features, self.loss.n_classes)

    def _drift_guard(self, state: BiCADMMState, params: SolveParams,
                     dyn: bool) -> BiCADMMState:
        """One cached-factor x-solve probes whether the fresh chunk moved
        the S^kappa ladder out from under the warm iterate; on a support
        shift past ``drift_tol`` the consensus block is re-projected onto
        the new top-kappa set before the refit iterates."""
        kap = params.kappa
        if isinstance(kap, jax.core.Tracer):
            return state
        f_solo = self.solo_factors(dyn)
        if f_solo is None or self._result is None:
            return state
        kap = int(kap)
        q = state.z - state.u[0]
        x_p, _ = prox.x_solve(f_solo, q, params.rho_c, params.sigma,
                              x0=state.x[0])
        dt = state.z.dtype
        w = (x_p + state.u[0]).astype(dt)
        new_supp = jnp.abs(bilinear.hard_threshold(w, kap)) > 0
        old_supp = jnp.abs(bilinear.hard_threshold(state.z, kap)) > 0
        overlap = int(jnp.sum(new_supp & old_supp))
        if overlap >= kap * (1.0 - self.drift_tol):
            return state
        self.drift_reprojections += 1
        t = jnp.sum(jnp.abs(w)).astype(dt)
        s = bilinear.s_update(w, t, jnp.asarray(0.0, dt), kap)
        return state._replace(x=x_p[None].astype(dt), z=w, t=t, s=s,
                              v=jnp.asarray(0.0, dt))

    # -- refit -------------------------------------------------------------
    def _refit(self, state: BiCADMMState, *, kappa, gamma, rho_c,
               dyn: bool) -> FitResult:
        if self._mode == "dense":
            params = self.solver._make_params(1, kappa=kappa, gamma=gamma,
                                              rho_c=rho_c)
            st0 = reset_for_resume(state)
            factors = jax.tree.map(lambda a: a[None], self.solo_factors(dyn))
            st = self.solver._run_while_donated(
                factors, self._empty_As, self._empty_bs, params, st0)
            return self.finalize_dense(st, params)
        A_win, y_win = self._window_data()
        As, bs = A_win[None], y_win[None]
        solver = self.solver
        As, bs = solver._cast(As, bs)
        f_solo = self.solo_factors(dyn)
        if f_solo is not None:
            self._seed_setup(As, bs, dyn, f_solo)
        return solver.run_from(As, bs, state, kappa=kappa, gamma=gamma,
                               rho_c=rho_c)

    def finalize_dense(self, st: BiCADMMState, params: SolveParams
                       ) -> FitResult:
        """Data-free finalize for the dense regime: hard-threshold, then
        the masked-ridge KKT polish straight from the maintained Gram —
        the same expression as the batch engine's dense polish branch,
        with ``G`` accumulated instead of recomputed."""
        cfg = self.cfg
        acc = self._acc
        z_sparse = bilinear.hard_threshold(st.z, params.kappa)
        support = jnp.abs(z_sparse) > 0
        if cfg.polish:
            G = acc.G
            pen = jnp.where(support, 0.0, 1e8)
            H = G + jnp.diag((pen + params.sigma).astype(G.dtype))
            x = jnp.linalg.solve(H, acc.Atb)
            x_final = jnp.where(support, x, 0.0)
        else:
            x_final = z_sparse
        coef = x_final.reshape(self.n_features, self.loss.n_classes)
        status = classify_status(st.k, st.p_r, st.d_r, st.b_r,
                                 tol=cfg.tol,
                                 divergence_tol=cfg.divergence_tol)
        return FitResult(coef, st.z, support, st.k, st.p_r, st.d_r, st.b_r,
                         None, st, status=status)

    def adopt(self, res: FitResult) -> None:
        """Install a refit result as the stream's warm state (the serve
        update path finalizes lanes itself, then adopts)."""
        self._state = res.state
        self._result = res

    def seed_state(self, state: BiCADMMState) -> None:
        """Warm-start the next refit from an externally stored solver
        state — e.g. a serve warm-pool entry for a client whose previous
        fits were plain batch fits (the stream itself starts empty)."""
        self._state = state

    def train_loss(self, coef) -> float | None:
        """Squared-loss training objective over the window from the
        accumulators alone: ``0.5 (x^T G x - 2 x^T A^T b + b^T b)``.
        None outside the dense regime (no maintained Gram)."""
        if self._mode != "dense":
            return None
        acc = self._acc
        x = jnp.asarray(coef).reshape(-1).astype(acc.Atb.dtype)
        return float(0.5 * (x @ (acc.G @ x) - 2.0 * x @ acc.Atb + acc.yty))

    def partial_fit(self, X, y, *, kappa=None, gamma=None,
                    rho_c=None) -> FitResult:
        """Absorb one row chunk and refit, warm-started from the previous
        state. ``kappa`` / ``gamma`` / ``rho_c`` override the config for
        this refit (penalty overrides run the eigh fallback).

        A refit that ends ``DIVERGED`` is retried once through the
        full-refactorization rung (accumulators rebuilt from the replay
        window, state sanitized); every rung taken is logged in
        ``result.recovery``. A still-diverged result is returned as-is —
        the API layer escalates through the standard recovery ladder.
        """
        rungs = self.absorb(X, y)
        dyn = gamma is not None or rho_c is not None
        params = self.solver._make_params(1, kappa=kappa, gamma=gamma,
                                          rho_c=rho_c)
        state = self._drift_guard(self.warm_state(), params, dyn)
        res = self._refit(state, kappa=kappa, gamma=gamma, rho_c=rho_c,
                          dyn=dyn)
        if (int(res.status) == int(SolveStatus.DIVERGED)
                and (self.window != 0 and self._chunks or self._mode == "dense")):
            rungs.append("post-divergence rebuild")
            self.refactorizations += 1
            self._rebuild()
            res = self._refit(sanitize_state(reset_for_resume(res.state)),
                              kappa=kappa, gamma=gamma, rho_c=rho_c, dyn=dyn)
        if rungs:
            att = tuple(RecoveryAttempt("refactorize", r, int(res.status),
                                        int(res.iters)) for r in rungs)
            res = res._replace(recovery=(res.recovery or ()) + att)
        self.adopt(res)
        return res
