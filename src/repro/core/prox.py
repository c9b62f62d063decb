"""Prox engines for the Bi-cADMM x-update (eq 10).

x_i^{k+1} = argmin_x  l(A x, b) + sigma/2 ||x||^2 + rho_c/2 ||x - q||^2
with q = z^k - u_i^k, sigma = 1/(N gamma).

For the squared loss the update is the linear solve
``(A^T A + c I) x = A^T b + rho_c q`` with c = sigma + rho_c constant
across all ADMM iterations. :class:`NodeProxEngine` unifies three *exact*
backends behind an ``x_solver="auto"`` policy, chosen per (m, n,
dynamic-penalty) regime:

================  =============  ==========  ===========  =================
backend           setup          per-solve   memory       regime
================  =============  ==========  ===========  =================
``dense``         O(m n^2+n^3)   O(n^2)      O(n^2)       n <= DENSE_MAX_N
``woodbury``      O(m^2 n+m^3)   O(m n)      O(m n+m^2)   m << n
``pcg``           O(m n)         O(k m n)    O(m n)       both large
================  =============  ==========  ===========  =================

* ``dense``    — cached Cholesky of (A^T A + c I) (``ridge_setup``), or the
  spectral eigh factorization when sigma/rho_c are traced scalars on a
  hyperparameter path (``ridge_setup_eigh``).
* ``woodbury`` — the dual/Woodbury identity
  ``(A^T A + c I)^{-1} = (I - A^T (A A^T + c I)^{-1} A) / c``: factor the
  m x m matrix once, every solve is two matvecs on A plus an m x m
  triangular (or spectral, for traced c) solve. The n x n Gram never
  exists — this is the regime the paper's large-d experiments live in.
* ``pcg``      — matrix-free Jacobi-preconditioned conjugate gradients,
  warm-started from the previous outer iterate carried in
  ``BiCADMMState.x``; the Hessian-vector product A^T (A p) + c p runs
  through the tiled Pallas normal-equation matvec kernel
  (``repro.kernels.matvec``) on TPU and plain jnp elsewhere. Exact in the
  sense that the tolerance is driven to the f32 floor; iteration counts of
  the outer ADMM loop match the dense oracle (tests/test_xsolver.py).

All backend solves dispatch through :func:`x_solve` on the factor pytree
type, so the solver loops stay backend-agnostic. The non-squared losses use
``newton_cg_prox`` — matrix-free guarded Newton-CG whose matvecs route
through the same kernel layer.

Conventions: A is (m, n); for multiclass, x is (n, C) and prox operates on
the flattened vector.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from .losses import Loss
from ..runtime import F32_DOT
from ..kernels.ops import (gram_auto, matvec_auto, normal_matvec_auto,
                           rmatvec_auto)

Array = jax.Array

# x_solver="auto" policy thresholds: largest n for the O(n^2)-memory dense
# factorization, largest m for the O(m^2)-memory Woodbury dual factor.
DENSE_MAX_N = 2048
WOODBURY_MAX_M = 8192

_static = dict(metadata=dict(static=True))

_REDUCED = (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16))


def _accum(dtype) -> jnp.dtype:
    """Factor/accumulation dtype for ``dtype`` data: f32 for reduced
    precision (bf16/fp16), unchanged otherwise — the f32 path is
    bit-identical to the historical setup expressions."""
    d = jnp.dtype(dtype)
    return jnp.dtype(jnp.float32) if d in _REDUCED else d


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RidgeFactors:
    """Cached Cholesky factors for the squared-loss prox."""
    chol: Array        # (n, n) lower factor of A^T A + c I
    Atb: Array         # (n,) A^T b
    c: float = dataclasses.field(**_static)  # sigma + rho_c


def ridge_setup(A: Array, b: Array, sigma: float, rho_c: float) -> RidgeFactors:
    """Factor once per dataset; the Gram matrix — the dominant setup cost —
    runs through the MXU-tiled Pallas kernel on TPU (gram_auto)."""
    n = A.shape[1]
    c = sigma + rho_c
    acc = _accum(A.dtype)
    G = gram_auto(A, out_dtype=acc) + c * jnp.eye(n, dtype=acc)
    return RidgeFactors(jnp.linalg.cholesky(G),
                        rmatvec_auto(A, b, out_dtype=acc), c)


def ridge_prox_factorized(f: RidgeFactors, q: Array, rho_c: float) -> Array:
    """argmin_x 1/2||Ax-b||^2 + sigma/2||x||^2 + rho_c/2||x-q||^2
    = (A^T A + (sigma+rho_c) I)^{-1} (A^T b + rho_c q)."""
    rhs = f.Atb + rho_c * q
    y = jax.scipy.linalg.solve_triangular(f.chol, rhs, lower=True)
    return jax.scipy.linalg.solve_triangular(f.chol.T, y, lower=False)


class EighRidgeFactors(NamedTuple):
    """Spectral factors of A^T A: solve (A^T A + c I)^{-1} rhs for *any*
    (traced) shift c. This is what lets the path engine sweep gamma / rho_c
    grids without refactorizing — the Cholesky in :class:`RidgeFactors` bakes
    the shift in, the eigendecomposition does not."""
    V: Array       # (n, n) orthonormal eigenvectors of A^T A
    evals: Array   # (n,) eigenvalues (>= 0)
    Atb: Array     # (n,)


def ridge_setup_eigh(A: Array, b: Array) -> EighRidgeFactors:
    acc = _accum(A.dtype)
    evals, V = jnp.linalg.eigh(gram_auto(A, out_dtype=acc))
    return EighRidgeFactors(V, evals, rmatvec_auto(A, b, out_dtype=acc))


def ridge_prox_eigh(f: EighRidgeFactors, q: Array, rho_c: Array | float,
                    sigma: Array | float) -> Array:
    """Same prox as :func:`ridge_prox_factorized` but with a dynamic shift
    c = sigma + rho_c: x = V diag(1/(evals + c)) V^T (A^T b + rho_c q)."""
    rhs = f.Atb + rho_c * q
    y = jnp.dot(f.V.T, rhs, precision=F32_DOT) / (f.evals + sigma + rho_c)
    return jnp.dot(f.V, y, precision=F32_DOT)


# ------------------------------------------------------------ woodbury ----
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class WoodburyFactors:
    """Dual (m x m) factors: exact squared-loss prox in O(m n) per solve
    without ever forming the n x n Gram."""
    A: Array           # (m, n) data, by reference
    chol: Array        # (m, m) lower factor of A A^T + c I
    Atb: Array         # (n,)
    c: float = dataclasses.field(**_static)  # sigma + rho_c


class WoodburyEighFactors(NamedTuple):
    """Spectral dual factors of A A^T: any (traced) shift c at solve time —
    the Woodbury counterpart of :class:`EighRidgeFactors` for penalty
    sweeps on the path engine."""
    A: Array
    U: Array           # (m, m) orthonormal eigenvectors of A A^T
    evals: Array       # (m,) eigenvalues (>= 0)
    Atb: Array         # (n,)


def woodbury_setup(A: Array, b: Array, sigma: float,
                   rho_c: float) -> WoodburyFactors:
    """Factor (A A^T + c I) once; the m x m outer Gram runs through the
    tiled Pallas kernel on TPU (gram_auto on A^T)."""
    m = A.shape[0]
    c = sigma + rho_c
    acc = _accum(A.dtype)
    G = gram_auto(A.T, out_dtype=acc) + c * jnp.eye(m, dtype=acc)
    return WoodburyFactors(A, jnp.linalg.cholesky(G),
                           rmatvec_auto(A, b, out_dtype=acc), c)


def woodbury_setup_eigh(A: Array, b: Array) -> WoodburyEighFactors:
    acc = _accum(A.dtype)
    evals, U = jnp.linalg.eigh(gram_auto(A.T, out_dtype=acc))
    return WoodburyEighFactors(A, U, evals,
                               rmatvec_auto(A, b, out_dtype=acc))


def woodbury_prox(f: WoodburyFactors, q: Array, rho_c: Array | float) -> Array:
    """x = (rhs - A^T (A A^T + c I)^{-1} A rhs) / c with rhs = A^T b + rho_c q
    — algebraically identical to the primal Cholesky solve."""
    rhs = f.Atb + rho_c * q
    t = matvec_auto(f.A, rhs)
    y = jax.scipy.linalg.solve_triangular(f.chol, t, lower=True)
    y = jax.scipy.linalg.solve_triangular(f.chol.T, y, lower=False)
    return (rhs - rmatvec_auto(f.A, y)) / f.c


def _woodbury_eigh_solve(f: WoodburyEighFactors, rhs: Array,
                         c: Array | float) -> Array:
    t = matvec_auto(f.A, rhs)
    y = jnp.dot(f.U.T, t, precision=F32_DOT) / (f.evals + c)
    y = jnp.dot(f.U, y, precision=F32_DOT)
    return (rhs - rmatvec_auto(f.A, y)) / c


def woodbury_prox_eigh(f: WoodburyEighFactors, q: Array,
                       rho_c: Array | float, sigma: Array | float) -> Array:
    """Spectral dual solve with one iterative-refinement pass.

    When m >= rank(A) the dual Gram A A^T is singular: its near-zero
    eigenvalues carry O(eps * lambda_max) rounding noise, and the raw
    reconstruction ``(rhs - A^T y) / c`` loses a cond-factor of forward
    accuracy relative to the primal (dense eigh) solve. Warm-started path
    sweeps compound that loss into iteration-count drift vs the dense
    oracle. One residual-correction pass — solve, form the true residual
    of (A^T A + c I) x = rhs, solve for the correction — restores
    dense-level accuracy at the cost of a second O(m n) solve, keeping
    traced-penalty trajectories inside the documented +-2 iteration band
    (tests/test_xsolver.py::test_path_traced_penalties_all_backends).
    """
    c = sigma + rho_c
    rhs = f.Atb + rho_c * q
    x0 = _woodbury_eigh_solve(f, rhs, c)
    r = rhs - (rmatvec_auto(f.A, matvec_auto(f.A, x0)) + c * x0)
    return x0 + _woodbury_eigh_solve(f, r, c)


# ----------------------------------------------------------------- pcg ----
def col_sumsq(A: Array) -> Array:
    """Per-column sum of squares — diag(A^T A), the Jacobi preconditioner.
    Shared by the reference and sharded CG engines so single-device
    trajectories stay bit-identical. Reduced-precision data accumulates
    (and emits) in f32; the f32 path is untouched."""
    acc = _accum(A.dtype)
    if acc == A.dtype:
        return jnp.einsum("mn,mn->n", A, A, precision=F32_DOT)
    return jnp.einsum("mn,mn->n", A, A, preferred_element_type=acc,
                      precision=F32_DOT)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CGFactors:
    """Matrix-free backend state: no factorization, O(m n) setup."""
    A: Array           # (m, n) data, by reference
    Atb: Array         # (n,)
    diag: Array        # (n,) diag(A^T A) — Jacobi preconditioner
    iters: int = dataclasses.field(**_static)
    tol: float = dataclasses.field(**_static)


def cg_setup(A: Array, b: Array, iters: int = 200,
             tol: float = 1e-6) -> CGFactors:
    return CGFactors(A, rmatvec_auto(A, b, out_dtype=_accum(A.dtype)),
                     col_sumsq(A), iters, tol)


def pcg(matvec: Callable[[Array], Array], rhs: Array, x0: Array,
        precond: Callable[[Array], Array], iters: int, tol: float,
        dot_fn: Callable[[Array, Array], Array] | None = None) -> Array:
    """Preconditioned conjugate gradients, warm-started at ``x0``, with a
    relative-residual stop and fixed max iterations (jit-safe while_loop).

    ``dot_fn`` makes the two reductions per iteration injectable: the
    reference engine passes the plain vdot default, ``repro.core.sharded``
    passes a feat-axis psum'd vdot — the SAME loop then runs on local
    feature shards (the matvec carries its own psum of the partial
    predictions) and on a single device the two engines are bit-identical.
    """
    dot = dot_fn if dot_fn is not None else (
        lambda u, w: jnp.vdot(u, w, precision=F32_DOT))
    r0 = rhs - matvec(x0)
    z0 = precond(r0)
    rz0 = dot(r0, z0)
    tol2 = tol * tol * jnp.maximum(dot(rhs, rhs), 1e-30)

    def body(state):
        x, r, p, rz, _, k = state
        Ap = matvec(p)
        alpha = rz / jnp.maximum(dot(p, Ap), 1e-30)
        x = x + alpha * p
        r = r - alpha * Ap
        z = precond(r)
        rz_new = dot(r, z)
        p = z + (rz_new / jnp.maximum(rz, 1e-30)) * p
        return x, r, p, rz_new, dot(r, r), k + 1

    def cond(state):
        *_, rr, k = state
        return (rr > tol2) & (k < iters)

    x, *_ = jax.lax.while_loop(cond, body,
                               (x0, r0, z0, rz0, dot(r0, r0), jnp.asarray(0)))
    return x


def pcg_prox(f: CGFactors, q: Array, rho_c: Array | float,
             sigma: Array | float, x0: Array | None = None) -> Array:
    """Matrix-free exact prox: solve (A^T A + c I) x = A^T b + rho_c q by
    Jacobi-PCG, warm-started from the previous outer iterate (``x0``) —
    after the ADMM transient the prox center moves O(step) per iteration,
    so warm CG needs a handful of matvecs where cold CG needs dozens."""
    c = sigma + rho_c
    rhs = f.Atb + rho_c * q
    inv = 1.0 / (f.diag + c)
    x0 = q if x0 is None else x0
    return pcg(lambda p: normal_matvec_auto(f.A, p, c), rhs, x0,
               lambda r: inv * r, f.iters, f.tol)


# ------------------------------------------------- the unified engine ----
XSOLVERS = ("auto", "dense", "woodbury", "pcg")


@dataclasses.dataclass(frozen=True)
class NodeProxEngine:
    """Squared-loss x-update engine: a hashable (jit-static) policy object
    that builds per-node factors once and solves every ADMM iteration.

    ``kind`` is the resolved backend; ``dynamic`` switches the
    factorization backends to their spectral variants so sigma / rho_c may
    be traced scalars (hyperparameter-path sweeps). The factor pytrees it
    returns dispatch through :func:`x_solve`, so solver loops never branch
    on the backend themselves.
    """
    kind: str                 # "dense" | "woodbury" | "pcg"
    dynamic: bool = False     # traced sigma/rho_c at solve time
    cg_iters: int = 200
    cg_tol: float = 1e-6

    @staticmethod
    def choose(m: int, n: int, *, x_solver: str = "auto",
               dynamic: bool = False, cg_iters: int = 200,
               cg_tol: float = 1e-6) -> "NodeProxEngine":
        """Resolve the ``x_solver`` policy for an (m, n) node block: dense
        factorization while the n x n Gram is cheap, the m x m Woodbury
        dual when samples are the short axis, matrix-free PCG when both
        axes are large (the only O(m n)-memory option)."""
        if x_solver not in XSOLVERS:
            raise ValueError(f"unknown x_solver {x_solver!r}; "
                             f"expected one of {XSOLVERS}")
        kind = x_solver
        if kind == "auto":
            if n <= DENSE_MAX_N:
                kind = "dense"
            elif m <= WOODBURY_MAX_M and m < n:
                kind = "woodbury"
            else:
                kind = "pcg"
        return NodeProxEngine(kind, bool(dynamic), cg_iters, cg_tol)

    def setup(self, A: Array, b: Array, sigma: float, rho_c: float):
        """Build the per-node factor pytree (vmap over stacked nodes)."""
        if self.kind == "dense":
            return (ridge_setup_eigh(A, b) if self.dynamic
                    else ridge_setup(A, b, sigma, rho_c))
        if self.kind == "woodbury":
            return (woodbury_setup_eigh(A, b) if self.dynamic
                    else woodbury_setup(A, b, sigma, rho_c))
        return cg_setup(A, b, self.cg_iters, self.cg_tol)

    def solve(self, factors, q: Array, rho_c, sigma,
              x0: Array | None = None) -> Array:
        return x_solve(factors, q, rho_c, sigma, x0)


def x_solve(factors, q: Array, rho_c: Array | float, sigma: Array | float,
            x0: Array | None = None) -> Array:
    """Backend dispatch on the factor pytree type (vmap-safe: the types
    survive batching). ``x0`` is the warm start; only PCG consumes it."""
    if isinstance(factors, RidgeFactors):
        return ridge_prox_factorized(factors, q, rho_c)
    if isinstance(factors, EighRidgeFactors):
        return ridge_prox_eigh(factors, q, rho_c, sigma)
    if isinstance(factors, WoodburyFactors):
        return woodbury_prox(factors, q, rho_c)
    if isinstance(factors, WoodburyEighFactors):
        return woodbury_prox_eigh(factors, q, rho_c, sigma)
    if isinstance(factors, CGFactors):
        return pcg_prox(factors, q, rho_c, sigma, x0)
    raise TypeError(f"unknown x-update factor pytree {type(factors)!r}")


# ------------------------------------------- incremental factor updates ----
# The streaming engine (repro.core.streaming) maintains the squared-loss
# factors under row arrival without refactorizing: appending k rows to the
# data is a rank-k UPDATE of the n x n ridge factor (chol of A^T A + c I),
# evicting rows from a sliding window is a rank-k DOWNDATE, and growing the
# m x m Woodbury dual factor (chol of A A^T + c I) is a bordered APPEND.
# All three are exact: the refreshed factor equals a from-scratch Cholesky
# of the refreshed matrix up to fp round-off (tests/test_stream.py).


def _chol_rank1(L: Array, v: Array, sign: float) -> tuple[Array, Array]:
    """One rank-1 Cholesky update (sign=+1) or downdate (sign=-1) of the
    lower factor ``L``: returns ``(L', ok)`` with L' L'^T = L L^T +- v v^T.

    The LINPACK column recurrence (Givens rotations for the update,
    hyperbolic rotations for the downdate), O(n^2) whole-column work per
    column under ``lax.fori_loop`` — no O(n^3) refactorization. A downdate
    of energy the factor does not hold drives a pivot non-positive;
    ``ok`` goes False and the caller must refactorize (the matrix is no
    longer numerically positive definite along that direction).
    """
    n = L.shape[0]
    idx = jnp.arange(n)
    tiny = jnp.asarray(jnp.finfo(L.dtype).tiny, L.dtype)

    def body(j, carry):
        L, v, ok = carry
        Ljj = L[j, j]
        vj = v[j]
        r2 = Ljj * Ljj + sign * vj * vj
        ok = ok & (r2 > 0) & (Ljj > 0)
        r = jnp.sqrt(jnp.maximum(r2, tiny))
        c = r / jnp.maximum(Ljj, tiny)
        s = vj / jnp.maximum(Ljj, tiny)
        below = idx > j
        col = jnp.where(below, (L[:, j] + sign * s * v) / c, L[:, j])
        col = col.at[j].set(r)
        v = jnp.where(below, c * v - s * col, v)
        return L.at[:, j].set(col), v, ok

    L, _, ok = jax.lax.fori_loop(0, n, body,
                                 (L, v, jnp.asarray(True)))
    return L, ok


def _as_rank_k(V: Array) -> Array:
    return V if V.ndim == 2 else V[:, None]


def chol_update(L: Array, V: Array) -> Array:
    """Rank-k update of a lower Cholesky factor: the factor of
    ``L L^T + V V^T`` for ``V`` of shape (n, k) (or (n,) for rank one).

    Appending k data rows ``X_t`` to a dataset turns the ridge factor
    ``chol(A^T A + c I)`` into ``chol_update(L, X_t.T)`` — O(k n^2)
    against the O(m n^2 + n^3) from-scratch setup. An update cannot fail
    (the matrix only gains energy), so no status is returned."""
    def one(L, v):
        L, _ = _chol_rank1(L, v, 1.0)
        return L, None
    L, _ = jax.lax.scan(one, L, _as_rank_k(V).T)
    return L


def chol_downdate(L: Array, V: Array) -> tuple[Array, Array]:
    """Rank-k downdate: ``(L', ok)`` with L' L'^T = L L^T - V V^T.

    Evicting k rows from a sliding data window downdates the ridge factor
    by ``X_evicted.T``. Unlike the update this can fail: removing energy
    the (rounded) factor does not hold drives a pivot non-positive.
    ``ok`` is a scalar bool — on False the returned factor is garbage and
    the caller must refactorize from the raw accumulators (the streaming
    engine's full-refactorization recovery rung)."""
    def one(carry, v):
        L, ok = carry
        L, ok1 = _chol_rank1(L, v, -1.0)
        return (L, ok & ok1), None
    (L, ok), _ = jax.lax.scan(one, (L, jnp.asarray(True)),
                              _as_rank_k(V).T)
    return L, ok


def chol_append(L: Array, M12: Array, M22: Array) -> Array:
    """Bordered extension: the (p+q, p+q) lower factor of
    ``[[M11, M12], [M12^T, M22]]`` given ``L = chol(M11)``.

    This is how the m x m Woodbury dual factor grows when k new rows
    arrive: M12 = A_window @ X_t^T, M22 = X_t X_t^T + c I. Cost is one
    (p, q) triangular solve plus a q x q factorization — O(p^2 q + q^3)
    instead of the O(p^3) refactorization. Evicting the window's LEADING
    p rows is the reverse move and needs no new primitive: drop the
    leading block and ``chol_update(L22, L21)`` (since
    M22 = L21 L21^T + L22 L22^T)."""
    L21 = jax.scipy.linalg.solve_triangular(L, M12, lower=True).T
    L22 = jnp.linalg.cholesky(M22 - L21 @ L21.T)
    p, q = L.shape[0], M22.shape[0]
    top = jnp.concatenate([L, jnp.zeros((p, q), L.dtype)], axis=1)
    bot = jnp.concatenate([L21, L22], axis=1)
    return jnp.concatenate([top, bot], axis=0)


# --------------------------------------------------------- newton-cg ----
def _cg(matvec: Callable[[Array], Array], rhs: Array, iters: int,
        tol: float = 1e-10) -> Array:
    """Plain conjugate gradients with fixed max iterations (jit-safe)."""
    x0 = jnp.zeros_like(rhs)

    def body(state):
        x, r, p, rs, k = state
        Ap = matvec(p)
        alpha = rs / jnp.maximum(jnp.vdot(p, Ap, precision=F32_DOT), 1e-30)
        x = x + alpha * p
        r = r - alpha * Ap
        rs_new = jnp.vdot(r, r, precision=F32_DOT)
        p = r + (rs_new / jnp.maximum(rs, 1e-30)) * p
        return x, r, p, rs_new, k + 1

    def cond(state):
        _, _, _, rs, k = state
        return (rs > tol) & (k < iters)

    rs0 = jnp.vdot(rhs, rhs, precision=F32_DOT)
    x, *_ = jax.lax.while_loop(cond, body, (x0, rhs, rhs, rs0,
                                            jnp.asarray(0)))
    return x


def newton_cg_prox(loss: Loss, A: Array, b: Array, q: Array, sigma: float,
                   rho_c: float, newton_iters: int = 15,
                   cg_iters: int = 50) -> Array:
    """Matrix-free Newton-CG for argmin_x l(Ax,b) + sigma/2|x|^2 + rho_c/2|x-q|^2.

    For multiclass losses x/q are (n, C); pred = A @ x is (m, C). Every
    A-product routes through the kernels layer (tiled Pallas matvec on TPU,
    the identical plain contraction elsewhere).
    """
    def obj_grad(x):
        pred = matvec_auto(A, x)
        lg = loss.grad(pred, b)
        return rmatvec_auto(A, lg) + sigma * x + rho_c * (x - q)

    def hvp(x, p):
        pred = matvec_auto(A, x)
        # Gauss form via jvp of the loss gradient wrt pred
        _, dlg = jax.jvp(lambda pr: loss.grad(pr, b), (pred,),
                         (matvec_auto(A, p),))
        return rmatvec_auto(A, dlg) + (sigma + rho_c) * p

    x0 = q

    def body(_, x):
        g = obj_grad(x)
        step = _cg(lambda p: hvp(x, p), g, cg_iters)
        return x - step

    return jax.lax.fori_loop(0, newton_iters, body, x0)


def direct_prox(loss: Loss, A: Array, b: Array, q: Array, sigma: float,
                rho_c: float, ridge: RidgeFactors | None = None) -> Array:
    """Dispatch: closed form for squared loss, Newton-CG otherwise."""
    if loss.name == "squared":
        assert ridge is not None, "squared loss requires ridge_setup factors"
        return ridge_prox_factorized(ridge, q, rho_c)
    return newton_cg_prox(loss, A, b, q, sigma, rho_c)
