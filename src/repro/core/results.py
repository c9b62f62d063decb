"""Engine-agnostic result types for the Bi-cADMM solver family.

Before the estimator-API redesign every engine owned its own result tuple
(``BiCADMMResult.x`` vs ``ShardedResult.x_sparse``, ``PathResult`` vs
``ShardedPathResult``) and every differential test / benchmark special-cased
the field names. Both engines now return the same two types:

* :class:`FitResult`  — one solve. ``coef`` is the final sparse solution in
  the ``(n, K)`` model layout (K = number of classes; K = 1 for the scalar
  losses), ``z`` the pre-threshold consensus iterate on the flat ``(n*K,)``
  layout the engines iterate in, ``support`` the flat boolean mask,
  ``state`` the resumable solver state for warm starts, and ``counters``
  the reference engine's inner-step counts.
* :class:`SparsePath` — a stacked hyperparameter sweep (leading axis = grid
  index). ``strategy`` records how the sweep actually executed —
  ``"warm-scan"`` (state carried point to point), ``"cold-scan"``
  (sequential cold fits, shared compile), or ``"vmap"`` (batched
  independent cold fits) — so grid callers can no longer be handed a
  sequential scan silently labelled as a batched grid.

The legacy flat accessors ``x`` / ``x_sparse`` are kept as read-only views
so pre-redesign callers (and the bit-for-bit differential tests) keep
working unchanged; new code should read ``coef``.
"""
from __future__ import annotations

import enum
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

Array = jax.Array


class SolveStatus(enum.IntEnum):
    """How a solve ended, per lane. Stored on results as an int32 device
    array (vector-valued on fleet/path results) so classification costs
    no device sync; compare with the enum members directly
    (``int(res.status) == SolveStatus.CONVERGED``)."""

    CONVERGED = 0   # all three residuals under tol, iterates finite
    MAX_ITER = 1    # iteration budget exhausted before the tolerance
    DIVERGED = 2    # non-finite iterates or residual blow-up; loop exited
    ABORTED = 3     # stopped early by an external cap (deadline iter_caps)


def divergence_probe(state, divergence_tol) -> Array:
    """Per-lane ``True`` once a solve has demonstrably gone bad: any
    residual is non-finite, or the primal/dual residuals blew past
    ``divergence_tol``. Runs inside the while-loop predicates of both
    engines — a handful of scalar ops per lane, no device sync.

    The ``k > 0`` guard matters: fresh and resumed states carry ``inf``
    residuals *by construction* (they are maxed into the first real
    residuals), so the probe only speaks after at least one step.
    """
    finite = (jnp.isfinite(state.p_r) & jnp.isfinite(state.d_r)
              & jnp.isfinite(state.b_r))
    blown = (state.p_r > divergence_tol) | (state.d_r > divergence_tol)
    return (state.k > 0) & (~finite | blown)


def classify_status(iters, p_r, d_r, b_r, *, tol,
                    divergence_tol) -> Array:
    """Elementwise :class:`SolveStatus` codes from final residuals —
    int32 device array, same shape as ``iters``, no sync. ``ABORTED``
    is applied afterwards by the callers that know about external caps
    (:func:`mark_aborted`)."""
    finite = jnp.isfinite(p_r) & jnp.isfinite(d_r) & jnp.isfinite(b_r)
    converged = finite & (p_r < tol) & (d_r < tol) & (b_r < tol)
    diverged = (iters > 0) & (~finite | (p_r > divergence_tol)
                              | (d_r > divergence_tol))
    return jnp.where(
        converged, jnp.int32(SolveStatus.CONVERGED),
        jnp.where(diverged, jnp.int32(SolveStatus.DIVERGED),
                  jnp.int32(SolveStatus.MAX_ITER)))


def mark_aborted(status, iters, iter_caps, max_iter) -> Array:
    """Reclassify ``MAX_ITER`` lanes that were actually stopped by a
    per-lane external iteration cap (deadline enforcement, inert padding
    lanes) as ``ABORTED``. Eager elementwise ops, no sync."""
    budget = jnp.minimum(jnp.asarray(iter_caps), max_iter)
    hit = ((status == jnp.int32(SolveStatus.MAX_ITER))
           & (budget < max_iter) & (iters >= budget))
    return jnp.where(hit, jnp.int32(SolveStatus.ABORTED), status)


def status_name(status) -> str:
    """Human-readable name of a scalar status code (syncs the scalar)."""
    return SolveStatus(int(status)).name


class FitResult(NamedTuple):
    """One solve, from either engine. ``coef`` is ``(n, K)``; the engines'
    flat iterates (``z``, ``support``) stay on the ``(n*K,)`` layout."""
    coef: Array       # (n, K) final sparse solution (polished where enabled)
    z: Array          # (n*K,) consensus iterate before hard-thresholding
    support: Array    # (n*K,) bool
    iters: Array      # () outer iterations spent
    p_r: Array        # primal residual (14)
    d_r: Array        # dual residual
    b_r: Array        # bi-linear constraint residual
    history: Any = None   # residual traces (fit_with_history) or None
    state: Any = None     # resumable solver state — warm-start the next solve
    status: Any = None    # () int32 SolveStatus code (None on legacy paths)
    recovery: Any = None  # tuple[RecoveryAttempt, ...] when the ladder ran
    # {"xupdate_cg_steps", "polish_cg_steps"}: () int32 device scalars, the
    # x-update's CG steps summed over nodes and outer iterations (Newton-CG:
    # over its Newton iterations too) and the polish's; on the sharded
    # engine the x-update's are the slowest node's and the polish's are its
    # Newton steps; None on engines whose state carries no counter (fleet,
    # streaming)
    counters: Any = None

    @property
    def x(self) -> Array:
        """Flat ``(n*K,)`` view of ``coef`` (legacy reference-engine name)."""
        return self.coef.reshape(-1)

    @property
    def x_sparse(self) -> Array:
        """Flat ``(n*K,)`` view of ``coef`` (legacy sharded-engine name)."""
        return self.coef.reshape(-1)

    @property
    def converged(self) -> bool:
        """Whether this solve ended :data:`SolveStatus.CONVERGED` (syncs
        the status scalar; results that carry no status fall back to a
        residual-finiteness test)."""
        if self.status is None:
            return bool(jnp.isfinite(self.p_r) & jnp.isfinite(self.d_r)
                        & jnp.isfinite(self.b_r))
        return int(self.status) == int(SolveStatus.CONVERGED)

    @property
    def status_name(self) -> str | None:
        """Name of the status code (``"CONVERGED"`` …), or ``None``."""
        return None if self.status is None else status_name(self.status)


class FleetResult(NamedTuple):
    """A batch of B *independent* problems solved in one vmapped driver
    (``repro.core.fleet`` / ``repro.api.fit_many``); leading axis =
    problem index. Unlike :class:`SparsePath` (one dataset, many
    hyperparameter points) every lane here has its own data — and its own
    ``kappa`` / ``gamma`` / ``rho_c`` and its own convergence point: the
    masked fleet driver freezes converged lanes, so per-lane ``iters`` /
    ``support`` match a solo fit exactly (iterates to fp round-off).

    Index it like a sequence: ``result[i]`` is the i-th problem's
    :class:`FitResult` (with its slice of the batched solver state, so a
    single problem can be re-fit solo from the fleet's warm state)."""
    coef: Array         # (B, n, K) sparse solutions
    z: Array            # (B, n*K) consensus iterates
    support: Array      # (B, n*K) bool
    iters: Array        # (B,) outer iterations spent per problem
    p_r: Array          # (B,)
    d_r: Array          # (B,)
    b_r: Array          # (B,)
    cardinality: Array  # (B,) ||coef_b||_0
    kappas: Array       # (B,)
    gammas: Array       # (B,)
    rho_cs: Array       # (B,)
    train_loss: Any = None  # (B,) per-problem training loss
    state: Any = None       # batched solver state — warm-start the refit
    strategy: str | None = None  # "fleet-vmap"
    status: Any = None      # (B,) int32 SolveStatus codes

    def __len__(self) -> int:
        return int(self.coef.shape[0])

    def __getitem__(self, i: int) -> FitResult:
        """The i-th problem's solo-shaped :class:`FitResult` view."""
        state = (None if self.state is None
                 else jax.tree.map(lambda a: a[i], self.state))
        status = None if self.status is None else self.status[i]
        return FitResult(self.coef[i], self.z[i], self.support[i],
                         self.iters[i], self.p_r[i], self.d_r[i],
                         self.b_r[i], history=None, state=state,
                         status=status)

    @property
    def x(self) -> Array:
        """Flat ``(B, n*K)`` view of ``coef`` (legacy name)."""
        return self.coef.reshape(self.coef.shape[0], -1)


class SparsePath(NamedTuple):
    """Stacked per-grid-point results; leading axis = grid index."""
    coef: Array         # (P, n, K) sparse solutions
    z: Array            # (P, n*K) consensus iterates
    support: Array      # (P, n*K) bool
    iters: Array        # (P,) outer iterations spent per point
    p_r: Array          # (P,)
    d_r: Array          # (P,)
    b_r: Array          # (P,)
    cardinality: Array  # (P,) ||coef_p||_0
    kappas: Array       # (P,)
    gammas: Array       # (P,)
    rho_cs: Array       # (P,)
    train_loss: Any = None  # (P,) sum-loss on the training data (reference
    #                         engine; None on the sharded engine, which does
    #                         not materialize global predictions)
    state: Any = None       # final solver state of the last point (warm scans)
    strategy: str | None = None  # "warm-scan" | "cold-scan" | "vmap"
    status: Any = None      # (P,) int32 SolveStatus codes

    @property
    def x(self) -> Array:
        """Flat ``(P, n*K)`` view of ``coef`` (legacy name)."""
        return self.coef.reshape(self.coef.shape[0], -1)

    @property
    def x_sparse(self) -> Array:
        """Flat ``(P, n*K)`` view of ``coef`` (legacy sharded name)."""
        return self.coef.reshape(self.coef.shape[0], -1)
