"""Distributed Bi-cADMM under ``shard_map`` — the production engine.

Mesh mapping (DESIGN.md §5):

* ``nodes`` axis — the paper's sample decomposition (N computational nodes;
  on the production mesh this is ("pod","data")).
* ``feat``  axis — the paper's per-node feature decomposition across M GPUs
  (the production "model" axis).

Device (i, j) holds the data block A_ij (m_i, n_j) *exactly* as in the
paper's hierarchical layout. Per outer iteration the collectives are:

  x-update, selected by ``x_update``:
    "subsolver" (Algorithm 2), x ``inner_iters``:
      reduction over `feat` of the partial predictions A_ij x_ij — a psum
      in the approximate modes; the two exact modes instead all-gather the
      (m_i, K) prediction stack (2x per inner step, O(M*m_i) bytes) and
      take the replicated mean, mirroring the oracle's reduction order
    "cg" (matrix-free PCG on the squared-loss normal equations), x n_cg
    CG steps (warm-started: a handful after the ADMM transient):
      ONE (m_i,) prediction psum (the A p reduction over `feat`) + three
      scalar psums (p.Ap / r.z / r.r dots) per CG step, plus one (m_i,)
      psum + three scalars for the warm-start residual — O(n_cg * m_i)
      bytes, NO gather in any projection mode, and exact (tolerance at
      the f32 floor), so trajectories still match the reference oracle
  consensus center:
      psum over `nodes` of (x_ij + u_ij)                      [(n_j, K)]
  (z,t) FISTA + s-update — selected by ``projection``.

Projection modes and their wire cost over the `feat` axis per OUTER
iteration (d = n*K global features, B = 128 ladder rungs, F = ``zt_iters``
FISTA steps, p = polish steps/projection — generically 2-4 after the
ladder rounds, <= 15 with rounds = 0):

  mode           exact?  (z,t,s)-block bytes/outer-iteration on `feat`
  -------------  ------  ------------------------------------------------
  ladder_exact   yes     O(F * (rounds*2B + p*2 + 3)) scalars   [DEFAULT]
  exact          yes     O(3d) all-gather (the paper's "Collect")
  batched        ~B^-3   O(F * rounds * 2B) scalars
  bisect         ~2^-60  O(F * 60) scalars

(The table covers the projection block; on top of it, BOTH exact modes pay
the inner loop's prediction-stack gathers — 2 * inner_iters * O(M * m_i)
bytes per outer iteration, see above — which the approximate modes replace
with psums. fig4_transfer.py models every term.)

* ``"ladder_exact"`` (default): the sort-free exact projection engine
  (repro.core.bilinear.ladder_refine) with every reduction psum/pmax-
  wrapped: each bracketing round is ONE (2*B,)-vector psum, each
  closed-form polish step ONE (2,)-psum — and the result is *exact*, so
  iterate trajectories (and iteration counts) still agree with the
  single-process reference oracle. The O(n) gather is gone from the
  default hot path. Honest crossover: the ladder term is d-INDEPENDENT
  (~F*(rounds*2B + p*2 + 3) scalars ~ 250 KB/outer at TPU defaults), so
  on pure wire *bytes* it beats the O(3d) gather for d >~ 2e5 — the
  regime the paper targets — while below that the gather moves fewer
  bytes but serializes a full device sort per FISTA step on every
  replica; see benchmarks/proj_bench.py + fig4_transfer.py for both
  terms.
* ``"exact"``: all-gather z/s/w over `feat` and run the identical
  full-vector projections of ``repro.core.bicadmm`` replicated on every
  device. O(n) on the wire per outer iteration; kept as the opt-in
  reference for differential testing.
* ``"batched"``: batched threshold-ladder bisection through the same
  audited ``repro.kernels.bisect_proj.ladder_stats`` Pallas kernel, but
  WITHOUT the exact closing step: results match the exact ones only to
  ladder resolution (~|z|_max / 32^3).
* ``"bisect"``: naive scalar-bisection (one scalar psum per step),
  accurate to ~|z|_max / 2^60.

The paper's global coordinator node does not exist here: every device runs
the identical (z, t, s, v) update on psum'd / gathered statistics (symmetric
replication), which removes the paper's stated single-coordinator
limitation (§6 of the paper).

Resumable-state API
-------------------
Warm starts are first-class, mirroring ``repro.core.bicadmm``:

* ``init_state(n, n_samples, dtype)`` — a fresh :class:`ShardedGlobalState`
  (host-side pytree of *global* arrays; shard_map scatters/gathers it).
* ``fit(A, b, state=...)`` — start the while-loop from a previous solve's
  state; the returned :class:`repro.core.results.FitResult` carries the
  final state in ``.state`` for chaining.
* ``fit_path(A, b, kappas, warm_start=True)`` — the entire kappa-path in
  ONE ``shard_map`` + ``lax.scan`` call: each budget's while-loop is
  warm-started shard-locally from the previous budget's (x, u, z, t, s, v),
  with no host round-trips between path points.

The semantics are tested for exact agreement with ``repro.core.bicadmm``
(single-process oracle) in ``tests/test_sharded.py`` / ``tests/test_path.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from . import bilinear, prox
from .bicadmm import BiCADMMConfig, _zt_update
from .. import faults, runtime
from ..runtime import F32_DOT
from .losses import Loss, get_loss
from .results import (FitResult, SparsePath, classify_status,
                      divergence_probe)
from ..kernels.ops import (block_matvec, block_rmatvec, gram_auto,
                           ladder_stats_auto)

Array = jax.Array

X_UPDATE_MODES = ("auto", "subsolver", "cg")


def auto_axes_mesh(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis in Auto mode. ``jax.make_mesh`` builds
    Explicit axes, which put shardings into array types; the engine's
    shard_map programs and its eager post-processing of their outputs are
    written for Auto axes, so it runs on this view of the caller's mesh
    (same devices, same names and shape)."""
    if mesh.are_all_axes_auto:
        return mesh
    return mesh.update(axis_types=(AxisType.Auto,) * len(mesh.axis_names))


class ShardedState(NamedTuple):
    x: Array        # (n_pad, K) feature-sharded local estimate (per node)
    u: Array        # (n_pad, K)
    z: Array        # (n_pad, K) feature-sharded consensus
    t: Array        # ()
    s: Array        # (n_pad, K)
    v: Array        # ()
    nu: Array       # (m_loc, K) inner dual (per node, replicated over feat)
    omega: Array    # (m_loc, K)
    k: Array
    p_r: Array
    d_r: Array
    b_r: Array


class ShardedGlobalState(NamedTuple):
    """Host-side resumable state: global arrays, scattered by shard_map.

    Layouts: x/u are (N, n_pad, K) — node-major, feature-sharded; z/s are
    (n_pad, K); nu/omega are (n_samples, K) row-sharded over nodes."""
    x: Array
    u: Array
    z: Array
    t: Array
    s: Array
    v: Array
    nu: Array
    omega: Array


# Both engines return the engine-agnostic result types
# (repro.core.results); the old names are kept as aliases.
ShardedResult = FitResult
ShardedPathResult = SparsePath


# --------------------------------------------------------------------------
# batched-threshold reductions (collective-efficient projections)
# --------------------------------------------------------------------------
def _psum(ax):
    # ax=None means "single shard holding the full data": the cross-shard
    # reduction is the identity (a blanket jnp.sum would collapse
    # array-valued ladder statistics, not just scalars)
    return (lambda x: jax.lax.psum(x, ax)) if ax else (lambda x: x)


def _pmax(ax):
    return (lambda x: jax.lax.pmax(x, ax)) if ax else (lambda x: x)


def batched_epigraph_project(z0: Array, t0: Array, feat_axis: str | None,
                             rounds: int = 3, B: int = 32) -> tuple[Array, Array]:
    """Projection onto {(z,t): ||z||_1 <= t} with batched-ladder bisection.

    Each round evaluates h(theta) on a ladder of B thresholds through the
    audited ``repro.kernels.bisect_proj.ladder_stats`` Pallas kernel (the
    same one-pass kernel the exact ``ladder_exact`` engine mode uses) with
    ONE (2*B,)-vector psum, then solves the root inside the final bracket
    as if it were breakpoint-free (h is linear once the active set is
    fixed) — WITHOUT the exact engine's certification/polish, so the result
    is only ladder-resolution accurate. z0 is the local feature shard; the
    returned z is the local shard of the projection.
    """
    sum_fn = _psum(feat_axis)
    max_fn = _pmax(feat_axis)
    az = jnp.abs(z0)
    t0 = jnp.asarray(t0, z0.dtype)
    abs_sum = sum_fn(jnp.sum(az))
    inside = abs_sum <= t0
    hi0 = max_fn(jnp.max(az, initial=0.0))
    apex = (-t0 - hi0) > 0

    def crossing(thetas):
        # ladder stats for the whole round in one data pass + one psum;
        # h decreasing: count the leading rungs with h > 0
        st = sum_fn(ladder_stats_auto(az, thetas))
        h = st[0].astype(z0.dtype) - t0 - thetas
        return jnp.sum((h > 0).astype(jnp.int32))

    lo, hi = bilinear._bracket_rounds(jnp.zeros_like(hi0), hi0, rounds,
                                      B, crossing)
    # root inside [lo, hi] assuming the active set is constant (h linear)
    stats = sum_fn(bilinear.point_stats(az, lo[None]))[:, 0]
    S_lo, cnt = stats[0], stats[1]
    theta = lo + jnp.maximum(S_lo - t0 - lo, 0.0) / (cnt + 1.0)
    theta = jnp.clip(theta, lo, hi)
    theta = jnp.where(inside, 0.0, theta)
    z = jnp.where(apex & ~inside, 0.0,
                  jnp.sign(z0) * jnp.maximum(az - theta, 0.0))
    t = jnp.where(apex & ~inside, jnp.maximum(t0, 0.0),
                  jnp.where(inside, t0, t0 + theta))
    return z, t


def batched_support_skappa(z: Array, kappa: Array | float,
                           feat_axis: str | None,
                           rounds: int = 3, B: int = 32) -> tuple[Array, Array]:
    """Distributed LP over S^kappa via batched-count bisection on tau,
    through the shared ``ladder_stats`` Pallas kernel (count row)."""
    sum_fn = _psum(feat_axis)
    max_fn = _pmax(feat_axis)
    az = jnp.abs(z)
    kap = jnp.asarray(kappa, az.dtype)
    hi0 = max_fn(jnp.max(az, initial=0.0))

    def crossing(taus):
        # cnt decreasing in tau; want largest tau with cnt > kappa as lo
        cnt = sum_fn(ladder_stats_auto(az, taus))[1].astype(z.dtype)
        return jnp.sum((cnt > kap).astype(jnp.int32))

    lo, tau = bilinear._bracket_rounds(jnp.zeros_like(hi0), hi0, rounds,
                                       B, crossing)
    above = (az > tau).astype(z.dtype)
    boundary = ((az > lo) & (az <= tau)).astype(z.dtype)
    cnts = sum_fn(jnp.stack([jnp.sum(above), jnp.sum(boundary)]))
    cnt_above, cnt_bnd = cnts[0], cnts[1]
    leftover = jnp.maximum(kap - cnt_above, 0.0)
    bnd_w = jnp.where(cnt_bnd > 0, leftover / jnp.where(cnt_bnd > 0, cnt_bnd,
                                                        1.0), 0.0)
    w = above + jnp.minimum(bnd_w, 1.0) * boundary
    s_star = jnp.sign(z) * w
    u_max = sum_fn(jnp.sum(az * w))
    return u_max, s_star


# --------------------------------------------------------------------------
# the sharded solver
# --------------------------------------------------------------------------
@dataclasses.dataclass
class ShardedBiCADMM:
    """Bi-cADMM over a ("nodes", "feat") mesh.

    A_global: (N_total_samples, n) — rows sharded over `nodes`, cols over
    `feat`. b_global: (N_total_samples,) [or int labels]. The number of
    paper-nodes N equals the `nodes` mesh size; M equals the `feat` size.
    """
    loss: Loss | str
    cfg: BiCADMMConfig
    mesh: Mesh
    nodes_axis: str | tuple[str, ...] = "nodes"
    feat_axis: str = "feat"
    n_classes: int = 1
    # "ladder_exact" | "exact" | "batched" | "bisect" (see module docstring)
    projection: str = "ladder_exact"
    # x-update engine: "subsolver" = the paper's feature-split inner ADMM
    # (per-block nb x nb Cholesky), "cg" = distributed matrix-free
    # Jacobi-PCG on the squared-loss normal equations (exact node prox, no
    # factorization, one (m_loc, K) psum + three scalar psums per CG step
    # — gather-free under projection="ladder_exact"). "auto" picks cg when
    # the per-device block factor would exceed the dense regime.
    x_update: str = "auto"

    _FACTOR_CACHE_MAX = 4

    def __post_init__(self):
        if isinstance(self.loss, str):
            self.loss = get_loss(self.loss, self.n_classes)
        self.mesh = auto_axes_mesh(self.mesh)
        if self.projection not in ("ladder_exact", "exact", "batched",
                                   "bisect"):
            raise ValueError(f"unknown projection mode {self.projection!r}")
        if self.cfg.projection not in ("ladder", "sort"):
            raise ValueError(
                f"unknown cfg.projection mode {self.cfg.projection!r}")
        if self.cfg.projection == "sort" and self.projection != "exact":
            raise ValueError(
                'cfg.projection="sort" needs the full gathered vector; use '
                'the gather-based engine mode (projection="exact")')
        if self.x_update not in X_UPDATE_MODES:
            raise ValueError(f"unknown x_update mode {self.x_update!r}; "
                             f"expected one of {X_UPDATE_MODES}")
        if self.x_update == "cg" and self.loss.name != "squared":
            raise ValueError('x_update="cg" solves the squared-loss normal '
                             "equations; other losses use the feature-split "
                             'sub-solver (x_update="subsolver")')
        runtime.check_x64(self.cfg.precision)
        # fault-injection hook (repro.faults): None outside an inject()
        # context; baked into this instance's shard_map programs at trace
        # time (the _jit_cache is per instance, so it never leaks).
        self._fault_hook = faults.active_hook(self)
        # memoized policy data casts (see BiCADMM._cast): stable array ids
        # keep the id-keyed factor cache below hitting across repeat fits.
        self._cast_cache: dict = {}
        # jitted shard_map programs, keyed on the python values the closures
        # bake in — reused across calls so repeated fits/sweeps don't
        # re-trace (shapes/dtypes are handled by jit's own cache)
        self._jit_cache: dict = {}
        # per-data setup factors (per-device Cholesky / CG preconditioner),
        # keyed on the data array so repeated warm-started fits — the
        # resumable-state workflow — pay the setup shard_map program once.
        # Entries hold strong references to the keyed arrays.
        self._factor_cache: dict = {}

    def _cast(self, A_global: Array, b_global: Array) -> tuple[Array, Array]:
        """Apply the precision policy's data cast (no-op for data=None)."""
        pol = self.cfg.precision
        if pol.data is None:
            return A_global, b_global
        if isinstance(A_global, jax.core.Tracer) \
                or isinstance(b_global, jax.core.Tracer):
            return pol.cast_data(A_global), pol.cast_data(b_global)
        key = (id(A_global), id(b_global))
        hit = self._cast_cache.get(key)
        if hit is None:
            if len(self._cast_cache) >= self._FACTOR_CACHE_MAX:
                self._cast_cache.pop(next(iter(self._cast_cache)))
            hit = (A_global, b_global, pol.cast_data(A_global),
                   pol.cast_data(b_global))
            self._cast_cache[key] = hit
        return hit[2], hit[3]

    def _x_mode(self, nb: int) -> str:
        if self.x_update != "auto":
            return self.x_update
        if self.loss.name == "squared" and nb > prox.DENSE_MAX_N:
            return "cg"
        return "subsolver"

    # ---- specs -------------------------------------------------------------
    def _sizes(self, n: int):
        ax = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        nodes = self.nodes_axis if isinstance(self.nodes_axis, tuple) else (self.nodes_axis,)
        N = 1
        for a in nodes:
            N *= ax[a]
        M = ax[self.feat_axis]
        nb = -(-n // M)
        return N, M, nb

    def _pad(self, A: Array, n_pad: int) -> Array:
        n = A.shape[1]
        if n_pad != n:
            A = jnp.pad(A, ((0, 0), (0, n_pad - n)))
        return A

    # ---- cached setup --------------------------------------------------------
    def _setup_factors(self, A_p: Array, n: int) -> Array:
        """Per-device x-update factors as one jitted shard_map program:
        the (nb, nb) block Cholesky for the sub-solver engine — global
        layout (N, M, nb, nb) — or the (nb,) Jacobi preconditioner diagonal
        for the CG engine, layout (N, M, nb)."""
        cfg = self.cfg
        N, M, nb = self._sizes(n)
        mode = self._x_mode(nb)
        nodes, feat = self.nodes_axis, self.feat_axis
        sigma = 1.0 / (N * cfg.gamma)
        c = sigma + cfg.rho_c

        if mode == "cg":
            def setup_run(A_blk):
                # batched-mirrored col_sumsq (unit leading axis): the
                # reference engine computes it under vmap over nodes, and
                # batched/unbatched reductions differ at the ulp level
                acc = prox._accum(A_blk.dtype)
                if acc == A_blk.dtype:
                    colsq = jnp.einsum("jmn,jmn->jn", A_blk[None],
                                       A_blk[None], precision=F32_DOT)[0]
                else:
                    colsq = jnp.einsum("jmn,jmn->jn", A_blk[None],
                                       A_blk[None],
                                       preferred_element_type=acc,
                                       precision=F32_DOT)[0]
                return colsq[None, None]
            out_specs = P(nodes, feat, None)
        else:
            def setup_run(A_blk):
                acc = prox._accum(A_blk.dtype)
                G = gram_auto(A_blk, out_dtype=acc)
                H = cfg.rho_l * G + c * jnp.eye(A_blk.shape[1], dtype=acc)
                return jnp.linalg.cholesky(H)[None, None]
            out_specs = P(nodes, feat, None, None)

        key = ("setup", n, mode)
        if key not in self._jit_cache:
            self._jit_cache[key] = jax.jit(jax.shard_map(
                setup_run, mesh=self.mesh, in_specs=(P(nodes, feat),),
                out_specs=out_specs, check_vma=False))
        return self._jit_cache[key](A_p)

    def _prepare(self, A_global: Array, n: int) -> tuple[Array, Array]:
        """Pad + factor once per data array (id-keyed, strong-ref cache):
        repeated warm-started ``fit``/``fit_path`` calls on the same data
        skip the Gram + factorization entirely."""
        N, M, nb = self._sizes(n)
        n_pad = M * nb
        if isinstance(A_global, jax.core.Tracer):
            A_p = self._pad(A_global, n_pad)
            return A_p, self._setup_factors(A_p, n)
        key = (id(A_global), A_global.shape, str(A_global.dtype),
               self._x_mode(nb))
        hit = self._factor_cache.get(key)
        if hit is not None:
            return hit[1], hit[2]
        A_p = self._pad(A_global, n_pad)
        xfac = self._setup_factors(A_p, n)
        if len(self._factor_cache) >= self._FACTOR_CACHE_MAX:
            self._factor_cache.pop(next(iter(self._factor_cache)))
        self._factor_cache[key] = (A_global, A_p, xfac)
        return A_p, xfac

    # ---- resumable state -----------------------------------------------------
    def init_state(self, n: int, n_samples: int,
                   dtype=jnp.float32) -> ShardedGlobalState:
        """Fresh zero state for problems with ``n`` features and
        ``n_samples`` total rows (global, host-side layout)."""
        N, M, nb = self._sizes(n)
        K = self.loss.n_classes
        n_pad = M * nb
        z = jnp.zeros((n_pad, K), dtype)
        return ShardedGlobalState(
            x=jnp.zeros((N, n_pad, K), dtype), u=jnp.zeros((N, n_pad, K), dtype),
            z=z, t=jnp.asarray(0.0, dtype), s=jnp.zeros((n_pad, K), dtype),
            v=jnp.asarray(0.0, dtype),
            nu=jnp.zeros((n_samples, K), dtype),
            omega=jnp.zeros((n_samples, K), dtype))

    def _state_specs(self):
        nodes, feat = self.nodes_axis, self.feat_axis
        return ShardedGlobalState(
            x=P(nodes, feat, None), u=P(nodes, feat, None),
            z=P(feat, None), t=P(), s=P(feat, None), v=P(),
            nu=P(nodes, None), omega=P(nodes, None))

    # ---- the shard-local program --------------------------------------------
    def _local_funcs(self, N, M, A_blk, b_blk, xfac):
        """Build the shard-local (init/step/cond) closures. Runs on each
        device inside shard_map; A_blk is the (m_loc, nb) data block and
        ``xfac`` its cached setup factors — the (nb, nb) block Cholesky
        (sub-solver engine) or the (nb,) Jacobi diagonal (CG engine)."""
        cfg, loss = self.cfg, self.loss
        K = loss.n_classes
        nodes, feat = self.nodes_axis, self.feat_axis
        psum_f = _psum(feat)
        psum_n = _psum(nodes)
        rho_b = cfg.rho_b_eff
        sigma = 1.0 / (N * cfg.gamma)
        c = sigma + cfg.rho_c
        m_loc, nb = A_blk.shape
        x_mode = self._x_mode(nb)
        chol = xfac if x_mode == "subsolver" else None

        def chol_solve(rhs):
            y = jax.scipy.linalg.solve_triangular(chol, rhs, lower=True)
            return jax.scipy.linalg.solve_triangular(chol.T, y, lower=False)

        mode = self.projection
        exact = mode in ("exact", "ladder_exact")
        if exact:
            # Reference-faithful linear algebra (both exact modes): the
            # sub-solver oracle (repro.core.subsolver) computes every block
            # through *batched* (leading block axis) einsums / vmapped
            # triangular solves, and XLA lowers batched and unbatched
            # matmuls differently at the ulp level. Mirror the batched
            # forms with a unit leading axis so a (1,1)-mesh trajectory is
            # bit-identical to the oracle.
            from .subsolver import _block_solve
            A1 = A_blk[None]                       # (1, m_loc, nb)

            def mm_fwd(x):                         # (nb, K) -> (m_loc, K)
                return block_matvec(A1, x[None])[0]

            def mm_t(ct):                          # (m_loc, K) -> (nb, K)
                return block_rmatvec(A1, ct[None])[0]

            if chol is not None:
                chol1 = chol[None]

                def x_solve(rhs):
                    return jax.vmap(_block_solve)(chol1, rhs[None])[0]
            else:
                x_solve = None
        else:
            mm_fwd = lambda x: jnp.dot(A_blk, x, precision=F32_DOT)
            mm_t = lambda ct: jnp.dot(A_blk.T, ct, precision=F32_DOT)
            x_solve = chol_solve if chol is not None else None

        def flat(x):  # (nb, K) -> (nbK,) for the projection helpers
            return x.reshape(-1)

        def unflat(x):
            return x.reshape(nb, K)

        def gather_full(x2d):
            """(nb, K) local shard -> (n_pad*K,) replicated full vector,
            laid out exactly like the reference engine's flat iterate."""
            g = jax.lax.all_gather(x2d, feat, axis=0, tiled=True)
            return g.reshape(-1)

        def slice_local(flat_g):
            """(n_pad*K,) full vector -> this device's (nb, K) shard."""
            g = flat_g.reshape(M * nb, K)
            j = jax.lax.axis_index(feat)
            return jax.lax.dynamic_slice_in_dim(g, j * nb, nb, axis=0)

        def feat_mean(w):
            if exact:
                # mean over the gathered (M, m_loc, K) stack — the same
                # reduction order as the reference sub-solver
                return jnp.mean(jax.lax.all_gather(w, feat, axis=0), axis=0)
            return psum_f(w) / M

        def inner_admm(x0, nu0, om0, q):
            """Algorithm 2 across the feat axis (q: (nb,K) prox center)."""
            Mf = float(M)

            def it(carry, _):
                x, nu, om = carry
                w = mm_fwd(x)                              # (m_loc, K)
                w_bar = feat_mean(w)
                c_t = w + (om - w_bar - nu)
                rhs = cfg.rho_l * mm_t(c_t) + cfg.rho_c * q
                x_new = x_solve(rhs)
                w_new = mm_fwd(x_new)
                w_bar_new = feat_mean(w_new)
                a = w_bar_new + nu
                pq = Mf * a
                pred = loss.prox_omega(
                    pq[:, 0] if K == 1 else pq, b_blk, cfg.rho_l / Mf)
                pred = pred[:, None] if K == 1 else pred
                om_new = pred / Mf
                nu_new = nu + w_bar_new - om_new
                return (x_new, nu_new, om_new), None
            (x, nu, om), _ = jax.lax.scan(it, (x0, nu0, om0), None,
                                          length=cfg.inner_iters)
            return x, nu, om

        if x_mode == "cg":
            # Distributed matrix-free x-update: exact squared-loss node prox
            # by Jacobi-PCG on (A_i^T A_i + c I) x = A_i^T b_i + rho_c q,
            # run directly on the feature shards. Per CG iteration the wire
            # carries ONE (m_loc,) prediction psum (the A p reduction over
            # `feat`) and three scalar psums (the p.Ap / r.z / r.r dots) —
            # no all-gather, so with projection="ladder_exact" the whole
            # outer iteration is gather-free. The loop is the SAME
            # repro.core.prox.pcg the reference engine runs (psum-wrapped
            # reductions), warm-started from the previous outer iterate, so
            # a (1,1) mesh matches BiCADMM(x_solver="pcg") with identical
            # iteration counts. The reference x-update is vmapped over
            # nodes, so its matvecs/dots lower as BATCHED contractions;
            # mirror them with a unit leading axis (same trick as the exact
            # projection modes) so the setup statistics (colsq, Atb) are
            # bit-identical and the iterates agree to the last ulps of the
            # CG recurrence itself.
            A1 = A_blk[None]

            def cg_fwd(p):                                 # (nb,) -> (m_loc,)
                return jnp.einsum("jmn,jn->jm", A1, p[None],
                                  precision=F32_DOT)[0]

            def cg_adj(w):                                 # (m_loc,) -> (nb,)
                return jnp.einsum("jmn,jm->jn", A1, w[None],
                                  precision=F32_DOT)[0]

            def cg_dot(u2, w2):
                return psum_f(jnp.einsum("jn,jn->j", u2[None], w2[None],
                                         precision=F32_DOT)[0])

            Atb = cg_adj(b_blk)                            # (nb,)
            inv = 1.0 / (xfac + c)                         # Jacobi precond

            def x_update(x0, nu0, om0, q):
                xf = prox.pcg(
                    lambda p: cg_adj(psum_f(cg_fwd(p))) + c * p,
                    Atb + cfg.rho_c * q[:, 0], x0[:, 0],
                    lambda r: inv * r, cfg.cg_iters, cfg.cg_tol,
                    dot_fn=cg_dot)
                return xf[:, None], nu0, om0
        else:
            x_update = inner_admm

        # every reduction of the exact sort-free engine, psum/pmax-wrapped:
        # bracketing rounds are one (2*B,)-psum, polish steps one (2,)-psum
        lops = bilinear.LadderOps(
            sum_fn=lambda x: psum_f(jnp.sum(x)),
            max_fn=lambda x: _pmax(feat)(jnp.max(x, initial=0.0)),
            stats_fn=lambda az, th: psum_f(ladder_stats_auto(az, th)),
            point_fn=lambda az, th: psum_f(bilinear.point_stats(az, th)),
            band_fn=lambda az, lo, hi: psum_f(bilinear.band_stats(az, lo, hi)),
        )

        def project(z0f, t0):
            if self.projection == "batched":
                return batched_epigraph_project(z0f, t0, feat)
            return bilinear.project_l1_epigraph_bisect(
                z0f, t0, sum_fn=lambda x: psum_f(jnp.sum(x)) if x.ndim else psum_f(x),
                max_fn=lambda x: _pmax(feat)(jnp.max(x)) if x.ndim else _pmax(feat)(x))

        def zt_update_sharded(z0, t0, wc, s, v):
            a = N * cfg.rho_c
            ss = psum_f(jnp.vdot(s, s, precision=F32_DOT))
            L = a + rho_b * (ss + 1.0)
            step = 1.0 / L

            def grads(z, t):
                r = psum_f(jnp.vdot(s, z, precision=F32_DOT)) - t + v
                return a * (z - wc) + rho_b * r * s, -rho_b * r

            def body(_, carry):
                z, t, zy, ty, tk = carry
                gz, gt = grads(zy, ty)
                zf, tf = project(flat(zy - step * gz), ty - step * gt)
                z_new, t_new = unflat(zf), tf
                tk_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * tk * tk))
                beta = (tk - 1.0) / tk_new
                return (z_new, t_new, z_new + beta * (z_new - z),
                        t_new + beta * (t_new - t), tk_new)

            z0f, t0p = project(flat(z0), t0)
            z0p = unflat(z0f)
            z, t, *_ = jax.lax.fori_loop(
                0, cfg.zt_iters, body,
                (z0p, t0p, z0p, t0p, jnp.asarray(1.0, z0.dtype)))
            return z, t

        def outer_step_exact(st: ShardedState, kappa) -> ShardedState:
            """Reference-faithful outer iteration via the paper's "Collect":
            all-gather the (z,t,s,v) block over `feat` and run the *same*
            full-vector projections as repro.core.bicadmm, replicated on
            every device. O(n) on the wire; opt-in (projection="exact")."""
            q = st.z - st.u
            x_new, nu, om = x_update(st.x, st.nu, st.omega, q)
            if cfg.over_relax != 1.0:
                x_eff = cfg.over_relax * x_new + (1.0 - cfg.over_relax) * st.z
            else:
                x_eff = x_new
            wc = psum_n(x_eff + st.u) / N
            zg_old = gather_full(st.z)
            zg, t_new = _zt_update(zg_old, st.t, gather_full(wc),
                                   gather_full(st.s), st.v,
                                   float(N), cfg.rho_c, rho_b, cfg.zt_iters,
                                   projection=cfg.projection,
                                   polish_dtype=cfg.precision.kkt_polish)
            sg = bilinear.s_update(
                zg, t_new, st.v, kappa,
                method=("sort" if cfg.projection == "sort" else "ladder"))
            gval = bilinear.g(zg, sg, t_new)
            z_new, s_new = slice_local(zg), slice_local(sg)
            u_new = st.u + x_eff - z_new
            v_new = st.v + gval
            # residuals (14), reference reduction order
            p_r = psum_n(jnp.linalg.norm(gather_full(x_new - z_new)))
            d_r = jnp.sqrt(jnp.asarray(N, zg.dtype)) * cfg.rho_c * \
                jnp.linalg.norm(zg - zg_old)
            b_r = jnp.abs(gval)
            return ShardedState(x_new, u_new, z_new, t_new, s_new, v_new,
                                nu, om, st.k + 1, p_r, d_r, b_r)

        def outer_step_ladder(st: ShardedState, kappa) -> ShardedState:
            """Default outer iteration: the exact sort-free projection
            engine on the local feature shard. Identical math to the
            reference oracle — the shared ``_zt_update`` / ``s_update`` run
            here with psum-wrapped reductions, so the only wire traffic of
            the (z,t,s,v) block is O(B)-sized ladder/polish statistics."""
            q = st.z - st.u
            x_new, nu, om = x_update(st.x, st.nu, st.omega, q)
            if cfg.over_relax != 1.0:
                x_eff = cfg.over_relax * x_new + (1.0 - cfg.over_relax) * st.z
            else:
                x_eff = x_new
            wc = psum_n(x_eff + st.u) / N
            zf, t_new = _zt_update(flat(st.z), st.t, flat(wc), flat(st.s),
                                   st.v, float(N), cfg.rho_c, rho_b,
                                   cfg.zt_iters, ops=lops,
                                   polish_dtype=cfg.precision.kkt_polish)
            z_new = unflat(zf)
            sf = bilinear.s_update(zf, t_new, st.v, kappa, ops=lops)
            s_new = unflat(sf)
            u_new = st.u + x_eff - z_new
            gval = bilinear.g(zf, sf, t_new, sum_fn=lops.sum_fn)
            v_new = st.v + gval
            # residuals (14): p_r = sum_i ||x_i - z||; local: ssq over feat
            p_r = psum_n(jnp.sqrt(psum_f(jnp.sum((x_new - z_new) ** 2))))
            d_r = jnp.sqrt(jnp.asarray(N, zf.dtype)) * cfg.rho_c * \
                jnp.sqrt(psum_f(jnp.sum((z_new - st.z) ** 2)))
            b_r = jnp.abs(gval)
            return ShardedState(x_new, u_new, z_new, t_new, s_new, v_new,
                                nu, om, st.k + 1, p_r, d_r, b_r)

        def outer_step_sharded(st: ShardedState, kappa) -> ShardedState:
            q = st.z - st.u
            x_new, nu, om = x_update(st.x, st.nu, st.omega, q)
            if cfg.over_relax != 1.0:
                x_eff = cfg.over_relax * x_new + (1.0 - cfg.over_relax) * st.z
            else:
                x_eff = x_new
            wc = psum_n(x_eff + st.u) / N
            z_new, t_new = zt_update_sharded(st.z, st.t, wc, st.s, st.v)
            if self.projection == "batched":
                u_max, s_star = batched_support_skappa(
                    flat(z_new), kappa, feat)
            else:
                u_max, s_star = bilinear.support_skappa_bisect(
                    flat(z_new), kappa,
                    sum_fn=lambda x: psum_f(jnp.sum(x)) if x.ndim else psum_f(x),
                    max_fn=lambda x: _pmax(feat)(jnp.max(x)) if x.ndim else _pmax(feat)(x))
            ctar = jnp.asarray(t_new - st.v, z_new.dtype)
            c_cl = jnp.clip(ctar, -u_max, u_max)
            theta = jnp.where(u_max > 0, c_cl / jnp.where(u_max > 0, u_max, 1.0), 0.0)
            s_new = unflat(theta * s_star)
            u_new = st.u + x_eff - z_new
            gval = psum_f(jnp.vdot(z_new, s_new, precision=F32_DOT)) - t_new
            v_new = st.v + gval
            # residuals (14): p_r = sum_i ||x_i - z||; local: ssq over feat
            loc_sq = jnp.sum((x_new - z_new) ** 2)
            p_r = psum_n(jnp.sqrt(psum_f(loc_sq)))
            d_r = jnp.sqrt(jnp.asarray(N, z_new.dtype)) * cfg.rho_c * \
                jnp.sqrt(psum_f(jnp.sum((z_new - st.z) ** 2)))
            b_r = jnp.abs(gval)
            return ShardedState(x_new, u_new, z_new, t_new, s_new, v_new,
                                nu, om, st.k + 1, p_r, d_r, b_r)

        if mode == "exact":
            outer_step = outer_step_exact
        elif mode == "ladder_exact":
            outer_step = outer_step_ladder
        else:
            outer_step = outer_step_sharded

        big = jnp.asarray(jnp.inf, cfg.precision.state_dtype(A_blk.dtype))

        def reset(st: ShardedState) -> ShardedState:
            return st._replace(k=jnp.asarray(0), p_r=big, d_r=big, b_r=big)

        return outer_step, reset

    def _unpack_state(self, gs: ShardedGlobalState, dt):
        """Shard-local views (inside shard_map) -> ShardedState."""
        big = jnp.asarray(jnp.inf, dt)
        return ShardedState(
            x=gs.x[0], u=gs.u[0], z=gs.z, t=gs.t, s=gs.s, v=gs.v,
            nu=gs.nu, omega=gs.omega,
            k=jnp.asarray(0), p_r=big, d_r=big, b_r=big)

    @staticmethod
    def _pack_state(st: ShardedState) -> ShardedGlobalState:
        return ShardedGlobalState(x=st.x[None], u=st.u[None], z=st.z, t=st.t,
                                  s=st.s, v=st.v, nu=st.nu, omega=st.omega)

    def _unpad_flat(self, z: Array, n: int, n_pad: int) -> Array:
        """(n_pad, K) feature-padded iterate -> (n*K,) reference layout."""
        K = self.loss.n_classes
        return z[:n].reshape(-1) if K > 1 else z.reshape(-1)[: n * K]

    # ---- public API ----------------------------------------------------------
    def fit(self, A_global: Array, b_global: Array, *,
            state: ShardedGlobalState | None = None,
            record_history: bool = False, iters: int | None = None
            ) -> ShardedResult:
        cfg = self.cfg
        K = self.loss.n_classes
        A_global, b_global = self._cast(A_global, b_global)
        n = A_global.shape[1]
        N, M, nb = self._sizes(n)
        n_pad = M * nb
        A_p, xfac = self._prepare(A_global, n)
        sdt = cfg.precision.state_dtype(A_p.dtype)
        iters = iters if iters is not None else cfg.max_iter
        if state is None:
            state = self.init_state(n, A_global.shape[0], sdt)

        nodes = self.nodes_axis
        st_specs = self._state_specs()
        fac_spec = P(nodes, self.feat_axis, *([None] * (xfac.ndim - 2)))
        in_specs = (P(nodes, self.feat_axis),
                    P(nodes) if b_global.ndim == 1 else P(nodes, None),
                    fac_spec, st_specs)
        # z / history / scalars are replicated over `nodes`; z is
        # feat-sharded on its leading dim.
        out_specs = ((P(self.feat_axis, None), P(), P(), P(), P(), P()),
                     P(None, None), st_specs)

        def run(A_blk, b_blk, xf, gs):
            outer_step, _ = self._local_funcs(N, M, A_blk, b_blk, xf[0, 0])
            st0 = self._unpack_state(gs, sdt)
            kappa = jnp.asarray(float(cfg.kappa), sdt)
            step = lambda st: outer_step(st, kappa)

            if self._fault_hook is not None:
                inner_step = step
                step = lambda st: self._fault_hook(inner_step(st))

            if record_history:
                def body(st, _):
                    st = step(st)
                    return st, jnp.stack([st.p_r, st.d_r, st.b_r])
                st, hist = jax.lax.scan(body, st0, None, length=iters)
            else:
                def cond(st):
                    done = ((st.p_r < cfg.tol) & (st.d_r < cfg.tol)
                            & (st.b_r < cfg.tol))
                    diverged = divergence_probe(st, cfg.divergence_tol)
                    return (~done) & (~diverged) & (st.k < iters)
                st = jax.lax.while_loop(cond, step, st0)
                hist = jnp.zeros((iters, 3), sdt)
            return ((st.z, st.k, st.p_r, st.d_r, st.b_r, st.t), hist,
                    self._pack_state(st))

        key = ("fit", n, b_global.ndim, record_history, iters)
        if key not in self._jit_cache:
            # the state pytree is donated: its iterate buffers are reused
            # in place by the while-loop (fit consumes a passed-in state —
            # keep using the returned result.state)
            self._jit_cache[key] = jax.jit(jax.shard_map(
                run, mesh=self.mesh, in_specs=in_specs,
                out_specs=out_specs, check_vma=False), donate_argnums=(3,))
        (z, k, p_r, d_r, b_r, t), hist, gs = \
            self._jit_cache[key](A_p, b_global, xfac, state)

        zf = self._unpad_flat(z, n, n_pad)
        z_sparse = bilinear.hard_threshold(zf, cfg.kappa)
        support = jnp.abs(z_sparse) > 0
        status = classify_status(k, p_r, d_r, b_r, tol=cfg.tol,
                                 divergence_tol=cfg.divergence_tol)
        return FitResult(z_sparse.reshape(n, K), zf, support, k, p_r, d_r,
                         b_r, hist if record_history else None, gs,
                         status=status)

    def fit_path(self, A_global: Array, b_global: Array, kappas, *,
                 state: ShardedGlobalState | None = None,
                 warm_start: bool = True) -> ShardedPathResult:
        """Fit the whole kappa-path in one shard_map'd ``lax.scan``: each
        budget's while-loop warm-starts from the previous budget's ADMM
        state (``warm_start=False`` re-initializes per point — the cold
        baseline with identical numerics and collectives)."""
        cfg = self.cfg
        K = self.loss.n_classes
        A_global, b_global = self._cast(A_global, b_global)
        n = A_global.shape[1]
        N, M, nb = self._sizes(n)
        n_pad = M * nb
        A_p, xfac = self._prepare(A_global, n)
        sdt = cfg.precision.state_dtype(A_p.dtype)
        kaps = jnp.asarray(kappas, sdt)
        if kaps.ndim != 1 or kaps.shape[0] == 0:
            raise ValueError("kappas must be a non-empty 1-D grid")
        if state is None:
            state = self.init_state(n, A_global.shape[0], sdt)

        nodes = self.nodes_axis
        st_specs = self._state_specs()
        fac_spec = P(nodes, self.feat_axis, *([None] * (xfac.ndim - 2)))
        in_specs = (P(nodes, self.feat_axis),
                    P(nodes) if b_global.ndim == 1 else P(nodes, None),
                    fac_spec, P(), st_specs)
        out_specs = ((P(None, self.feat_axis, None), P(None), P(None),
                      P(None), P(None)), st_specs)

        def run(A_blk, b_blk, xf, ks, gs):
            outer_step, reset = self._local_funcs(N, M, A_blk, b_blk,
                                                  xf[0, 0])
            st_init = self._unpack_state(gs, sdt)

            def cond(st):
                done = ((st.p_r < cfg.tol) & (st.d_r < cfg.tol)
                        & (st.b_r < cfg.tol))
                diverged = divergence_probe(st, cfg.divergence_tol)
                return (~done) & (~diverged) & (st.k < cfg.max_iter)

            def step_pt(kappa):
                if self._fault_hook is None:
                    return lambda s: outer_step(s, kappa)
                return lambda s: self._fault_hook(outer_step(s, kappa))

            def solve_one(carry, kappa):
                st = jax.lax.while_loop(
                    cond, step_pt(kappa), reset(carry))
                out = (st.z, st.k, st.p_r, st.d_r, st.b_r)
                return (st if warm_start else st_init), out

            last, outs = jax.lax.scan(solve_one, st_init, ks)
            return outs, self._pack_state(last)

        key = ("path", n, b_global.ndim, warm_start)
        if key not in self._jit_cache:
            # state donated: path iterate buffers are reused in place
            self._jit_cache[key] = jax.jit(jax.shard_map(
                run, mesh=self.mesh, in_specs=in_specs,
                out_specs=out_specs, check_vma=False), donate_argnums=(4,))
        (z, k, p_r, d_r, b_r), gs = \
            self._jit_cache[key](A_p, b_global, xfac, kaps, state)

        zf = jax.vmap(lambda zz: self._unpad_flat(zz, n, n_pad))(z)
        x_sparse = jax.vmap(bilinear.hard_threshold)(zf, kaps)
        support = jnp.abs(x_sparse) > 0
        npts = kaps.shape[0]
        fill = lambda v: jnp.full((npts,), v, kaps.dtype)
        status = classify_status(k, p_r, d_r, b_r, tol=cfg.tol,
                                 divergence_tol=cfg.divergence_tol)
        return SparsePath(x_sparse.reshape(npts, n, K), zf, support, k,
                          p_r, d_r, b_r, jnp.sum(support, axis=1), kaps,
                          fill(cfg.gamma), fill(cfg.rho_c), state=gs,
                          strategy="warm-scan" if warm_start else "cold-scan",
                          status=status)
