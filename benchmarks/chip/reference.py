"""Plain reference for a best-subset fit: imports nothing of the program.

The problem (the paper's (1), one loss summed over all N * m rows):

    min_x  sum_r l(a_r^T x, b_r) + 1/(2 gamma) ||x||^2   s.t. ||x||_0 <= kappa

solved the plain way, in f32 with every product at HIGHEST precision:

  1. the ridge (or ridge-logistic) fit over all n features;
  2. its kappa largest magnitudes are the support;
  3. the same fit restricted to that support gives the coefficients.

On the benchmark's data (unit-norm columns, planted values of magnitude at
least 1, noise 1e-2 or 2% flipped labels) step 2 recovers the best subset
with a margin of two orders of magnitude, so the restricted optimum of
step 3 is the answer a converged Bi-cADMM fit has to reproduce.

Products run node by node over the (N, m, n) design, so no copy of A is
made. Where A's nodes lie on several chips, each chip forms the Gram
A_i^T A_i and A_i^T b_i of its own rows, and only those (n, n) and (n,)
sums travel, to the chip of the first node, where the one (n, n) solve
runs: A is never gathered. Logistic fits use Newton's method with a
backtracking line search that sums the objective's change row by row
(``_change``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
NEWTON_FULL = 30
NEWTON_SUPPORT = 40
STEPS = 2.0 ** -np.arange(12)


@jax.jit
def _gram(A):
    def body(i, G):
        Ai = A[i]
        return G + jnp.dot(Ai.T, Ai, precision=HI)
    n = A.shape[2]
    return jax.lax.fori_loop(0, A.shape[0], body,
                             jnp.zeros((n, n), jnp.float32))


@jax.jit
def _rmatvec(A, b):
    return jnp.einsum("imn,im->n", A, b, precision=HI)


def _node_blocks(x) -> list:
    """The shards of ``x`` that hold distinct ranges of nodes (its leading
    axis), in node order: one for an array on one chip."""
    blocks = {}
    for s in x.addressable_shards:
        if any(sl != slice(None) for sl in s.index[1:]):
            raise ValueError("the reference takes arrays split by nodes "
                             f"only, got a shard at {s.index}")
        blocks.setdefault(s.index[0].start or 0, s)
    return [blocks[k] for k in sorted(blocks)]


def _summed(parts: list, device):
    """The sum, in order, of per-chip results, each moved to ``device``."""
    total = None
    for p in parts:
        p = jax.device_put(p, device)
        total = p if total is None else total + p
    return total


def gram(A):
    """A^T A, each chip's nodes summed on that chip (``_gram``), then the
    chips' (n, n) sums on the first node's chip."""
    blocks = _node_blocks(A)
    return _summed([_gram(s.data) for s in blocks], blocks[0].device)


def rmatvec(A, b):
    """A^T b, each chip's rows on that chip, summed as ``gram`` sums."""
    a_blocks, b_blocks = _node_blocks(A), _node_blocks(b)
    if [s.index[0] for s in a_blocks] != [s.index[0] for s in b_blocks]:
        raise ValueError("A and b are split over different nodes")
    return _summed([_rmatvec(sa.data, jax.device_put(sb.data, sa.device))
                     for sa, sb in zip(a_blocks, b_blocks)],
                    a_blocks[0].device)


@jax.jit
def _matvec(A, x):
    return jnp.einsum("imn,n->im", A, x, precision=HI)


@partial(jax.jit, static_argnames=("kappa",))
def _ridge(G, c, inv_gamma, kappa: int):
    n = G.shape[0]
    x_full = jax.scipy.linalg.solve(G + inv_gamma * jnp.eye(n), c,
                                    assume_a="pos")
    _, idx = jax.lax.top_k(jnp.abs(x_full), kappa)
    idx = jnp.sort(idx)
    Gs = G[idx][:, idx]
    xs = jax.scipy.linalg.solve(Gs + inv_gamma * jnp.eye(kappa), c[idx],
                                assume_a="pos")
    return jnp.zeros((n,), jnp.float32).at[idx].set(xs), idx


def squared(A, b, gamma: float, kappa: int, G=None):
    """(coef (n,), support (n,) bool) of the best-subset ridge fit.
    ``G`` = A^T A (``gram``) may be passed in, as it depends on A alone."""
    G = gram(A) if G is None else G
    coef, idx = _ridge(G, rmatvec(A, b), 1.0 / gamma, kappa)
    support = np.zeros(A.shape[2], bool)
    support[np.asarray(idx)] = True
    return np.asarray(coef), support


def _change(s, b, Ad, x, d, t, inv_gamma):
    """f(x + t d) - f(x) for f(x) = sum softplus(-b A x) + inv_gamma/2
    ||x||^2, with s = A x. Near the optimum the decrease is far below the
    f32 rounding of f itself (a sum over all rows), so a difference of two
    totals refuses every step and Newton stalls short of the optimum. Row
    by row, softplus(u + v) - softplus(u) = log1p(sigmoid(u) expm1(v)) is
    exact to f32 in the change itself; where |v| >= 1 the plain
    difference is, and expm1 could overflow."""
    u, v = -b * s, -b * (t * Ad)
    small = jnp.abs(v) < 1.0
    near = jnp.log1p(jax.nn.sigmoid(u) * jnp.expm1(jnp.where(small, v, 0.0)))
    far = jax.nn.softplus(u + v) - jax.nn.softplus(u)
    rows = jnp.where(small, near, far)
    return jnp.sum(rows) + 0.5 * inv_gamma * t * jnp.vdot(2.0 * x + t * d, d)


@partial(jax.jit, static_argnames=("iters",))
def _newton(A, b, x0, mask, inv_gamma, iters: int):
    """Damped Newton on sum softplus(-b A x) + inv_gamma/2 ||x||^2 over
    the coordinates in ``mask`` (the others stay 0)."""
    steps = jnp.asarray(STEPS, jnp.float32)

    def body(_, x):
        s = _matvec(A, x)
        p = jax.nn.sigmoid(-b * s)
        g = (_rmatvec(A, -b * p) + inv_gamma * x) * mask
        w = p * (1.0 - p)

        def hblock(i, H):
            Aw = A[i] * w[i][:, None]
            return H + jnp.dot(A[i].T, Aw, precision=HI)
        n = x.shape[0]
        H = jax.lax.fori_loop(0, A.shape[0], hblock,
                              jnp.zeros((n, n), jnp.float32))
        H = H * mask[:, None] * mask[None, :] + jnp.diag(
            jnp.where(mask > 0, inv_gamma, 1.0))
        d = -jax.scipy.linalg.solve(H, g, assume_a="pos") * mask
        Ad = _matvec(A, d)
        slope = jnp.vdot(g, d)
        changes = jax.vmap(
            lambda t: _change(s, b, Ad, x, d, t, inv_gamma))(steps)
        ok = changes <= 1e-4 * steps * slope
        t = jnp.where(jnp.any(ok), steps[jnp.argmax(ok)], 0.0)
        return x + t * d

    return jax.lax.fori_loop(0, iters, body, x0)


def logistic(A, b, gamma: float, kappa: int):
    """(coef (n,), support (n,) bool) of the best-subset ridge-logistic
    fit, of an A on one chip."""
    if len(_node_blocks(A)) > 1:
        raise ValueError("the logistic reference takes an A on one chip")
    n = A.shape[2]
    inv_gamma = jnp.float32(1.0 / gamma)
    ones = jnp.ones((n,), jnp.float32)
    x_full = _newton(A, b, jnp.zeros((n,), jnp.float32), ones, inv_gamma,
                     NEWTON_FULL)
    idx = np.sort(np.argsort(-np.abs(np.asarray(x_full)))[:kappa])
    mask = np.zeros(n, np.float32)
    mask[idx] = 1.0
    coef = _newton(A, b, x_full * mask, jnp.asarray(mask), inv_gamma,
                   NEWTON_SUPPORT)
    return np.asarray(coef), mask > 0


def fit(loss: str, A, b, gamma: float, kappa: int, G=None):
    if loss == "squared":
        return squared(A, b, gamma, kappa, G)
    if loss == "logistic":
        return logistic(A, b, gamma, kappa)
    raise ValueError(f"no reference for loss {loss!r}")
