"""The benchmark's own copy of the paper's synthetic data (arXiv:2405.16267,
section 4), made on the device from a seed.

* Design A (N, m, n) f32: standard-normal entries, each column scaled to
  unit l2 norm over all N * m rows, so every node shares one column
  scaling. Where A is sharded over nodes, each node's rows are drawn on
  that node's chip (partitionable threefry: the values do not depend on
  the sharding) and only the (n,) column norms cross chips.
* Response k: a planted x with kappa nonzeros at random positions, values
  g + sign(g) with g standard normal (bounded away from 0), and
  - squared loss: b = A x + noise * e, e standard normal;
  - logistic loss: b = sign(A x / std(A x)), 2% of the labels flipped.

Everything is keyed by (seed, stream, index) only, so the same seed gives
the same design and the same k-th response on one chip or across four.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

DESIGN, RESPONSE = 0, 1


def base_key(seed: int) -> jax.Array:
    """A key for any non-negative seed, 64-bit ones included."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def kappa(cfg: dict) -> int:
    """Nonzeros of the planted model: round(n (1 - s_l)), at least 1."""
    return max(1, round(cfg["n_features"] * (1.0 - cfg["sparsity_level"])))


@partial(jax.jit, static_argnames=("shape", "sharding"))
def _design(key, shape, sharding):
    A = jax.random.normal(key, shape, jnp.float32)
    if sharding is not None:
        A = jax.lax.with_sharding_constraint(A, sharding)
    norm = jnp.sqrt(jnp.sum(A * A, axis=(0, 1)))
    return A / norm


def make_design(seed: int, cfg: dict, sharding=None) -> jax.Array:
    """A (N, m, n) f32 with unit-norm global columns, rows sharded as
    ``sharding`` says (None: the default device)."""
    shape = (cfg["n_nodes"], cfg["m_per_node"], cfg["n_features"])
    key = jax.random.fold_in(base_key(seed), DESIGN)
    return _design(key, shape, sharding)


def _planted(key, n: int, k: int) -> jax.Array:
    kv, ks = jax.random.split(key)
    g = jax.random.normal(kv, (k,))
    idx = jax.random.permutation(ks, n)[:k]
    return jnp.zeros((n,), jnp.float32).at[idx].set(g + jnp.sign(g))


@partial(jax.jit, static_argnames=("loss", "k", "noise"))
def _response(A, key, loss: str, k: int, noise: float):
    kx, ke = jax.random.split(key)
    x = _planted(kx, A.shape[2], k)
    scores = jnp.einsum("imn,n->im", A, x,
                        precision=jax.lax.Precision.HIGHEST)
    if loss == "squared":
        b = scores + noise * jax.random.normal(ke, scores.shape)
    elif loss == "logistic":
        s = scores / jnp.std(scores)
        flip = jax.random.bernoulli(ke, 0.02, s.shape)
        b = jnp.where(flip, -jnp.sign(s), jnp.sign(s))
    else:
        raise ValueError(f"no response generator for loss {loss!r}")
    return b, x


def make_response(A: jax.Array, seed: int, index: int, cfg: dict
                  ) -> tuple[jax.Array, jax.Array]:
    """The ``index``-th response on design ``A``: (b (N, m), x (n,)).
    ``index`` is traced, so every response shares one compiled program."""
    key = jax.random.fold_in(
        jax.random.fold_in(base_key(seed), RESPONSE), index)
    return _response(A, key, cfg["loss"], kappa(cfg),
                     float(cfg.get("noise", 0.0)))
