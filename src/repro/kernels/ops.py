"""Registry-dispatched public wrappers around the Pallas kernels.

These are the entry points the rest of the framework uses. Every
``*_auto`` dispatcher is one lookup in the ``repro.runtime`` per-backend
kernel registry — no backend string checks live here:

=================  =====================  =====================  ==========
kernel             tpu (Mosaic)           gpu (Triton)           default
=================  =====================  =====================  ==========
gram               tiled resident-tile    per-tile fori_loop     plain jnp
matvec / rmatvec   1 column: VPU GEMV;    per-tile fori_loop     plain jnp
                   K > 1: MXU tiles
normal_matvec      two tiled passes       two tiled passes       plain jnp
block_(r)matvec    vmapped kernel         vmapped kernel         einsum
ladder_stats       one-pass (2, B) tile   partial tiles + sum    plain jnp
flash_attention    compiled kernel        (not ported)           interpret
=================  =====================  =====================  ==========

On TPU the operand's shape picks the matvec body: a single column
(x (n,) or (n, 1)) runs the f32 multiply-reduce on the VPU over ~4 MB
tiles of A, with both vectors lane-dense; several columns (softmax's
classes, the feature-split sub-solver's (M, nb, K) blocks) run the f32
MXU dot per tile. ``normal_matvec`` and the block products inherit the
choice. See ``repro.kernels.matvec``.

The ``default`` column is the bit-identical historical CPU fallback (XLA's
CPU matmuls need no hand tiling); interpret-mode Pallas is reachable only
through an explicit ``interpret=True`` or the runtime debug flag — never
picked implicitly by production dispatch (the one exception is flash
attention on CPU, which has no jnp production fallback and is documented
as emulation for the LM zoo).

Reduced-precision data (bf16/fp16) composes with an optional ``out_dtype``:
pass e.g. ``out_dtype=jnp.float32`` to get f32-accumulated f32 outputs from
bf16 operands (the PrecisionPolicy plumbing in ``repro.core`` does this for
every factor/Gram/A^T b materialization).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout

from .. import runtime
from .bisect_proj import ladder_stats, ladder_stats_gpu
from .flash_attention import flash_attention_flat
from .gram import gram, gram_gpu, gram_xy, gram_xy_gpu
from .matvec import (matvec, matvec_gpu, normal_matvec, normal_matvec_gpu,
                     rmatvec, rmatvec_gpu)

Array = jax.Array

__all__ = ["gram", "gram_auto", "gram_gpu", "gram_xy", "gram_xy_gpu",
           "ladder_stats", "ladder_stats_auto", "ladder_stats_gpu",
           "flash_attention", "flash_attention_flat", "matvec",
           "matvec_auto", "matvec_gpu", "rmatvec", "rmatvec_auto",
           "rmatvec_gpu", "normal_matvec", "normal_matvec_auto",
           "normal_matvec_gpu", "block_matvec", "block_rmatvec",
           "kernel_operand"]


def _out(x: Array, like: Array, out_dtype) -> Array:
    return x.astype(out_dtype if out_dtype is not None else like.dtype)


def _matmul_jnp(a: Array, b: Array, out_dtype) -> Array:
    """``a @ b``, accumulating/emitting in ``out_dtype`` when it differs
    from the natural promotion (bit-identical to ``a @ b`` otherwise)."""
    if out_dtype is None or jnp.dtype(out_dtype) == jnp.result_type(a, b):
        return a @ b
    return jnp.matmul(a, b, preferred_element_type=jnp.dtype(out_dtype))


def _ladder_stats_jnp(az: Array, thetas: Array) -> Array:
    """Plain-jnp ladder statistics (the CPU production path)."""
    diff = az.astype(jnp.float32)[:, None] - \
        thetas.astype(jnp.float32)[None, :]
    return jnp.stack([jnp.sum(jnp.maximum(diff, 0.0), axis=0),
                      jnp.sum((diff > 0).astype(jnp.float32), axis=0)])


def _row_major(a: Array) -> Array:
    """``a`` in the row-major device layout the Mosaic kernels read.

    A TPU may store an array in another dimension order (for an
    (N, m, n) design with n not a multiple of 128, the one that pads its
    (8, 128) tiles least puts m minor). A kernel operand must be
    row-major, so every compiled fit would start by copying such an A
    into a row-major temp; relaying it out once, where the engine first
    sees the data, keeps that copy out of the fits."""
    fmt = getattr(a, "format", None)
    want = tuple(range(a.ndim))
    if (fmt is None or fmt.layout is None
            or tuple(fmt.layout.major_to_minor) == want):
        return a
    return jax.device_put(a, Format(Layout(major_to_minor=want),
                                    fmt.sharding))


def _flash_gpu(*_args, **_kw):
    raise NotImplementedError(
        "flash attention has no GPU Pallas port yet; use the attention "
        "layer's impl='chunked' or impl='full' on GPU")


# --- registry: one table per kernel, consulted by the *_auto dispatchers --
runtime.register_kernel(
    "gram", "tpu", lambda a, out_dtype=None: _out(gram(a), a, out_dtype))
runtime.register_kernel(
    "gram", "gpu", lambda a, out_dtype=None: _out(gram_gpu(a), a, out_dtype))
runtime.register_kernel(
    "gram", "default", lambda a, out_dtype=None: _matmul_jnp(a.T, a,
                                                             out_dtype))

runtime.register_kernel(
    "matvec", "tpu",
    lambda a, x, out_dtype=None: _out(matvec(a, x), a, out_dtype))
runtime.register_kernel(
    "matvec", "gpu",
    lambda a, x, out_dtype=None: _out(matvec_gpu(a, x), a, out_dtype))
runtime.register_kernel(
    "matvec", "default",
    lambda a, x, out_dtype=None: _matmul_jnp(a, x, out_dtype))

runtime.register_kernel(
    "rmatvec", "tpu",
    lambda a, y, out_dtype=None: _out(rmatvec(a, y), a, out_dtype))
runtime.register_kernel(
    "rmatvec", "gpu",
    lambda a, y, out_dtype=None: _out(rmatvec_gpu(a, y), a, out_dtype))
runtime.register_kernel(
    "rmatvec", "default",
    lambda a, y, out_dtype=None: _matmul_jnp(a.T, y, out_dtype))

runtime.register_kernel("normal_matvec", "tpu", normal_matvec)
runtime.register_kernel("normal_matvec", "gpu", normal_matvec_gpu)
runtime.register_kernel(
    "normal_matvec", "default",
    lambda a, p, shift: a.T @ (a @ p) + shift * p)

runtime.register_kernel(
    "block_matvec", "tpu",
    jax.vmap(lambda a, x: matvec(a, x).astype(a.dtype)))
runtime.register_kernel(
    "block_matvec", "gpu",
    jax.vmap(lambda a, x: matvec_gpu(a, x).astype(a.dtype)))
runtime.register_kernel(
    "block_matvec", "default",
    lambda a_blocks, x_blocks: jnp.einsum("jmn,jnk->jmk", a_blocks,
                                          x_blocks))

runtime.register_kernel(
    "block_rmatvec", "tpu",
    jax.vmap(lambda a, y: rmatvec(a, y).astype(a.dtype)))
runtime.register_kernel(
    "block_rmatvec", "gpu",
    jax.vmap(lambda a, y: rmatvec_gpu(a, y).astype(a.dtype)))
runtime.register_kernel(
    "block_rmatvec", "default",
    lambda a_blocks, y_blocks: jnp.einsum("jmn,jmk->jnk", a_blocks,
                                          y_blocks))

runtime.register_kernel("kernel_operand", "tpu", _row_major)
runtime.register_kernel("kernel_operand", "default", lambda a: a)

runtime.register_kernel("ladder_stats", "tpu", ladder_stats)
runtime.register_kernel("ladder_stats", "gpu", ladder_stats_gpu)
runtime.register_kernel("ladder_stats", "default", _ladder_stats_jnp)

runtime.register_kernel(
    "flash_attention", "tpu",
    functools.partial(flash_attention_flat, interpret=False))
runtime.register_kernel("flash_attention", "gpu", _flash_gpu)
# CPU: interpret-mode emulation, the documented exception — there is no
# plain-jnp flash production path and the LM zoo still has to run on CPU.
runtime.register_kernel(
    "flash_attention", "default",
    functools.partial(flash_attention_flat, interpret=True))


def gram_auto(a: Array, out_dtype=None) -> Array:
    """A^T A through the per-backend kernel registry.

    This is the Gram entry point the solver setup paths use
    (``repro.core.prox.ridge_setup`` / ``repro.core.sharded``): TPU/GPU run
    the tiled Pallas kernels with f32 accumulator tiles; the default entry
    is the historical ``a.T @ a`` (XLA's CPU matmul needs no hand tiling).
    ``out_dtype`` requests the output (and jnp accumulation) dtype — the
    mixed-precision path passes f32 so bf16/fp16 data still yields f32
    factors.
    """
    return runtime.kernel("gram")(a, out_dtype)


def matvec_auto(a: Array, x: Array, out_dtype=None) -> Array:
    """a @ x through the per-backend kernel registry. This is the matvec
    entry point of the matrix-free x-update engines (``repro.core.prox``):
    the Woodbury/PCG backends and ``newton_cg_prox`` route every A-product
    through it, so on TPU/GPU the whole (7a) hot path is tile-blocked with
    f32 accumulation while the default fallback stays bit-identical to the
    historical ``a @ x``."""
    return runtime.kernel("matvec")(a, x, out_dtype)


def rmatvec_auto(a: Array, y: Array, out_dtype=None) -> Array:
    """a^T @ y — the adjoint companion of :func:`matvec_auto`."""
    return runtime.kernel("rmatvec")(a, y, out_dtype)


def normal_matvec_auto(a: Array, p: Array, shift: Array | float) -> Array:
    """(A^T A + diag(shift)) p without materializing A^T A: the PCG
    backend's Hessian-vector product. ``shift`` may be a traced scalar
    (dynamic penalties on a hyperparameter path) or a vector (the polish
    engine's masked ridge)."""
    return runtime.kernel("normal_matvec")(a, p, shift)


def block_matvec(a_blocks: Array, x_blocks: Array) -> Array:
    """Batched forward matvec (M, m, nb) @ (M, nb, K) -> (M, m, K).

    The feature-split sub-solver's partial-prediction product. On TPU/GPU
    each block runs the tiled Pallas matvec; the default entry IS the
    historical einsum (same expression, so reference/sharded trajectories
    stay bit-identical on CPU test meshes)."""
    return runtime.kernel("block_matvec")(a_blocks, x_blocks)


def block_rmatvec(a_blocks: Array, y_blocks: Array) -> Array:
    """Batched adjoint matvec (M, m, nb)^T @ (M, m, K) -> (M, nb, K)."""
    return runtime.kernel("block_rmatvec")(a_blocks, y_blocks)


def kernel_operand(a: Array) -> Array:
    """``a`` laid out as the registry's kernels read it: row-major on TPU
    (relaid out once if the device stores it otherwise), unchanged
    elsewhere. Engines apply it to the design matrix on entry."""
    return runtime.kernel("kernel_operand")(a)


def ladder_stats_auto(az: Array, thetas: Array) -> Array:
    """Ladder statistics (2, B) through the per-backend kernel registry.

    az (n,) nonnegative; thetas (B,). Row 0 = sum_i max(az_i - theta_b, 0);
    row 1 = count(az_i > theta_b), f32. TPU evaluates the whole ladder in
    one resident-tile pass; GPU reduces per-program partial tiles; the
    default entry is the plain-jnp broadcast.
    """
    return runtime.kernel("ladder_stats")(az, thetas)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None) -> Array:
    """Model-layout wrapper: q (B, Sq, Hq, Dh), k/v (B, Sk, Hkv, Dh).

    With ``interpret=None`` the flat kernel is picked from the registry
    (compiled on TPU, interpret-mode emulation on CPU, unsupported on GPU);
    an explicit ``interpret=`` bypasses the registry for debugging.
    """
    B, Sq, Hq, Dh = q.shape
    _, Sk, Hkv, _ = k.shape
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, Dh)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, Dh)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, Dh)
    if interpret is None:
        out = runtime.kernel("flash_attention")(
            qf, kf, vf, causal=causal, block_q=block_q, block_k=block_k)
    else:
        out = flash_attention_flat(qf, kf, vf, causal=causal,
                                   block_q=block_q, block_k=block_k,
                                   interpret=interpret)
    return out.reshape(B, Hq, Sq, Dh).transpose(0, 2, 1, 3)
