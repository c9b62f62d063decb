"""Pallas kernels: tiled Gram matrix G = A^T A with f32 accumulation.

The Bi-cADMM setup cost is dominated by forming the per-feature-block Gram
matrices ``A_ij^T A_ij`` (once, cached across all outer iterations — DESIGN
§6.3). Two implementations:

* **TPU (Mosaic)** — ``gram`` / ``gram_xy``: A tiled into MXU-aligned
  (block_m x block_n) VMEM blocks, ``x_tile^T y_tile`` accumulated over the
  sample dimension in the innermost grid axis with one (block_n x block_n)
  f32 accumulator tile resident (grid iterations are sequential on TPU).
* **GPU (Triton)** — ``gram_gpu`` / ``gram_xy_gpu``: Triton programs run in
  parallel, so each program owns one output tile and contracts the sample
  dimension inside the kernel (``fori_loop`` + local f32 accumulator,
  single store) — no cross-program read-modify-write.

Dispatch goes through the ``repro.runtime`` registry (``repro.kernels.ops``);
``interpret=None`` resolves to the runtime debug flag, never an implicit
interpret-mode production path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import runtime
from .matvec import _accumulate, _f32_dot, _masked_tail

Array = jax.Array

_GPU_MIN = 16


def _gram_kernel(x_ref, y_ref, o_ref, *, last: int, tail: int):
    def dot(valid):
        x, y = x_ref[...], y_ref[...]
        if valid is not None:
            x, y = _masked_tail(x, 0, valid), _masked_tail(y, 0, valid)
        return _f32_dot(x.T, y)
    _accumulate(o_ref, pl.program_id(2), last, tail, dot)


def gram(a: Array, *, block_m: int = 512, block_n: int = 128,
         interpret: bool | None = None) -> Array:
    """G = a^T a, f32 (TPU/Mosaic). a (m, n); returns (n, n)."""
    return gram_xy(a, a, block_m=block_m, block_n=block_n,
                   interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "interpret"))
def _gram_xy(x: Array, y: Array, *, block_m: int, block_n: int,
             interpret: bool) -> Array:
    # x and y are read in place: cdiv grid, clipped edge tiles of G, and
    # the ragged tail of the sample (contraction) axis masked in-kernel.
    m, nx = x.shape
    my, ny = y.shape
    assert m == my
    bm = m if m <= block_m else block_m
    bnx = nx if nx <= block_n else block_n
    bny = ny if ny <= block_n else block_n
    nk = pl.cdiv(m, bm)
    out = pl.pallas_call(
        functools.partial(_gram_kernel, last=nk - 1, tail=m % bm),
        grid=(pl.cdiv(nx, bnx), pl.cdiv(ny, bny), nk),
        in_specs=[pl.BlockSpec((bm, bnx), lambda i, j, k: (k, i)),
                  pl.BlockSpec((bm, bny), lambda i, j, k: (k, j))],
        out_specs=pl.BlockSpec((bnx, bny), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nx, ny), jnp.float32),
        interpret=interpret,
    )(x, y)
    return out


def gram_xy(x: Array, y: Array, *, block_m: int = 512, block_n: int = 128,
            interpret: bool | None = None) -> Array:
    """x^T y with tiled accumulation (TPU/Mosaic). (m, nx), (m, ny) ->
    (nx, ny) f32."""
    return _gram_xy(x, y, block_m=block_m, block_n=block_n,
                    interpret=runtime.resolve_interpret(interpret))


# ------------------------------------------------------------ GPU (Triton) --

def _gram_kernel_gpu(x_ref, y_ref, o_ref, *, nsteps: int, bm: int):
    # x_ref (m_pad, bnx) and y_ref (m_pad, bny) windows: one G tile per
    # program, sample blocks contracted inside (parallel Triton programs
    # cannot revisit a shared accumulator tile).
    def body(k, acc):
        x_blk = x_ref[pl.ds(k * bm, bm), :]
        y_blk = y_ref[pl.ds(k * bm, bm), :]
        return acc + jnp.dot(x_blk.T, y_blk,
                             preferred_element_type=jnp.float32)

    acc = jnp.zeros(o_ref.shape, jnp.float32)
    o_ref[...] = jax.lax.fori_loop(0, nsteps, body, acc)


def gram_gpu(a: Array, *, block_m: int = 64, block_n: int = 64,
             interpret: bool | None = None) -> Array:
    """G = a^T a, f32 — GPU-portable (Triton-lowered) tiled Gram."""
    return gram_xy_gpu(a, a, block_m=block_m, block_n=block_n,
                       interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "interpret"))
def _gram_xy_gpu(x: Array, y: Array, *, block_m: int, block_n: int,
                 interpret: bool) -> Array:
    m, nx = x.shape
    my, ny = y.shape
    assert m == my
    bm = _gpu_block(m, block_m)
    bnx = _gpu_block(nx, block_n)
    bny = _gpu_block(ny, block_n)
    xp = _pad2(x, bm, bnx)
    yp = _pad2(y, bm, bny)
    mp = xp.shape[0]
    out = pl.pallas_call(
        functools.partial(_gram_kernel_gpu, nsteps=mp // bm, bm=bm),
        grid=(xp.shape[1] // bnx, yp.shape[1] // bny),
        in_specs=[pl.BlockSpec((mp, bnx), lambda i, j: (0, i)),
                  pl.BlockSpec((mp, bny), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((bnx, bny), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[1], yp.shape[1]),
                                       jnp.float32),
        interpret=interpret,
    )(xp, yp)
    return out[:nx, :ny]


def gram_xy_gpu(x: Array, y: Array, *, block_m: int = 64, block_n: int = 64,
                interpret: bool | None = None) -> Array:
    """x^T y, f32 — GPU-portable variant of :func:`gram_xy`."""
    return _gram_xy_gpu(x, y, block_m=block_m, block_n=block_n,
                        interpret=runtime.resolve_interpret(interpret))


def _rup(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def _pow2ge(v: int) -> int:
    p = 1
    while p < v:
        p *= 2
    return p


def _gpu_block(dim: int, cap: int) -> int:
    """Smallest power-of-two tile >= 16 covering ``dim``, capped at ``cap``."""
    b = _GPU_MIN
    while b < dim and b < cap:
        b *= 2
    return b


def _pad2(a: Array, bm: int, bn: int) -> Array:
    m, n = a.shape
    return jnp.pad(a, ((0, _rup(m, bm) - m), (0, _rup(n, bn) - n)))
