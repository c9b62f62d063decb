"""Pallas kernels: tiled matrix-vector products for the matrix-free
x-update engines (normal-equation Hessian-vector products).

The (7a) prox of the squared loss reduces to solving
``(A^T A + c I) x = A^T b + rho_c q``; the Woodbury and PCG backends of
``repro.core.prox`` never materialize ``A^T A`` — their hot loop is the pair
of matvecs

    w = A p          (forward,  (m, n) @ (n, K))
    g = A^T w        (adjoint,  (n, m) @ (m, K))

plus an axpy. Two Pallas implementations live here:

* **TPU (Mosaic)** — ``matvec`` / ``rmatvec`` / ``normal_matvec``, two
  bodies chosen by the operand's shape. A is read in place in both —
  never padded or copied, so a solver loop around these kernels holds no
  second A in HBM: the grid covers A with cdiv blocks, edge tiles of the
  output are clipped on write, and the ragged edge of the contraction is
  masked in-kernel.

  - One column (``x`` of shape (n,) or (n, 1)): ``_gemv`` / ``_gemv_t``,
    a multiply-reduce in f32 on the VPU over ~4 MB tiles of A. Both
    vectors stay lane-dense in HBM, as (1, n) and (1, m) rows, and are
    turned between row and column in VMEM. A GEMV does two flops per
    element of A, so an MXU body would spend its passes on padding: the
    VPU keeps pace with HBM.
  - Several columns (K > 1: softmax's classes, the feature-split
    sub-solver's blocks): ``_matvec`` / ``_rmatvec``, an f32 MXU dot per
    MXU-aligned tile with the reduction axis innermost in the grid, so
    each f32 output tile stays resident across the whole sweep of the
    contracted dimension (grid iterations are sequential on TPU); the
    operand is zero-padded to whole blocks and 128 lanes.
* **GPU (Triton)** — ``matvec_gpu`` / ``rmatvec_gpu`` / ``normal_matvec_gpu``:
  Triton grid programs run in *parallel* with no cross-program memory
  ordering, so the TPU accumulation pattern would race. The GPU kernels
  grid over output tiles only and run the contraction *inside* each program
  (``fori_loop`` over contraction blocks, local f32 accumulator, single
  store) — deterministic, race-free, ``tl.dot``-shaped (every dot dim a
  power of two >= 16).

Production dispatch routes through the per-backend registry in
``repro.runtime`` (see ``repro.kernels.ops``); the plain-jnp CPU fallback
stays bit-identical to the historical contractions. ``interpret=None``
resolves via ``runtime.resolve_interpret`` — interpret-mode Pallas is a
debug/CI-parity tool, never an implicit production path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import runtime

Array = jax.Array

# f32 elements of VMEM we allow one kernel instance to hold across the A
# tile, the operand tile and the resident accumulator (~4 MB of the ~16 MB
# per-core budget, leaving room for double buffering).
_VMEM_ELEMS = 1 << 20
_LANE = 128
# A tile of the one-column bodies: ~4 MB, so the fixed cost of a grid
# step is a few percent of its DMA, and double-buffered it stays well
# inside v5e's 16 MB of scoped VMEM. Its rows come in multiples of 128
# (the vectors are turned by 128 x 128 transposes), at most
# _GEMV_MAX_ROWS so those turned blocks stay small beside the tile.
_GEMV_TILE_BYTES = 4 << 20
_GEMV_MAX_ROWS = 1024
# Triton's tl.dot needs every dot dimension >= 16, and tile extents must be
# powers of two (tl.arange constraint).
_GPU_MIN = 16


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


def _as_2d(x: Array) -> tuple[Array, bool]:
    return (x[:, None], True) if x.ndim == 1 else (x, False)


def _tpu_blocks(block_m: int, block_n: int, m: int, n: int,
                kp: int) -> tuple[int, int]:
    """Mosaic tile of A: rows a multiple of 8, lanes a multiple of 128,
    or the full extent of a dimension shorter than the requested block
    (both are legal block shapes). The row tile halves until A-tile +
    operand + accumulator tiles fit the VMEM budget."""
    bn = n if n <= block_n else block_n
    bm = block_m
    while bm > 8 and bm * bn + (bm + bn) * kp > _VMEM_ELEMS:
        bm = max(8, bm // 2)
    return (m if m <= bm else bm), bn


def _masked_tail(a: Array, axis: int, valid: int) -> Array:
    """Zero the entries of an A tile past ``valid`` along ``axis``. The
    last block of a ragged contraction reads past the edge of A, and what
    it reads there is unspecified (possibly NaN), so it is masked before
    it meets the zero-padded operand."""
    idx = jax.lax.broadcasted_iota(jnp.int32, a.shape, axis)
    return jnp.where(idx < valid, a, jnp.zeros_like(a))


def _f32_dot(a: Array, b: Array) -> Array:
    """a @ b accumulated in f32. f32 operands keep f32 products: the
    MXU's default precision would round them to bf16 first."""
    prec = runtime.F32_DOT if jnp.result_type(a, b) == jnp.float32 else None
    return jnp.dot(a, b, preferred_element_type=jnp.float32, precision=prec)


def _accumulate(o_ref, k, last: int, tail: int, dot) -> None:
    """``o_ref += dot(mask)`` for contraction step ``k``: the first step
    zeroes the resident accumulator, and only the last step of a ragged
    contraction (``tail`` > 0 valid entries) pays for the mask."""
    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    if tail == 0:
        o_ref[...] += dot(None)
        return

    @pl.when(k != last)
    def _body():
        o_ref[...] += dot(None)

    @pl.when(k == last)
    def _tail():
        o_ref[...] += dot(tail)


# ------------------------------------------------------------ TPU (Mosaic) --

def _one_column(v: Array) -> bool:
    return v.ndim == 1 or v.shape[1] == 1


def _gemv_blocks(m: int, n: int, itemsize: int) -> tuple[int, int]:
    """A tile of the one-column bodies: whole rows (lanes rounded up to
    128) where 128 of them fit the tile, and as many rows as fit, in
    multiples of 128. Tiles may reach past A's edge; the kernels mask or
    clip what lies there."""
    bn = min(_rup(n, _LANE), _GEMV_TILE_BYTES // (_LANE * itemsize))
    bm = _GEMV_TILE_BYTES // (bn * itemsize) // _LANE * _LANE
    return min(bm, _GEMV_MAX_ROWS, _rup(m, _LANE)), bn


def _strip_rows(dtype) -> int:
    """Rows of A one VPU step reads: a whole (8, 128) f32 register, or a
    packed (16, 128) one of 16-bit data."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def _tree_sum(terms: list[Array]) -> Array:
    """Pairwise sum: a short chain of dependent adds, and less rounding
    than a running sum."""
    while len(terms) > 1:
        pairs = [terms[i] + terms[i + 1] for i in range(0, len(terms) - 1, 2)]
        terms = pairs + terms[2 * len(pairs):]
    return terms[0]


def _sweep_by_step(k, last: int, full: int, tail: int, sweep) -> None:
    """``sweep(valid)`` over contraction step ``k``: every step sees
    ``full`` valid entries but the last of a ragged contraction, which
    sees ``tail``. The count is static, so the tail's code is its own."""
    if tail == 0 or last == 0:
        sweep(tail or full)
        return
    pl.when(k != last)(lambda: sweep(full))
    pl.when(k == last)(lambda: sweep(tail))


def _gemv_kernel(a_ref, x_ref, o_ref, acc_ref, *, rows: int, last: int,
                 tail: int):
    """w = A x for one (bm, bn) tile on the VPU. Each strip of ``rows``
    rows multiplies its 128-lane chunks by x's (a row, broadcast over
    sublanes) and adds them into ``acc_ref`` (bm, 128), resident across
    the contraction; the last step turns it and sums its lanes into the
    lane-dense ``o_ref`` (1, bm)."""
    k = pl.program_id(1)
    bm, bn = a_ref.shape

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def sweep(valid):
        full, part = divmod(valid, _LANE)
        xs = [jnp.broadcast_to(
                  x_ref[:, pl.ds(c * _LANE, _LANE)].astype(jnp.float32),
                  (rows, _LANE))
              for c in range(full + bool(part))]
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANE), 1)

        def strip(r, carry):
            i = pl.multiple_of(r * rows, rows)
            terms = []
            for c, xc in enumerate(xs):
                t = a_ref[pl.ds(i, rows), pl.ds(c * _LANE, _LANE)]
                t = t.astype(jnp.float32) * xc
                if c == full:   # the chunk across A's edge
                    t = jnp.where(lane < part, t, 0.0)
                terms.append(t)
            acc_ref[pl.ds(i, rows), :] += _tree_sum(terms)
            return carry

        jax.lax.fori_loop(0, bm // rows, strip, 0)

    _sweep_by_step(k, last, bn, tail, sweep)

    @pl.when(k == last)
    def _out():
        o_ref[...] = jnp.sum(acc_ref[...].T, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gemv(a: Array, x: Array, *, interpret: bool) -> Array:
    m, n = a.shape
    bm, bn = _gemv_blocks(m, n, a.dtype.itemsize)
    nk = pl.cdiv(n, bn)
    out = pl.pallas_call(
        functools.partial(_gemv_kernel, rows=_strip_rows(a.dtype),
                          last=nk - 1, tail=n % bn),
        grid=(pl.cdiv(m, bm), nk),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
                  pl.BlockSpec((1, bn), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((1, bm), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, m), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, _LANE), jnp.float32)],
        name="gemv",
        interpret=interpret,
    )(a, x.reshape(1, n))
    return out.reshape((m,) + x.shape[1:])


def _gemv_t_kernel(a_ref, y_ref, o_ref, acc_ref, part_ref, ycol_ref, *,
                   rows: int, last: int, tail: int):
    """g = A^T y for one (bm, bn) tile on the VPU. y's slice, a row, is
    turned into a column in ``ycol_ref`` (bm, 128) (row r holds y[r] in
    every lane); each strip of ``rows`` rows of A, scaled by it, is added
    into the tile's ``part_ref`` (rows, bn), and that into ``acc_ref``,
    resident across the contraction (two levels of sums, not one long
    chain); the last step folds its sublanes into the lane-dense
    ``o_ref`` (1, bn)."""
    k = pl.program_id(1)
    bm, bn = a_ref.shape

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    part_ref[...] = jnp.zeros_like(part_ref)
    ycol_ref[...] = jnp.broadcast_to(y_ref[...].astype(jnp.float32),
                                     (_LANE, bm)).T

    def strip(i, valid_rows=None):
        yc = ycol_ref[pl.ds(i, rows), :]
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANE), 0)
        for c in range(bn // _LANE):
            cols = pl.ds(c * _LANE, _LANE)
            t = a_ref[pl.ds(i, rows), cols].astype(jnp.float32) * yc
            if valid_rows is not None:   # the strip across A's edge
                t = jnp.where(row < valid_rows, t, 0.0)
            part_ref[:, cols] += t

    def sweep(valid):
        full, part = divmod(valid, rows)

        def body(r, carry):
            strip(pl.multiple_of(r * rows, rows))
            return carry

        jax.lax.fori_loop(0, full, body, 0)
        if part:
            strip(full * rows, part)

    _sweep_by_step(k, last, bm, tail, sweep)
    acc_ref[...] += part_ref[...]

    @pl.when(k == last)
    def _out():
        o_ref[...] = jnp.sum(acc_ref[...], axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gemv_t(a: Array, y: Array, *, interpret: bool) -> Array:
    m, n = a.shape
    bm, bn = _gemv_blocks(m, n, a.dtype.itemsize)
    mk = pl.cdiv(m, bm)
    rows = _strip_rows(a.dtype)
    out = pl.pallas_call(
        functools.partial(_gemv_t_kernel, rows=rows, last=mk - 1,
                          tail=m % bm),
        grid=(pl.cdiv(n, bn), mk),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (j, i)),
                  pl.BlockSpec((1, bm), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((1, bn), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((rows, bn), jnp.float32),
                        pltpu.VMEM((rows, bn), jnp.float32),
                        pltpu.VMEM((bm, _LANE), jnp.float32)],
        name="gemv_t",
        interpret=interpret,
    )(a, y.reshape(1, m))
    return out.reshape((n,) + y.shape[1:])


def _mv_kernel(a_ref, x_ref, o_ref, *, last: int, tail: int):
    def dot(valid):
        a = a_ref[...]
        if valid is not None:
            a = _masked_tail(a, 1, valid)
        return _f32_dot(a, x_ref[...])
    _accumulate(o_ref, pl.program_id(1), last, tail, dot)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "interpret"))
def _matvec(a: Array, x: Array, *, block_m: int, block_n: int,
            interpret: bool) -> Array:
    m, n = a.shape
    k = x.shape[1]
    kp = _rup(k, _LANE)
    bm, bn = _tpu_blocks(block_m, block_n, m, n, kp)
    mi, nk = pl.cdiv(m, bm), pl.cdiv(n, bn)
    xp = _pad2(x, bn, kp)
    out = pl.pallas_call(
        functools.partial(_mv_kernel, last=nk - 1, tail=n % bn),
        grid=(mi, nk),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
                  pl.BlockSpec((bn, kp), lambda i, j: (j, 0))],
        out_specs=pl.BlockSpec((bm, kp), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, kp), jnp.float32),
        interpret=interpret,
    )(a, xp)
    return out[:, :k]


def matvec(a: Array, x: Array, *, block_m: int = 256, block_n: int = 512,
           interpret: bool | None = None) -> Array:
    """w = a @ x in f32 (TPU/Mosaic). a (m, n); x (n,) or (n, K). One
    column runs the VPU body, whose tile the shape sets; ``block_m`` /
    ``block_n`` are the MXU body's tile, for K > 1."""
    interpret = runtime.resolve_interpret(interpret)
    if _one_column(x):
        return _gemv(a, x, interpret=interpret)
    return _matvec(a, x, block_m=block_m, block_n=block_n,
                   interpret=interpret)


def _rmv_kernel(a_ref, y_ref, o_ref, *, last: int, tail: int):
    def dot(valid):
        a = a_ref[...]
        if valid is not None:
            a = _masked_tail(a, 0, valid)
        return _f32_dot(a.T, y_ref[...])
    _accumulate(o_ref, pl.program_id(1), last, tail, dot)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "interpret"))
def _rmatvec(a: Array, y: Array, *, block_m: int, block_n: int,
             interpret: bool) -> Array:
    m, n = a.shape
    k = y.shape[1]
    kp = _rup(k, _LANE)
    bm, bn = _tpu_blocks(block_m, block_n, m, n, kp)
    ni, mk = pl.cdiv(n, bn), pl.cdiv(m, bm)
    yp = _pad2(y, bm, kp)
    out = pl.pallas_call(
        functools.partial(_rmv_kernel, last=mk - 1, tail=m % bm),
        grid=(ni, mk),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (j, i)),
                  pl.BlockSpec((bm, kp), lambda i, j: (j, 0))],
        out_specs=pl.BlockSpec((bn, kp), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, kp), jnp.float32),
        interpret=interpret,
    )(a, yp)
    return out[:, :k]


def rmatvec(a: Array, y: Array, *, block_m: int = 256, block_n: int = 512,
            interpret: bool | None = None) -> Array:
    """g = a^T @ y in f32 (TPU/Mosaic). a (m, n); y (m,) or (m, K). One
    column runs the VPU body; ``block_m`` / ``block_n`` as in
    :func:`matvec`."""
    interpret = runtime.resolve_interpret(interpret)
    if _one_column(y):
        return _gemv_t(a, y, interpret=interpret)
    return _rmatvec(a, y, block_m=block_m, block_n=block_n,
                    interpret=interpret)


def normal_matvec(a: Array, p: Array, shift: Array | float, *,
                  block_m: int = 256, block_n: int = 512,
                  interpret: bool | None = None) -> Array:
    """Normal-equation Hessian-vector product (A^T A + diag(shift)) p.

    Two tiled passes over A (never A^T A): w = A p then A^T w, f32
    accumulation throughout, plus the shifted axpy. ``shift`` may be a
    scalar (the prox penalty c = sigma + rho_c, possibly traced) or a
    vector (the polish engine's masked ridge diagonal).
    """
    w = matvec(a, p, block_m=block_m, block_n=block_n, interpret=interpret)
    g = rmatvec(a, w.astype(a.dtype), block_m=block_m, block_n=block_n,
                interpret=interpret)
    return (g + shift * p.astype(jnp.float32)).astype(a.dtype)


# ------------------------------------------------------------ GPU (Triton) --

def _mv_kernel_gpu(a_ref, x_ref, o_ref, *, nsteps: int, bn: int):
    # a_ref (bm, n_pad) window, x_ref (n_pad, kp): contract inside the
    # program — parallel Triton programs cannot share an accumulator tile.
    def body(j, acc):
        a_blk = a_ref[:, pl.ds(j * bn, bn)]
        x_blk = x_ref[pl.ds(j * bn, bn), :]
        return acc + jnp.dot(a_blk, x_blk, preferred_element_type=jnp.float32)

    acc = jnp.zeros(o_ref.shape, jnp.float32)
    o_ref[...] = jax.lax.fori_loop(0, nsteps, body, acc)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "interpret"))
def _matvec_gpu(a: Array, x: Array, *, block_m: int, block_n: int,
                interpret: bool) -> Array:
    m, n = a.shape
    x2, was_1d = _as_2d(x)
    k = x2.shape[1]
    kp = max(_GPU_MIN, _pow2ge(k))
    bm = _gpu_block(m, block_m)
    bn = _gpu_block(n, block_n)
    ap = _pad2(a, bm, bn)
    xp = _pad2(x2, bn, kp)
    np_ = ap.shape[1]
    out = pl.pallas_call(
        functools.partial(_mv_kernel_gpu, nsteps=np_ // bn, bn=bn),
        grid=(ap.shape[0] // bm,),
        in_specs=[pl.BlockSpec((bm, np_), lambda i: (i, 0)),
                  pl.BlockSpec((np_, kp), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bm, kp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((ap.shape[0], kp), jnp.float32),
        interpret=interpret,
    )(ap, xp)
    out = out[:m, :k]
    return out[:, 0] if was_1d else out


def matvec_gpu(a: Array, x: Array, *, block_m: int = 64, block_n: int = 64,
               interpret: bool | None = None) -> Array:
    """w = a @ x in f32 — GPU-portable (Triton-lowered) tiled matvec."""
    return _matvec_gpu(a, x, block_m=block_m, block_n=block_n,
                       interpret=runtime.resolve_interpret(interpret))


def _rmv_kernel_gpu(a_ref, y_ref, o_ref, *, nsteps: int, bm: int):
    # a_ref (m_pad, bn) window, y_ref (m_pad, kp): one n-tile per program,
    # fori_loop over the sample blocks of the adjoint contraction.
    def body(j, acc):
        a_blk = a_ref[pl.ds(j * bm, bm), :]
        y_blk = y_ref[pl.ds(j * bm, bm), :]
        return acc + jnp.dot(a_blk.T, y_blk,
                             preferred_element_type=jnp.float32)

    acc = jnp.zeros(o_ref.shape, jnp.float32)
    o_ref[...] = jax.lax.fori_loop(0, nsteps, body, acc)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "interpret"))
def _rmatvec_gpu(a: Array, y: Array, *, block_m: int, block_n: int,
                 interpret: bool) -> Array:
    m, n = a.shape
    y2, was_1d = _as_2d(y)
    k = y2.shape[1]
    kp = max(_GPU_MIN, _pow2ge(k))
    bm = _gpu_block(m, block_m)
    bn = _gpu_block(n, block_n)
    ap = _pad2(a, bm, bn)
    yp = _pad2(y2, bm, kp)
    mp = ap.shape[0]
    out = pl.pallas_call(
        functools.partial(_rmv_kernel_gpu, nsteps=mp // bm, bm=bm),
        grid=(ap.shape[1] // bn,),
        in_specs=[pl.BlockSpec((mp, bn), lambda i: (0, i)),
                  pl.BlockSpec((mp, kp), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bn, kp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((ap.shape[1], kp), jnp.float32),
        interpret=interpret,
    )(ap, yp)
    out = out[:n, :k]
    return out[:, 0] if was_1d else out


def rmatvec_gpu(a: Array, y: Array, *, block_m: int = 64, block_n: int = 64,
                interpret: bool | None = None) -> Array:
    """g = a^T @ y in f32 — GPU-portable adjoint of :func:`matvec_gpu`."""
    return _rmatvec_gpu(a, y, block_m=block_m, block_n=block_n,
                        interpret=runtime.resolve_interpret(interpret))


def normal_matvec_gpu(a: Array, p: Array, shift: Array | float, *,
                      block_m: int = 64, block_n: int = 64,
                      interpret: bool | None = None) -> Array:
    """(A^T A + diag(shift)) p via two GPU-portable passes over A."""
    w = matvec_gpu(a, p, block_m=block_m, block_n=block_n,
                   interpret=interpret)
    g = rmatvec_gpu(a, w.astype(a.dtype), block_m=block_m, block_n=block_n,
                    interpret=interpret)
    return (g + shift * p.astype(jnp.float32)).astype(a.dtype)
