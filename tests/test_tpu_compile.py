"""Compile the main-path kernels and whole fits for one TPU v5e chip.

Nothing runs: the TPU compiler that ships with jax compiles for a chip
that is described, not attached, so these tests catch what interpret mode
cannot — unaligned blocks, VMEM overruns, a program that does not fit the
chip's HBM, or A copied inside the solver loop — at real widths and the
paper's Table-1 size. The topology is described inside a module fixture
(only the worker that runs this file loads the TPU library); where it
cannot be described every test here skips.

Arguments carry the row-major layout device arrays have. Left free, the
compiler may pick another layout for A and then copy it to the one the
kernels read, which a real call never does.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro import runtime
from repro.core.bicadmm import BiCADMM, BiCADMMConfig
from repro.kernels.bisect_proj import ladder_stats
from repro.kernels.gram import gram_xy
from repro.kernels.matvec import matvec, normal_matvec, rmatvec

# v5e HBM as its compiler counts it ("Used ... of 15.75G hbm")
V5E_HBM = int(15.75 * 2**30)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's programs cannot be read back from the persistent
    # cache, so keep them out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(shape, dtype, sharding):
    from jax.experimental.layout import Format, Layout
    fmt = Format(Layout(major_to_minor=tuple(range(len(shape)))), sharding)
    return jax.ShapeDtypeStruct(shape, dtype, sharding=fmt)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _big_copies(hlo: str, n_elems: int) -> list[str]:
    """copy/pad instructions whose result has at least ``n_elems``."""
    out = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* (copy|pad)\(",
                     line)
        if m:
            size = 1
            for d in m.group(1).split(","):
                size *= int(d) if d else 1
            if size >= n_elems:
                out.append(line.strip()[:120])
    return out


def _assert_kernel_reads_a_in_place(compiled, a_elems: int) -> None:
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert not _big_copies(hlo, a_elems // 4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gram_xy_compiles(one_chip, dtype):
    a = _sds((25000, 2000), dtype, one_chip)
    c = _compile(lambda x: gram_xy(x, x, interpret=False), a)
    _assert_kernel_reads_a_in_place(c, 25000 * 2000)


@pytest.mark.parametrize("m,n", [(75000, 4000), (2000, 100000)])
@pytest.mark.parametrize("kernel", ["matvec", "rmatvec", "normal_matvec"])
def test_matvec_kernels_compile(one_chip, kernel, m, n):
    a = _sds((m, n), jnp.float32, one_chip)
    if kernel == "matvec":
        fn, v = (lambda a, v: matvec(a, v, interpret=False)), (n,)
    elif kernel == "rmatvec":
        fn, v = (lambda a, v: rmatvec(a, v, interpret=False)), (m,)
    else:
        fn, v = (lambda a, v: normal_matvec(a, v, 1.5, interpret=False)), (n,)
    c = _compile(fn, a, _sds(v, jnp.float32, one_chip))
    _assert_kernel_reads_a_in_place(c, m * n)


def test_ladder_stats_compiles(one_chip):
    c = _compile(lambda az, th: ladder_stats(az, th, interpret=False),
                 _sds((1_000_000,), jnp.float32, one_chip),
                 _sds((128,), jnp.float32, one_chip))
    assert "tpu_custom_call" in c.as_text()


def test_block_matvec_compiles(one_chip):
    fn = runtime.kernel("block_matvec", "tpu")
    c = _compile(fn, _sds((4, 25000, 512), jnp.float32, one_chip),
                 _sds((4, 512, 1), jnp.float32, one_chip))
    _assert_kernel_reads_a_in_place(c, 4 * 25000 * 512)


@pytest.mark.parametrize("loss,shape", [
    ("squared", (4, 75000, 4000)),     # Table 1, largest: the PCG regime
    ("squared", (4, 25000, 2000)),     # dense Cholesky + Pallas Gram
    ("logistic", (4, 25000, 2000)),    # Newton-CG prox
])
def test_reference_fit_compiles_within_hbm(one_chip, loss, shape):
    """The whole outer while-loop of the reference engine, as
    ``BiCADMM.run_from`` calls it, fits one chip and never pads or copies
    A: the kernels read it in place on every solver iteration."""
    N, m, n = shape
    with runtime.use_backend("tpu"):
        solver = BiCADMM(loss, BiCADMMConfig(kappa=n // 10, gamma=1000.0,
                                             max_iter=400, tol=1e-4))
        As = _sds(shape, jnp.float32, one_chip)
        bs = _sds((N, m), jnp.float32, one_chip)
        factors = jax.eval_shape(lambda A, b: solver._setup(A, b)[0], As, bs)
        st0 = jax.eval_shape(lambda A, b: solver._init_state(A, b, n, 1),
                             As, bs)
        place = lambda t: jax.tree.map(
            lambda s: _sds(s.shape, s.dtype, one_chip), t)
        c = solver._run_while_donated.lower(
            place(factors), As, bs, solver._make_params(N),
            place(st0)).compile()
    mem = c.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < V5E_HBM
    assert mem.temp_size_in_bytes < N * m * n * 4 // 4   # no A-sized temp
    _assert_kernel_reads_a_in_place(c, N * m * n)


def test_table1_polish_compiles_within_hbm(one_chip):
    """The debiasing fit on the recovered support (matrix-free PCG over
    the stacked (N*m, n) rows) reads the node stack in place too."""
    N, m, n = 4, 75000, 4000
    with runtime.use_backend("tpu"):
        solver = BiCADMM("squared", BiCADMMConfig(kappa=400, gamma=1000.0))
        c = solver._polish_jit.lower(
            _sds((N, m, n), jnp.float32, one_chip),
            _sds((N, m), jnp.float32, one_chip),
            _sds((n,), jnp.bool_, one_chip), _sds((n,), jnp.float32, one_chip),
            solver._make_params(N)).compile()
    mem = c.memory_analysis()
    assert mem.temp_size_in_bytes < N * m * n * 4 // 4
    _assert_kernel_reads_a_in_place(c, N * m * n)
