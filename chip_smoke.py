#!/usr/bin/env python3
"""Bring-up check: the Bi-cADMM fit end to end on a TPU, at Table-1 size.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py                # one chip: phases a-d below
    python chip_smoke.py --four-chips   # the sharded fit on a 2x2 host

One chip, all in this one process, through the user entry points
(``repro.api`` estimators and ``repro.api.serve``):

  a) squared loss at the paper's largest Table-1 shape, A (4, 75000, 4000)
     f32: the matrix-free PCG x-update regime;
  b) squared loss, n = 2000 and m = 1e5: dense Cholesky + Pallas Gram;
  c) logistic loss at (4, 25000, 2000): the Newton-CG prox;
  d) a few dozen fit requests through ``api.serve`` over two shape
     signatures with returning clients (the vmapped fleet driver).

Each fit of a-c must end CONVERGED with the same support as a plain
reference of the same fit on the same chip: every registry kernel at its
``default`` (plain jnp) row, matmuls at f32 precision. The compiled fit
program must hold ``tpu_custom_call`` (the Mosaic kernels ran, not the
``default`` rows) and no copy or pad of A. Every served request must
resolve, CONVERGED. For information, each fit of a-c and its reference
run twice and print their iterations, residuals, ``fit_s`` (the warm
fit) and ``compile_s`` (what the cold fit took on top of it).

``--four-chips`` runs only the Table-1 squared fit through
``engine="sharded"`` on a (nodes=4, feat=1) mesh, each node's rows on its
own chip, and the one-chip fit of the same data to compare with.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``,
printed only when every phase passed. Where JAX finds no TPU the script
exits with code 2 and prints no result. Data is synthetic, made from
``--seed``.
"""
from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import os
import re
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

TABLE1 = dict(n_nodes=4, m_per_node=75_000, n_features=4_000,
              sparsity_level=0.9)
# benchmarks/table1_compare.py: the solver settings of the Table-1 rows
TABLE1_SOLVER = dict(gamma=1000.0, rho_c=1.0)
TABLE1_OPTIONS = dict(max_iter=400, tol=1e-4, over_relax=1.6)
DENSE = dict(n_nodes=4, m_per_node=25_000, n_features=2_000,
             sparsity_level=0.9)
LOGISTIC = dict(n_nodes=4, m_per_node=25_000, n_features=2_000,
                sparsity_level=0.9)
SERVE = dict(requests=32, clients=8, widths=[256, 512])


def log(msg: str) -> None:
    print(msg, flush=True)


def timed_fit(est, X, y) -> tuple[float, float]:
    """Fit ``est`` twice; returns (compile_s, fit_s): fit_s is the second,
    warm fit, compile_s what the first, cold one took on top of it."""
    import jax
    walls = []
    for _ in range(2):
        t0 = time.perf_counter()
        est.fit(X, y)
        jax.block_until_ready((est.result_.coef, est.result_.support))
        walls.append(time.perf_counter() - t0)
    return walls[0] - walls[1], walls[1]


def describe(res) -> str:
    return (f"iters={int(res.iters)} status={status_name(res)} "
            f"p_r={float(res.p_r):.2e} d_r={float(res.d_r):.2e} "
            f"b_r={float(res.b_r):.2e}")


@contextlib.contextmanager
def plain_reference():
    """Every registry kernel at its ``default`` (plain jnp) row, with f32
    matmul precision: the CPU path of the suite, run on this chip."""
    import jax
    from repro import runtime
    with runtime.use_backend("default"), \
            jax.default_matmul_precision("highest"):
        yield


def fit_program_text(est, X, y) -> str:
    """HLO of the compiled reference-engine while-loop the fit ran."""
    solver = est._adapter_named("reference").solver
    As, bs = solver._cast(X, y.reshape(X.shape[0], X.shape[1]))
    factors, N, _, _ = solver._setup(As, bs)
    low = solver._run_while_donated.lower(
        factors, As, bs, solver._make_params(N), solver.init_state(As, bs))
    return low.compile().as_text()


def big_copies(hlo: str, n_elems: int) -> list[str]:
    """Instructions that copy or pad an array of at least ``n_elems``."""
    out = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* (copy|pad)\(",
                     line)
        if m:
            size = 1
            for d in m.group(1).split(","):
                size *= int(d) if d else 1
            if size >= n_elems:
                out.append(line.strip()[:160])
    return out


def status_name(res) -> str:
    from repro.core.results import SolveStatus
    return SolveStatus(int(res.status)).name


def check_fit(name, make, X, y, *, x_true=None) -> None:
    """Fit through the estimator, check the program, fit the plain
    reference, and hold the two to CONVERGED and equal supports."""
    import numpy as np
    log(f"[{name}] A {X.shape} {X.dtype}, device layout "
        f"{X.format.layout.major_to_minor}")
    est = make()
    c_s, f_s = timed_fit(est, X, y)
    r = est.result_
    log(f"[{name}] pallas:    engine={est.engine_} {describe(r)} "
        f"compile_s={c_s:.2f} fit_s={f_s:.2f}")
    hlo = fit_program_text(est, X, y)
    n_kernels = hlo.count("tpu_custom_call")
    copies = big_copies(hlo, X.size // 4)
    log(f"[{name}] fit program: {n_kernels} tpu_custom_call, "
        f"{len(copies)} A-sized copy/pad")
    del hlo
    with plain_reference():
        ref = make()
        rc_s, rf_s = timed_fit(ref, X, y)
    rr = ref.result_
    log(f"[{name}] reference: engine={ref.engine_} {describe(rr)} "
        f"compile_s={rc_s:.2f} fit_s={rf_s:.2f}")
    sup, rsup = np.asarray(r.support), np.asarray(rr.support)
    same = bool(np.array_equal(sup, rsup))
    msg = f"[{name}] support: {int(sup.sum())} nonzeros, equal={same}"
    if x_true is not None:
        hit = int((sup & (np.asarray(x_true) != 0)).sum())
        msg += f", {hit} of {int((np.asarray(x_true) != 0).sum())} planted"
    log(msg)
    assert n_kernels > 0, "the fit program holds no Mosaic kernel"
    assert not copies, f"the fit program copies or pads A: {copies}"
    assert status_name(r) == "CONVERGED", f"fit ended {status_name(r)}"
    assert status_name(rr) == "CONVERGED", \
        f"reference ended {status_name(rr)}"
    assert same, "supports differ from the plain reference"


def phase_table1(seed: int) -> None:
    """a) squared loss, A (4, 75000, 4000): the PCG regime."""
    from repro import api
    from repro.data import SyntheticSpec, make_sparse_regression
    spec = SyntheticSpec(**TABLE1)
    As, bs, x_true = make_sparse_regression(seed, spec)
    make = lambda: api.SparseLinearRegression(
        spec.kappa, **TABLE1_SOLVER,
        options=api.SolverOptions(**TABLE1_OPTIONS))
    check_fit("a squared 4x75000x4000", make, As, bs, x_true=x_true)


def phase_dense(seed: int) -> None:
    """b) squared loss, n = 2000, m = 1e5: dense Cholesky + Pallas Gram."""
    from repro import api
    from repro.data import SyntheticSpec, make_sparse_regression
    spec = SyntheticSpec(**DENSE)
    As, bs, x_true = make_sparse_regression(seed + 1, spec)
    make = lambda: api.SparseLinearRegression(
        spec.kappa, **TABLE1_SOLVER,
        options=api.SolverOptions(**TABLE1_OPTIONS))
    check_fit("b squared 4x25000x2000", make, As, bs, x_true=x_true)


def phase_logistic(seed: int) -> None:
    """c) logistic loss at (4, 25000, 2000): the Newton-CG prox."""
    from repro import api
    from repro.data import SyntheticSpec, make_sparse_classification
    spec = SyntheticSpec(**LOGISTIC)
    As, bs, x_true = make_sparse_classification(seed + 2, spec)
    make = lambda: api.SparseLogisticRegression(
        spec.kappa, gamma=50.0, rho_c=0.2,
        options=api.SolverOptions(max_iter=400, tol=3e-4, over_relax=1.6))
    check_fit("c logistic 4x25000x2000", make, As, bs, x_true=x_true)


def phase_serve(seed: int) -> None:
    """d) fit requests through api.serve: two signatures, returning
    clients, the vmapped fleet driver with kernels inside vmap."""
    import numpy as np
    from repro import api
    from repro.launch.serve import run_demo
    requests, clients, widths = (SERVE["requests"], SERVE["clients"],
                                 SERVE["widths"])
    # run_demo's requests: X (2n, n) standard normal, kappa = n // 4
    problem = api.SparseProblem(loss="squared", kappa=32, gamma=5.0,
                                rho_c=50.0)
    service = api.serve(problem,
                        options=api.SolverOptions(max_iter=400, tol=1e-3),
                        serve_options=api.ServeOptions(max_batch=16,
                                                       max_wait_s=0.05))

    async def run():
        async with service:
            return await run_demo(service, requests=requests,
                                  clients=clients, widths=widths, seed=seed)

    t0 = time.perf_counter()
    results = asyncio.run(run())
    wall = time.perf_counter() - t0
    snap = service.snapshot()
    statuses = [status_name(r.result) for r in results]
    finite = all(bool(np.isfinite(np.asarray(r.result.coef)).all())
                 for r in results)
    log(f"[d serve] {len(results)} requests resolved over {len(widths)} "
        f"signatures: {statuses.count('CONVERGED')} CONVERGED, "
        f"{sum(r.warm for r in results)} warm resumes, {snap['batches']} "
        f"batches, {snap['compiled_shapes']} compiled shapes, "
        f"wall_s={wall:.2f} (compiles included)")
    distinct = {f"client-{i % clients}-n{widths[i % len(widths)]}"
                for i in range(requests)}
    assert len(results) == requests + len(distinct), "a request was lost"
    assert finite, "non-finite coefficients served"
    assert statuses.count("CONVERGED") == len(results), \
        "a served fit did not converge"


def phase_four_chips(seed: int) -> None:
    """The Table-1 squared fit through engine="sharded" on a (4, 1) mesh,
    against the one-chip fit of the same data."""
    import jax
    import numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro import api
    from repro.data import SyntheticSpec, make_sparse_regression
    spec = SyntheticSpec(**TABLE1)
    As, bs, x_true = make_sparse_regression(seed, spec)
    mesh = jax.make_mesh((4, 1), ("nodes", "feat"),
                         axis_types=(AxisType.Auto,) * 2)
    rows = NamedSharding(mesh, P("nodes"))
    A4 = jax.make_array_from_callback(As.shape, rows, lambda i: As[i])
    b4 = jax.make_array_from_callback(bs.shape, rows, lambda i: bs[i])
    n_dev = len(A4.sharding.device_set)
    log(f"[4 chips] A {A4.shape} on {n_dev} devices, "
        f"{A4.addressable_shards[0].data.shape} per device")
    assert n_dev == 4, "A does not span four devices"
    sharded = api.SparseLinearRegression(
        spec.kappa, **TABLE1_SOLVER,
        options=api.SolverOptions(engine="sharded", mesh=mesh,
                                  **TABLE1_OPTIONS))
    c_s, f_s = timed_fit(sharded, A4, b4)
    r = sharded.result_
    log(f"[4 chips] sharded:  engine={sharded.engine_} {describe(r)} "
        f"compile_s={c_s:.2f} fit_s={f_s:.2f}")
    del A4, b4
    one = api.SparseLinearRegression(
        spec.kappa, **TABLE1_SOLVER,
        options=api.SolverOptions(**TABLE1_OPTIONS))
    oc_s, of_s = timed_fit(one, As, bs)
    ro = one.result_
    log(f"[4 chips] one chip: engine={one.engine_} {describe(ro)} "
        f"compile_s={oc_s:.2f} fit_s={of_s:.2f}")
    same = bool(np.array_equal(np.asarray(r.support), np.asarray(ro.support)))
    log(f"[4 chips] support: {int(np.asarray(r.support).sum())} nonzeros, "
        f"equal={same}")
    assert sharded.engine_ == "sharded", "the sharded engine did not run"
    assert status_name(r) == "CONVERGED", f"sharded ended {status_name(r)}"
    assert status_name(ro) == "CONVERGED", \
        f"one-chip fit ended {status_name(ro)}"
    assert same, "sharded support differs from the one-chip fit"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded Table-1 fit on four chips "
                         "and its one-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    from repro import runtime
    assert runtime.backend() == "tpu", runtime.backend()
    assert not runtime.interpret_default(), \
        "REPRO_PALLAS_INTERPRET is set: the kernels would be emulated"
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, JAX finds {len(devices)}",
              file=sys.stderr)
        return 2
    log(f"chip_smoke: {len(devices)} x {devices[0].device_kind}, "
        f"jax {jax.__version__}, compile cache "
        f"{runtime.enable_compile_cache()}")

    phases = ([phase_four_chips] if args.four_chips else
              [phase_table1, phase_dense, phase_logistic, phase_serve])
    failed = []
    for phase in phases:
        t0 = time.perf_counter()
        try:
            phase(args.seed)
        except Exception:
            traceback.print_exc()
            failed.append(phase.__name__)
        log(f"{phase.__name__}: {time.perf_counter() - t0:.1f} s"
            f"{' FAILED' if phase.__name__ in failed else ''}")
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
