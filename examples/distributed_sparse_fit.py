"""Distributed sparse fitting through the estimator API with
``engine="auto"``: hand the estimator a device mesh and it negotiates the
shard_map engine (falling back to the single-process reference engine when
the mesh has no real parallelism or the data doesn't tile it — see
``repro.api.select_engine``).

The mesh is built from the devices present: (nodes=4, feat=1) on a
four-chip TPU host, (4, 2) on the CPU, where the script asks XLA for 8
emulated host devices unless ``XLA_FLAGS`` already sets a count:

    PYTHONPATH=src python examples/distributed_sparse_fit.py
"""
import math
import os

if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import jax
import numpy as np

from repro.api import SolverOptions, SparseLinearRegression
from repro.data.synthetic import SyntheticSpec, make_sparse_regression


def main():
    spec = SyntheticSpec(n_nodes=4, m_per_node=400, n_features=256,
                         sparsity_level=0.8)
    # the paper's N nodes over as many devices as tile them; the rest of
    # the devices split the features
    n_dev = len(jax.devices())
    nodes = math.gcd(n_dev, spec.n_nodes)
    mesh = jax.make_mesh((nodes, n_dev // nodes), ("nodes", "feat"))
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"({mesh.devices.size} {jax.devices()[0].platform} devices)")

    As, bs, x_true = make_sparse_regression(0, spec)

    # engine="auto": the mesh is available and the (N, m, n) data tiles it,
    # so the estimator negotiates the sharded engine; the SAME estimator
    # code runs single-process if you drop the mesh.
    opts = SolverOptions(engine="auto", mesh=mesh, max_iter=300,
                         inner_iters=10)
    model = SparseLinearRegression(spec.kappa, gamma=1000.0, options=opts)
    model.fit(As, bs)

    sup_true = np.abs(np.asarray(x_true)) > 0
    sup_hat = np.asarray(model.support_)
    f1 = 2 * (sup_hat & sup_true).sum() / (sup_hat.sum() + sup_true.sum())
    res = model.result_
    print(f"engine={model.engine_}  iters={model.n_iter_}  "
          f"R^2={model.score(As, bs):.4f}  support-F1={f1:.3f}  "
          f"p_r={float(res.p_r):.2e} b_r={float(res.b_r):.2e}")
    caps = model.capabilities_
    print(f"capabilities: gather_free={caps.gather_free}  "
          f"grid_strategy={caps.grid_strategy!r}  "
          f"penalty_grids={caps.penalty_grids}")
    print("collectives per outer iteration: one (m_i,) psum over 'feat' "
          "per inner step + one z-shard psum over 'nodes' + scalar ladders")


if __name__ == "__main__":
    main()
