"""Record the small device trace that ``test_reduce.py`` reads.

Run on a machine with one TPU, from the root of a checkout:

    python benchmarks/chip/tests/record_trace.py --out <dir>

A (4, 1024, 640) f32 design, three outer iterations each of:

  * a squared-loss fit through the PCG x-update with the Mosaic kernels;
  * the same fit with every kernel at its plain-jnp (XLA dot) row;
  * a logistic fit (Newton-CG: matvec / rmatvec kernels);

each inside the harness's spans (make_response, fit, fetch_result), with
a 50 ms host-only pause inside ``fetch_result`` so the trace holds an idle
gap whose open span is known. Writes ``<dir>/small_trace.xplane.pb`` and
``<dir>/small_trace.json`` (the shapes the test checks against).
"""
from __future__ import annotations

import argparse
import contextlib
import glob
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "..", "..", "src"))

CFG = dict(n_nodes=4, m_per_node=1024, n_features=640, sparsity_level=0.9,
           noise=1e-2)
PAUSE_S = 0.05


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    import jax
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 2
    from repro import api, runtime
    import data

    A = data.make_design(7, CFG)
    opts = dict(max_iter=3, tol=1e-12, x_solver="pcg", cg_iters=20)
    runs = []
    for name, loss, backend in (("pallas", "squared", None),
                                ("xla", "squared", "default"),
                                ("logistic", "logistic", None)):
        cfg = dict(CFG, loss=loss)
        cls = (api.SparseLinearRegression if loss == "squared"
               else api.SparseLogisticRegression)
        o = dict(opts) if loss == "squared" else dict(max_iter=3, tol=1e-12)
        est = cls(data.kappa(cfg), gamma=1000.0, rho_c=1.0,
                  options=api.SolverOptions(**o))
        runs.append((name, cfg, est, backend))

    def one(cfg, est, backend, k):
        with (runtime.use_backend(backend) if backend
              else contextlib.nullcontext()):
            with jax.profiler.TraceAnnotation("make_response"):
                b, _ = data.make_response(A, 7, k, cfg)
            with jax.profiler.TraceAnnotation("fit"):
                est.fit(A, b)
            with jax.profiler.TraceAnnotation("fetch_result"):
                r = est.result_
                jax.block_until_ready((r.coef, r.support))
                np.asarray(r.coef)
                time.sleep(PAUSE_S)
        return int(r.iters)

    for name, cfg, est, backend in runs:          # compile outside the trace
        one(cfg, est, backend, 0)
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    iters = {name: one(cfg, est, backend, 1)
             for name, cfg, est, backend in runs}
    jax.profiler.stop_trace()
    pb = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    os.makedirs(args.out, exist_ok=True)
    shutil.copy(pb[0], os.path.join(args.out, "small_trace.xplane.pb"))
    with open(os.path.join(args.out, "small_trace.json"), "w") as f:
        json.dump({"config": CFG, "design_shape": list(A.shape),
                   "runs": [r[0] for r in runs], "iters": iters,
                   "pause_s": PAUSE_S,
                   "device_kind": jax.devices()[0].device_kind}, f, indent=1)
    shutil.rmtree(tmp)
    print(json.dumps({"ok": True, "iters": iters}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
