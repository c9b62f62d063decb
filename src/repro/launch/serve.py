"""Run the always-on fitting service on a synthetic demo workload.

This is the operator-facing entry point for ``repro.serve`` (runbook:
``docs/serving.md``): it starts a :class:`~repro.serve.FittingService`,
submits a mixed-signature stream of sparse-regression fit requests
(several feature widths, per-request kappa, returning clients), then
prints the metrics snapshot — request latencies, batch composition, warm
pool hit rate, compiled driver shapes. CPU-scale demo:

  PYTHONPATH=src python -m repro.launch.serve --requests 32 --clients 8

The open-loop latency *benchmark* (Poisson arrivals, committed p50/p99
rows) lives in ``benchmarks/serve_bench.py``; this driver is the smallest
real end-to-end run of the serving plane.

Set ``REPRO_FAULTS=1`` to run the same workload as a **fault drill**: a
NaN fault is injected into the batch driver's compiled loop
(``repro.faults``), and the run asserts the plane quarantined the
poisoned lane, recovered it through the escalation ladder, and returned
finite coefficients everywhere — the CI smoke for the fault-tolerant
solve plane.
"""
from __future__ import annotations

import argparse
import asyncio
import contextlib
import os

import numpy as np

import repro.api as api
from repro import faults, runtime


def make_request_data(rng, n: int, m: int, kappa: int):
    """One synthetic sparse-recovery problem (X (m, n), y (m,)) with an
    exactly ``kappa``-sparse planted signal (so a correctly-specified fit
    converges)."""
    X = rng.standard_normal((m, n)).astype(np.float32)
    w = np.zeros(n)
    idx = rng.choice(n, kappa, replace=False)
    w[idx] = rng.standard_normal(kappa) + np.sign(rng.standard_normal(kappa))
    y = (X @ w + 0.01 * rng.standard_normal(m)).astype(np.float32)
    return X, y


async def run_demo(service, *, requests: int, clients: int, widths,
                   seed: int = 0) -> list:
    """Submit ``requests`` fits round-robin over ``clients`` returning
    client ids and the signature ``widths``; a second pass refits every
    client warm. Returns the resolved ServeResults."""
    rng = np.random.default_rng(seed)
    futures, last_data = [], {}
    for i in range(requests):
        n = widths[i % len(widths)]
        X, y = make_request_data(rng, n, m=2 * n, kappa=max(2, n // 4))
        cid = f"client-{i % clients}-n{n}"
        last_data[cid] = (X, y, n)
        futures.append(service.submit_fit(
            X, y, kappa=max(2, n // 4), client_id=cid))
    first = await asyncio.gather(*futures)
    # returning clients: same ids, slightly perturbed labels -> the warm
    # pool resumes near the previous solution instead of cold-starting
    refits = []
    for cid, (X, y, n) in last_data.items():
        y2 = y + 0.01 * rng.standard_normal(y.shape).astype(np.float32)
        refits.append(service.submit_fit(
            X, y2, kappa=max(2, n // 4), client_id=cid))
    second = await asyncio.gather(*refits)
    return list(first) + list(second)


def main(argv=None) -> None:
    """CLI entry: start the service, run the demo workload, print stats."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--widths", type=int, nargs="+", default=[12, 24],
                    help="feature counts -> distinct shape signatures")
    ap.add_argument("--kappa", type=int, default=4)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--max-wait-ms", type=float, default=10.0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI-sized run")
    args = ap.parse_args(argv)
    runtime.enable_compile_cache()
    if args.smoke:
        args.requests, args.clients, args.widths = 8, 4, [8, 12]

    drill = os.environ.get("REPRO_FAULTS", "") not in ("", "0")
    injection = (faults.inject(faults.nan_x(3, lane=0), limit=1)
                 if drill else contextlib.nullcontext())

    with injection:
        problem = api.SparseProblem(loss="squared", kappa=args.kappa,
                                    gamma=5.0)
        service = api.serve(
            problem, options=api.SolverOptions(max_iter=200, tol=1e-3),
            serve_options=api.ServeOptions(max_batch=args.max_batch,
                                           max_wait_s=args.max_wait_ms / 1e3))

        async def _run():
            async with service:
                return await run_demo(service, requests=args.requests,
                                      clients=args.clients,
                                      widths=args.widths)

        results = asyncio.run(_run())
    warm = sum(r.warm for r in results)
    snap = service.snapshot()
    if drill:
        coefs_finite = all(
            bool(np.isfinite(np.asarray(r.result.coef)).all())
            for r in results)
        assert snap["diverged_lanes"] > 0, "fault drill: nothing diverged"
        assert snap["failed_lanes"] == 0, (
            f"fault drill: {snap['failed_lanes']} lanes unrecovered")
        assert coefs_finite, "fault drill: non-finite coefficients served"
        print(f"fault drill: {snap['diverged_lanes']} lanes quarantined, "
              f"{snap['recovered_lanes']} recovered in "
              f"{snap['lane_retries']} ladder attempts, 0 failed; "
              f"all served coefficients finite")
    lat = snap["latency_s"]
    print(f"served {len(results)} fits over {len(args.widths)} signatures: "
          f"{warm} warm-pool resumes, {snap['batches']} micro-batches, "
          f"{snap['compiled_shapes']} compiled shapes "
          f"({snap['driver_hits']} driver-cache hits)")
    print(f"latency p50 {lat['p50'] * 1e3:.1f} ms  "
          f"p99 {lat['p99'] * 1e3:.1f} ms  (includes first-compile cost; "
          f"see benchmarks/serve_bench.py for steady-state rows)")
    mean_iters = float(np.mean([int(r.result.iters) for r in results]))
    warm_iters = [int(r.result.iters) for r in results if r.warm]
    if warm_iters:
        print(f"iterations: {mean_iters:.0f} mean overall, "
              f"{float(np.mean(warm_iters)):.0f} mean on warm resumes")


if __name__ == "__main__":
    main()
