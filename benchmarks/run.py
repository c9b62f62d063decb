"""Benchmark harness entry point: one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # CPU-scaled sizes
    PYTHONPATH=src python -m benchmarks.run --full     # paper sizes
    PYTHONPATH=src python -m benchmarks.run --smoke    # CI-sized subset

``--smoke`` runs only the CI-sized benchmarks (projection + x-update
engines) without touching the committed result baselines.

Each line is ``name,us_per_call,derived``. The roofline section reads the
dry-run records (benchmarks/results/dryrun_all.json) if present.
"""
from __future__ import annotations

import argparse
import time

from repro import runtime

from . import (fig1_convergence, fig23_scaling, fig4_transfer, fleet_bench,
               gpu_bench, path_sweep, proj_bench, roofline, serve_bench,
               stream_bench, table1_compare, xupdate_bench)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (hours on CPU)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized subset (projection + x-update engines)")
    args = ap.parse_args()
    runtime.enable_compile_cache()

    t0 = time.time()
    if args.smoke:
        print("# Projection engine — sort vs bisect vs ladder-exact (smoke)")
        proj_bench.main(smoke=True)
        print("# x-update engine — dense vs woodbury vs pcg (smoke)")
        xupdate_bench.main(smoke=True)
        print("# Fleet fitting — vmapped driver vs solo-fit loop (smoke)")
        fleet_bench.main(smoke=True)
        print("# Fitting service — open-loop latency, cold vs warm (smoke)")
        serve_bench.main(smoke=True)
        print("# Streaming — partial_fit vs batch refit at T chunks (smoke)")
        stream_bench.main(smoke=True)
        print("# Backend x precision — proj/xupdate/path (smoke)")
        gpu_bench.main(smoke=True)
        print(f"# total {time.time() - t0:.1f}s")
        return
    print("# Fig 1 — residual convergence vs rho_b")
    fig1_convergence.main(full=args.full)
    print("# Table 1 — Bi-cADMM vs exact (B&B) vs Lasso (FISTA)")
    table1_compare.main(full=args.full)
    print("# Figs 2-3 — feature / sample scaling")
    fig23_scaling.main(full=args.full)
    print("# Fig 4 — transfer / wire-byte accounting")
    fig4_transfer.main(full=args.full)
    print("# Path sweep — warm-started kappa-path vs cold fits")
    path_sweep.main(full=args.full)
    print("# Projection engine — sort vs bisect vs ladder-exact")
    proj_bench.main(full=args.full)
    print("# x-update engine — dense vs woodbury vs pcg")
    xupdate_bench.main(full=args.full)
    print("# Fleet fitting — vmapped driver vs solo-fit loop")
    fleet_bench.main(full=args.full)
    print("# Fitting service — open-loop latency, cold vs warm")
    serve_bench.main(full=args.full)
    print("# Streaming — partial_fit vs batch refit at T chunks")
    stream_bench.main(full=args.full)
    print("# Backend x precision — proj/xupdate/path")
    gpu_bench.main(full=args.full)
    print("# Roofline — from dry-run records")
    roofline.main()
    print(f"# total {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
