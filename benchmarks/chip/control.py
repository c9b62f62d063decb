#!/usr/bin/env python3
"""Readings of the correctness check over many datasets, and of its
control.

    python3 benchmarks/chip/control.py --workload <cell> \
        --precision fp32|bf16 --seeds 1 2 3 ...

Each seed is a dataset: the cell's configuration with that ``data_seed``.
``fp32`` fits its response 0 through the cell's estimator, as the cell
runs it; the readings over a dozen datasets give each limit's lower
reading. ``bf16`` is the control: the plain reference put in the
program's place and computed one precision lower, on the design rounded
to bfloat16 (products accumulated in f32, as the program's own bf16
preset would; that preset does not compile on the TPU, see PERF.md). It
has to come out not correct. Both are compared with the f32 reference
exactly as a benchmark run is. One JSON line per seed, then a summary.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))
sys.path.insert(0, HERE)


def readings(cfg: dict, seed: int, precision: str, mesh=None) -> dict:
    """One dataset's readings, with the number-by-number verdict; over
    ``mesh`` where the configuration states one, as the cell runs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import data
    import harness
    import reference
    cfg = dict(cfg, data_seed=seed)
    A = harness.make_design(cfg, mesh)
    if precision == "fp32":
        est = harness.make_estimator(cfg, mesh)
        rec = harness.one_fit(est, A, 0, cfg)
        del est
        gc.collect()
    else:
        b, _ = data.make_response(A, seed, 0, cfg)
        low = A.astype(jnp.bfloat16).astype(jnp.float32)
        coef, support = reference.fit(cfg["loss"], low, b,
                                      cfg["solver"]["gamma"],
                                      data.kappa(cfg))
        del low
        rec = harness.FitRecord(0, 0, 0, np.asarray(coef), support)
    r = harness.compare(cfg, A, [rec])
    r["not_converged"] = int(not rec.ok)
    r["window_compiles"] = 0
    chk = harness.checks(cfg, r)
    del A
    jax.clear_caches()
    return {"seed": seed, "precision": precision, "iters": rec.iters,
            "status": rec.status, "readings": r,
            "correct": all(c["value"] <= c["limit"] for c in chk.values())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--precision", choices=("fp32", "bf16"), required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import run
    import harness
    _, cell, cfg, _ = run.load_cell(args.workload)
    chips = int(cell["chips"])
    try:
        devs = harness.devices_for(chips)
    except harness.NoChip as e:
        print(f"control.py: {e}; nothing was run", file=sys.stderr)
        return 2
    mesh = harness.make_mesh(cfg, chips, devs)
    harness.enable_compile_cache()
    rows = []
    for seed in args.seeds:
        row = readings(cfg, seed, args.precision, mesh)
        print(json.dumps(row), flush=True)
        rows.append(row)
    keys = rows[0]["readings"]
    print(json.dumps({
        "workload": args.workload, "precision": args.precision,
        "seeds": args.seeds,
        "largest": {k: max(r["readings"][k] for r in rows) for k in keys},
        "smallest": {k: min(r["readings"][k] for r in rows) for k in keys},
        "all_correct": all(r["correct"] for r in rows),
        "none_correct": not any(r["correct"] for r in rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
