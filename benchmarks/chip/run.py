#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

from the root of a checkout, on a machine with the chips the cell asks
for. The cell, its configuration (``configs/<name>.json``), its traffic
mix (``traffic/<name>.json``, with the loop it names, ``loops/<name>.py``)
and its metrics' readers (``metrics/<name>.py``) are found by name from
``BENCHMARK.json``.

The last line of standard output is the JSON result; the numbers that
decide ``correct`` are also the last lines of standard error. Without a
TPU, or with fewer chips than the cell asks for, it exits with code 2 and
prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, HERE)


def load_cell(name: str):
    """(benchmark, cell, configuration, traffic mix) of cell ``name``."""
    import traffic
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    return bench, cell, cfg, traffic.load(cell["traffic"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell, cfg, mix = load_cell(args.workload)
    import harness
    try:
        out = harness.run(bench, cell, cfg, mix, args.seed, args.seconds,
                          bool(args.trace), T0)
    except harness.NoChip as e:
        print(f"run.py: {e}; nothing was run", file=sys.stderr)
        return 2
    harness.report(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
