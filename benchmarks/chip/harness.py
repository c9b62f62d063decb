"""One run of one cell: set-up, the measured window, the traced fit, the
check against the plain reference, and the result line.

Set-up (counted in ``setup_s``, from process start to window start):
TPU check, compile cache inside the checkout, the configuration's mesh
where it states one (a cell on more than one chip must), the design A
made on the device from its ``data_seed`` (over a mesh, each node's rows
on its own chip), the estimator, and the
traffic's warm-up fits on responses the window never uses (they compile
or load every program the window runs and lay A out once).

Window: the traffic's loop (``loops/<name>.py``, named by the mix) makes
its fits from ``--seed``; the window closes when the last of them has
returned, its coefficients and support ready and fetched. A compile
inside the window makes the run incorrect.

``--trace 1`` then traces what the loop names, whole, and reduces the
trace to the cell's per-layer metrics. Last, the program's state is freed
and every fit is compared with the plain reference (``reference.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import importlib
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
CACHE_DIR = os.path.join(HERE, ".jax_cache")
PEAKS = os.path.join(HERE, "peaks.json")
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class FitRecord:
    k: int
    iters: int
    status: int
    coef: np.ndarray
    support: np.ndarray

    @property
    def ok(self) -> bool:
        return self.status == 0 and bool(np.isfinite(self.coef).all())


@dataclasses.dataclass
class RunView:
    """What a per-layer metric's reader may read."""
    cfg: dict
    fits: list[FitRecord]
    window_s: float
    trace: object | None
    design_shape: tuple[int, int, int]
    peaks: dict
    chips: int = 1


class CompileCounter:
    """Counts programs lowered or compiled (or loaded from the persistent
    cache) while ``active``."""

    def __init__(self):
        import jax.monitoring
        self.active = False
        self.events: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event in COMPILE_EVENTS:
            self.events.append(f"{event.rsplit('/', 1)[-1]}:"
                               f"{kw.get('fun_name', '?')}")


def devices_for(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs


def enable_compile_cache(path: str = CACHE_DIR) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    with JAX's own thresholds: programs that compile in under a second
    stay out of it (see PERF.md on the relaid-out A)."""
    import jax
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)


def make_mesh(cfg: dict, chips: int, devs):
    """The configuration's ``"mesh": {"nodes": .., "feat": ..}`` over
    ``devs[:chips]``, with Auto axes as the sharded engine takes them;
    None for a configuration that states none. A cell whose chips differ
    from the mesh's size, or a cell on several chips with no mesh, is
    refused: its design would not be spread over the chips it holds."""
    spec = cfg.get("mesh")
    if spec is None:
        if chips != 1:
            raise ValueError(f"a cell on {chips} chips needs a configuration "
                             "that states its mesh")
        return None
    sizes = (int(spec["nodes"]), int(spec["feat"]))
    if sizes[0] * sizes[1] != chips:
        raise ValueError(f"the mesh {spec} spans {sizes[0] * sizes[1]} "
                         f"chips, the cell asks for {chips}")
    import jax
    from jax.sharding import AxisType
    return jax.make_mesh(sizes, ("nodes", "feat"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=devs[:chips])


def make_design(cfg: dict, mesh=None):
    """The configuration's design A on the device: over ``mesh``, each
    node's rows drawn on its own chip."""
    import data
    sharding = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        sharding = NamedSharding(mesh, P("nodes"))
    return data.make_design(cfg["data_seed"], cfg, sharding)


def make_estimator(cfg: dict, mesh=None):
    """The configuration's estimator, through the public API, in the
    default fp32 precision the configuration states; over ``mesh``
    where the configuration states one."""
    import data
    from repro import api
    cls = {"squared": api.SparseLinearRegression,
           "logistic": api.SparseLogisticRegression}[cfg["loss"]]
    extra = {} if mesh is None else {"mesh": mesh}
    return cls(data.kappa(cfg), **cfg["solver"],
               options=api.SolverOptions(**cfg["options"], **extra))


def one_fit(est, A, k: int, cfg: dict) -> FitRecord:
    """Fit response k of the configuration's dataset through the estimator
    inside the harness's spans."""
    import jax
    import data
    with jax.profiler.TraceAnnotation("make_response"):
        b, _ = data.make_response(A, cfg["data_seed"], k, cfg)
    with jax.profiler.TraceAnnotation("fit"):
        est.fit(A, b)
    with jax.profiler.TraceAnnotation("fetch_result"):
        r = est.result_
        jax.block_until_ready((r.coef, r.support))
        return FitRecord(k, int(r.iters), int(r.status),
                         np.asarray(r.coef, np.float32).reshape(-1),
                         np.asarray(r.support).reshape(-1))


@dataclasses.dataclass
class Session:
    """What a traffic loop drives: the configuration, its design A on the
    device and the estimator."""
    cfg: dict
    A: object
    est: object

    def fit(self, k: int) -> FitRecord:
        return one_fit(self.est, self.A, k, self.cfg)


def traced(ses: Session, loop, mix: dict, seed: int):
    """What the traffic's loop names for the trace, under the profiler:
    (records, trace.Trace)."""
    import jax
    import tracefile
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            recs = loop.traced(ses, mix, seed)
        finally:
            jax.profiler.stop_trace()
        pb = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                       recursive=True)
        return recs, tracefile.load(pb[0])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def compare(cfg: dict, A, fits: list[FitRecord]) -> dict:
    """Readings of every fit against the plain reference: the largest
    support mismatch and coefficient gap over the fits. The Gram is
    summed from each chip's rows, so A stays where it lies."""
    import data
    import reference
    G = reference.gram(A) if cfg["loss"] == "squared" else None
    mismatch, gap = 0, 0.0
    for rec in fits:
        b, _ = data.make_response(A, cfg["data_seed"], rec.k, cfg)
        coef, support = reference.fit(cfg["loss"], A, b,
                                      cfg["solver"]["gamma"],
                                      data.kappa(cfg), G)
        mismatch = max(mismatch, int(np.sum(rec.support != support)))
        scale = float(np.max(np.abs(coef)))
        g = float(np.max(np.abs(rec.coef - coef))) / scale
        gap = max(gap, g if np.isfinite(g) else float("inf"))
    return {"support_mismatch": mismatch, "coef_gap": gap}


def checks(cfg: dict, readings: dict) -> dict:
    """Each number compared beside its limit."""
    limits = cfg["limits"]
    return {name: {"value": readings[name], "limit": limits[name]}
            for name in limits}


def device_peaks(kind: str) -> dict:
    """The chip's peaks from ``peaks.json``; an unknown kind is an error."""
    with open(PEAKS) as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS}")
    return peaks[kind]


def peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def load_reader(name: str):
    return importlib.import_module(f"metrics.{name}")


def breakdown(tr) -> dict:
    """The device ops that took most time (seconds per device, summed over
    their occurrences) and the longest idle gaps by open span."""
    import tracefile
    devs = tr.devices
    per_op: dict[str, float] = {}
    for d in devs:
        for o in tr.device_ops(d):
            key = tracefile.op_label(o.text)
            per_op[key] = per_op.get(key, 0.0) + o.dur_ns * 1e-9
    ops = sorted(((k, v / len(devs)) for k, v in per_op.items()),
                 key=lambda kv: -kv[1])[:10]
    gaps = sorted((g for d in devs for g in tr.idle_gaps(d)),
                  key=lambda g: -g[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


def run(bench: dict, cell: dict, cfg: dict, mix: dict, seed: int,
        seconds: float, trace: bool, t0: float, *, devs=None,
        cache: bool = True) -> dict:
    """One run of ``cell``; returns the result object. ``devs`` skips the
    look for a chip (tests); ``t0`` is the process start on the host
    clock."""
    import jax
    import traffic
    chips = int(cell["chips"])
    if devs is None:
        devs = devices_for(chips)
    mesh = make_mesh(cfg, chips, devs)
    if cache:
        enable_compile_cache()
    devs = devs[:chips]
    loop = traffic.loop(mix)
    peaks = device_peaks(devs[0].device_kind) if trace else None

    A = make_design(cfg, mesh)
    ses = Session(cfg, A, make_estimator(cfg, mesh))
    loop.warm_up(ses, mix)
    counter = CompileCounter()

    counter.active = True
    t_start = time.perf_counter()
    setup_s = t_start - t0
    fits = loop.window(ses, mix, seed, seconds)
    window_s = time.perf_counter() - t_start
    counter.active = False

    peak = peak_bytes(devs)
    tr = None
    compared = list(fits)
    if trace:
        recs, tr = traced(ses, loop, mix, seed)
        compared += recs
    ses.est = None
    gc.collect()

    readings = compare(cfg, A, compared)
    readings["not_converged"] = sum(not f.ok for f in compared)
    readings["window_compiles"] = len(counter.events)
    chk = checks(cfg, readings)
    correct = all(c["value"] <= c["limit"] for c in chk.values())

    metrics = {}
    if trace:
        view = RunView(cfg, fits, window_s, tr,
                       tuple(int(s) for s in A.shape), peaks, chips)
        for m in bench["per_layer"]:
            if cell["name"] not in m.get("workloads", [cell["name"]]):
                continue
            v = load_reader(m["name"]).read(view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = {"fit_s": window_s / len(fits), "setup_s": setup_s,
                  "peak_hbm_gb": peak / 1e9}
        for m in bench["end_to_end"]:
            if cell["name"] in m.get("workloads", [cell["name"]]):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": len(compared),
           "failed": readings["not_converged"], "metrics": metrics,
           "device": device}
    if trace:
        device["busy_s"] = tr.mean_busy_s()
        device["window_s"] = tr.window_s
        out["breakdown"] = breakdown(tr)
    if counter.events:
        out["window_compile_events"] = counter.events[:20]
    out["checks"] = chk
    return out


def report(out: dict, stream_out=sys.stdout, stream_err=sys.stderr) -> None:
    """The readings as the last lines of standard error, then the result
    as the last line of standard output."""
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=stream_err)
    stream_err.flush()
    print(json.dumps(out), file=stream_out)
    stream_out.flush()
