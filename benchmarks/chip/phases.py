#!/usr/bin/env python3
"""The program's own phases in a profiler trace, and a run that measures
them for one cell.

``tracefile.load`` keeps the device ops and the harness's spans. A
:class:`ProgramTrace` is that ``tracefile.Trace`` plus, from the same
``.xplane.pb``:

* each device's program executions, the events of its ``XLA Modules``
  line, named as the jitted function is (``jit_repro_loop(8512...)`` ->
  ``repro_loop``);
* the program's host spans, whose names start with ``repro.``
  (``repro.runtime.SPANS``), in a list of their own.

The traced window, busy time and every metric that reads the harness's
spans read the same numbers from it as from the plain trace; only the
labels of idle gaps change, since ``span_at`` takes the innermost span
over both lists. Each op belongs to the program execution on its device
that contains its start (``"none"`` where none does).

As a script, on a machine with one chip, from the root of a checkout:

    python3 benchmarks/chip/phases.py --workload <cell> --seed <n>

sets the cell up as the harness does, fits one pass of its traffic with
the profiler off and one with it on, and prints as its last line a JSON
object: the seconds per fit in each pass, each fit's outer iterations
and counters, each program's share of the traced pass's busy time, the
longest idle gaps by open span, and the per-layer readers
``polish_busy_frac``, ``loop_a_passes_per_iter``, ``xupdate_cg_steps``
and ``polish_cg_steps`` (``metrics/``) over the traced pass.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import dataclasses
import functools
import glob
import json
import os
import re
import shutil
import sys
import tempfile
import time

import harness
import tracefile

PREFIX = "repro."
MODULE_LINES = ("XLA Modules",)
# "jit_repro_loop(8512033012417113097)" -> "repro_loop"
MODULE_NAME = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")
LOOP, POLISH = "repro_loop", "repro_polish"
READERS = ("polish_busy_frac", "loop_a_passes_per_iter", "xupdate_cg_steps",
           "polish_cg_steps", "a_stream_busy_frac", "device_idle_frac")


@dataclasses.dataclass(frozen=True)
class Module:
    """One execution of a device program."""
    device: int
    name: str
    start_ns: float
    end_ns: float


def module_name(event_name: str) -> str:
    return MODULE_NAME.match(event_name).group(1)


@dataclasses.dataclass
class ProgramTrace(tracefile.Trace):
    modules: list[Module] = dataclasses.field(default_factory=list)
    program_spans: list[tracefile.Span] = dataclasses.field(
        default_factory=list)

    def span_at(self, t: float) -> str:
        open_ = [s for s in self.spans + self.program_spans
                 if s.start_ns <= t < s.end_ns]
        if not open_:
            return "none"
        return min(open_, key=lambda s: s.end_ns - s.start_ns).name

    @functools.cached_property
    def _by_device(self) -> dict[int, tuple[list[float], list[Module]]]:
        out: dict[int, tuple[list[float], list[Module]]] = {}
        for mod in sorted(self.modules, key=lambda m: m.start_ns):
            starts, mods = out.setdefault(mod.device, ([], []))
            starts.append(mod.start_ns)
            mods.append(mod)
        return out

    def module_of(self, op: tracefile.Op) -> str:
        """The program whose execution on the op's device contains the
        op's start; ``"none"`` where none does."""
        starts, mods = self._by_device.get(op.device, ([], []))
        i = bisect.bisect_right(starts, op.start_ns) - 1
        if i >= 0 and op.start_ns < mods[i].end_ns:
            return mods[i].name
        return "none"

    def has_module(self, name: str) -> bool:
        return any(m.name == name for m in self.modules)

    def module_busy_s(self, device: int, name: str) -> float:
        """Union of the intervals of the window's ops in program ``name``
        on ``device``, clipped to the window."""
        lo, hi = self.window
        return sum(b - a for a, b in tracefile.union(
            (max(o.start_ns, lo), min(o.end_ns, hi))
            for o in self.device_ops(device)
            if self.module_of(o) == name)) * 1e-9


def load(path: str) -> ProgramTrace:
    """Read an ``.xplane.pb`` written by ``jax.profiler``."""
    from jax.profiler import ProfileData
    base = tracefile.load(path)
    modules, program_spans = [], []
    for plane in ProfileData.from_file(path).planes:
        dm = tracefile.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dm and line.name in MODULE_LINES:
                dev = int(dm.group(2))
                modules += [Module(dev, module_name(e.name), e.start_ns,
                                   e.start_ns + e.duration_ns)
                            for e in line.events]
            elif plane.name.startswith("/host:"):
                program_spans += [
                    tracefile.Span(e.name, e.start_ns,
                                   e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith(PREFIX)]
    return ProgramTrace(base.ops, base.spans, modules, program_spans)


def module_split(tr: ProgramTrace, design_shape) -> dict:
    """Per program, mean over devices: executions in the window, busy
    seconds and their share of the device's busy time, and executions of
    ops that read the design A."""
    devs = tr.devices
    lo, hi = tr.window
    out: dict[str, dict] = {}
    for d in devs:
        busy = tr.busy_s(d)
        ops: dict[str, list[tracefile.Op]] = {}
        for o in tr.device_ops(d):
            ops.setdefault(tr.module_of(o), []).append(o)
        runs = collections.Counter(
            m.name for m in tr.modules
            if m.device == d and m.end_ns > lo and m.start_ns < hi)
        for name, mine in ops.items():
            secs = sum(b - a for a, b in tracefile.union(
                (max(o.start_ns, lo), min(o.end_ns, hi)) for o in mine))
            row = out.setdefault(name, dict.fromkeys(
                ("executions", "busy_s", "busy_frac", "a_ops"), 0.0))
            row["executions"] += runs[name] / len(devs)
            row["busy_s"] += secs * 1e-9 / len(devs)
            row["busy_frac"] += (secs * 1e-9 / busy if busy else 0.0) \
                / len(devs)
            row["a_ops"] += sum(1 for o in mine if tracefile.a_bytes(
                o, design_shape)) / len(devs)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]["busy_s"]))


def span_split(tr: ProgramTrace) -> dict:
    """Per program span name: how often it opened and its host seconds."""
    out: dict[str, dict] = {}
    for s in tr.program_spans:
        row = out.setdefault(s.name, {"count": 0, "host_s": 0.0})
        row["count"] += 1
        row["host_s"] += (s.end_ns - s.start_ns) * 1e-9
    return out


# --------------------------------------------------------------- the run --
@dataclasses.dataclass
class CountedFit(harness.FitRecord):
    counters: dict | None = None


class CountingSession(harness.Session):
    """``harness.Session`` whose fits also keep the result's counters."""

    def fit(self, k: int) -> CountedFit:
        rec = harness.one_fit(self.est, self.A, k, self.cfg)
        c = getattr(self.est.result_, "counters", None)
        return CountedFit(rec.k, rec.iters, rec.status, rec.coef,
                          rec.support, None if c is None else
                          {n: int(v) for n, v in c.items()})


def measure(cfg: dict, mix: dict, seed: int, *, devs=None, peaks=None,
            cache: bool = True, keep: str | None = None) -> dict:
    """One pass of the traffic with the profiler off, one with it on, and
    the traced pass reduced. ``devs`` and ``peaks`` skip the look for a
    chip (tests); ``keep`` is a directory to copy the trace into."""
    import jax

    import data
    import traffic
    if devs is None:
        devs = harness.devices_for(1)
    if peaks is None:
        peaks = harness.device_peaks(devs[0].device_kind)
    if cache:
        harness.enable_compile_cache()
    loop = traffic.loop(mix)
    A = data.make_design(cfg["data_seed"], cfg)
    ses = CountingSession(cfg, A, harness.make_estimator(cfg))
    loop.warm_up(ses, mix)

    t0 = time.perf_counter()
    plain = loop.window(ses, mix, seed, 0.0)
    plain_s = (time.perf_counter() - t0) / len(plain)

    tmp = tempfile.mkdtemp(prefix="phases_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            t0 = time.perf_counter()
            recs = loop.window(ses, mix, seed, 0.0)
            traced_s = (time.perf_counter() - t0) / len(recs)
        finally:
            jax.profiler.stop_trace()
        pb = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                       recursive=True)[0]
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(pb, os.path.join(keep, "phases.xplane.pb"))
        tr = load(pb)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    shape = tuple(int(s) for s in A.shape)
    view = harness.RunView(cfg, recs, traced_s * len(recs), tr, shape, peaks)
    return {
        "device": devs[0].device_kind,
        "fit_s": {"profiler_off": plain_s, "profiler_on": traced_s},
        "fits": [{"k": r.k, "iters": r.iters, "status": r.status,
                  "counters": r.counters} for r in recs],
        "metrics": {name: harness.load_reader(name).read(view)
                    for name in READERS},
        "modules": module_split(tr, shape),
        "spans": span_split(tr),
        "idle_gaps": harness.breakdown(tr)["idle_gaps"],
        "busy_s": tr.mean_busy_s(), "window_s": tr.window_s,
    }


def main(argv=None) -> int:
    import run               # puts the checkout's src on the path
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--keep", help="directory to copy the trace into")
    args = ap.parse_args(argv)
    _, _, cfg, mix = run.load_cell(args.workload)
    try:
        out = measure(cfg, mix, args.seed, keep=args.keep)
    except harness.NoChip as e:
        print(f"phases.py: {e}; nothing was run", file=sys.stderr)
        return 2
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    # the readers import this file as ``phases``: run that module's code
    import phases
    sys.exit(phases.main())
