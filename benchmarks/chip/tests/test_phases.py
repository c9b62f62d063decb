"""The program-phase reduction (``phases.py``) and the readers of the
program's spans, device programs and counters: on hand-made events, on
the small v5e trace of a program without them (``data/small_trace``), and
in a whole ``phases.measure`` run at a size the CPU holds."""
import json
import os

import jax
import pytest

import harness
import phases
import tracefile
import traffic
from metrics import (a_stream_busy_frac, a_stream_roofline, device_idle_frac,
                     loop_a_passes_per_iter, polish_busy_frac,
                     polish_cg_steps, xupdate_cg_steps)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PEAKS = {"hbm_bytes_per_s": 819e9}
SHAPE = (4, 1000, 256)
A_OP = ("%custom-call.3 = f32[4,1000,1]{2,1,0} custom-call("
        "f32[4,1000,256]{2,1,0} %p0, f32[4,256,1]{2,1,0} %p1), "
        'custom_call_target="tpu_custom_call"')
VECTOR = "%add.1 = f32[256]{0} add(f32[256]{0} %a, f32[256]{0} %b)"


def op(start, dur, text=VECTOR, device=0):
    return tracefile.Op(device, float(start), float(dur), text)


def mod(name, start, end, device=0):
    return phases.Module(device, name, float(start), float(end))


def fit(iters, counters=None):
    return phases.CountedFit(0, iters, 0, None, None, counters)


def view(tr, fits=()):
    return harness.RunView({}, list(fits), 2.0, tr, SHAPE, PEAKS)


def test_module_names_drop_jit_and_hash():
    assert phases.module_name("jit_repro_loop(8512033012417113097)") == \
        "repro_loop"
    assert phases.module_name("jit__lambda(5)") == "_lambda"
    assert phases.module_name("jit_convert_element_type(1)") == \
        "convert_element_type"
    assert phases.module_name("repro_polish") == "repro_polish"


def test_ops_belong_to_the_program_that_contains_them():
    """An op nested in a program execution on its device belongs to it;
    an op between executions, or on a device with none, to "none"."""
    tr = phases.ProgramTrace(
        [op(10, 20), op(250, 50), op(150, 10), op(400, 1),
         op(10, 20, device=1)],
        [tracefile.Span("fit", 0, 1000)],
        [mod("repro_polish", 200, 400), mod("repro_loop", 0, 100)])
    assert [tr.module_of(o) for o in tr.ops] == \
        ["repro_loop", "repro_polish", "none", "none", "none"]


def test_program_spans_label_gaps_and_leave_the_window():
    """Program spans relabel idle gaps with the innermost open span; the
    window, busy time and the harness-span metrics do not move."""
    ops = [op(0, 100), op(300, 100), op(700, 50), op(950, 50)]
    spans = [tracefile.Span("fit", 0, 1000)]
    prog = [tracefile.Span("repro.loop", 50, 500),
            tracefile.Span("repro.finalize", 600, 900),
            tracefile.Span("repro.polish", 640, 800)]
    plain = tracefile.Trace(ops, spans)
    tr = phases.ProgramTrace(ops, spans, [], prog)
    assert tr.window == plain.window
    assert tr.busy_s(0) == plain.busy_s(0)
    assert device_idle_frac.read(view(tr)) == \
        device_idle_frac.read(view(plain))
    # gaps 100-300 (loop), 400-700 (midpoint 550: fit), 750-950 (850:
    # finalize)
    assert [g[0] for g in plain.idle_gaps(0)] == ["fit"] * 3
    assert [g[0] for g in tr.idle_gaps(0)] == ["repro.loop", "fit",
                                               "repro.finalize"]
    # gap 650-790, midpoint 720: in the polish, inside the finalize
    tr2 = phases.ProgramTrace([op(0, 650), op(790, 10)], spans, [], prog)
    assert tr2.idle_gaps(0)[0][0] == "repro.polish"


def test_polish_busy_frac():
    tr = phases.ProgramTrace(
        [op(0, 100), op(50, 100), op(200, 50), op(220, 50)],
        [tracefile.Span("fit", 0, 1000)],
        [mod("repro_loop", 0, 160), mod("repro_polish", 190, 300)])
    # busy: 0-150 and 200-270 = 220; polish: 200-270 = 70
    assert polish_busy_frac.read(view(tr)) == pytest.approx(70 / 220)


def test_loop_a_passes_per_iter():
    """Executions of ops that read A inside the loop program, over the
    fits' outer iterations; A-reading ops in other programs, and other
    ops in the loop, do not count."""
    tr = phases.ProgramTrace(
        [op(10, 5, A_OP), op(20, 5, A_OP), op(30, 5, VECTOR),
         op(40, 5, A_OP), op(210, 5, A_OP)],
        [tracefile.Span("fit", 0, 1000)],
        [mod("repro_loop", 0, 100), mod("repro_polish", 200, 300)])
    assert loop_a_passes_per_iter.read(view(tr, [fit(2), fit(1)])) == 1.0
    assert loop_a_passes_per_iter.read(view(tr, [fit(0)])) is None


def test_counter_readers():
    fits = [fit(10, {"xupdate_cg_steps": 80, "polish_cg_steps": 12}),
            fit(5, {"xupdate_cg_steps": 60, "polish_cg_steps": 20})]
    v = view(None, fits)
    # 80 / (10 x 4 nodes) = 2, 60 / (5 x 4) = 3
    assert xupdate_cg_steps.read(v) == pytest.approx(2.5)
    assert polish_cg_steps.read(v) == pytest.approx(16)


def test_readers_find_nothing_in_a_run_without_the_program_parts():
    """The parent program's run: plain records with no counters, a trace
    without the program's device programs or spans."""
    plain = [harness.FitRecord(0, 100, 0, None, None)]
    unnamed = phases.ProgramTrace(
        [op(0, 10, A_OP)], [tracefile.Span("fit", 0, 100)],
        [mod("_lambda", 0, 50), mod("_polish", 60, 90)])
    for tr in (None, tracefile.Trace([op(0, 10, A_OP)],
                                     [tracefile.Span("fit", 0, 100)]),
               unnamed):
        for reader in (polish_busy_frac, loop_a_passes_per_iter,
                       xupdate_cg_steps, polish_cg_steps):
            assert reader.read(view(tr, plain)) is None
    assert xupdate_cg_steps.read(view(None, [])) is None
    assert polish_cg_steps.read(view(None, [])) is None


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "small_trace.json")) as f:
        meta = json.load(f)
    path = os.path.join(DATA, "small_trace.xplane.pb")
    return meta, tracefile.load(path), phases.load(path)


def test_recorded_metrics_unchanged_by_the_program_reduction(recorded):
    """The v5e trace read through ``phases.load``: the same window, busy
    time, per-layer values and op breakdown as through ``tracefile``."""
    meta, plain, tr = recorded
    assert tr.window == plain.window
    assert tr.ops == plain.ops and tr.spans == plain.spans
    shape = tuple(meta["design_shape"])
    for reader in (a_stream_roofline, a_stream_busy_frac, device_idle_frac):
        a = reader.read(harness.RunView({}, [], tr.window_s, plain, shape,
                                        PEAKS))
        b = reader.read(harness.RunView({}, [], tr.window_s, tr, shape,
                                        PEAKS))
        assert a == b and a is not None
    assert harness.breakdown(tr)["device_ops"] == \
        harness.breakdown(plain)["device_ops"]


def test_recorded_ops_all_lie_in_programs(recorded):
    """Every op of the recorded trace nests in a device program; this
    program predates the names, so the loop is ``_lambda`` and the polish
    ``_polish``, and the program readers find nothing."""
    meta, _, tr = recorded
    ops = tr.device_ops(0)
    assert ops and all(tr.module_of(o) != "none" for o in ops)
    names = {tr.module_of(o) for o in ops}
    assert {"_lambda", "_polish"} <= names
    assert not tr.program_spans
    v = harness.RunView({}, [fit(1)], tr.window_s, tr,
                        tuple(meta["design_shape"]), PEAKS)
    assert polish_busy_frac.read(v) is None
    assert loop_a_passes_per_iter.read(v) is None


def test_measure_runs_on_a_size_the_cpu_holds(tmp_path):
    """The whole script on the CPU: counters from every fit, the program
    spans on the host, and no device plane to read."""
    with open(os.path.join(DATA, "..", "..", "configs",
                           "table1_squared.json")) as f:
        cfg = json.load(f)
    cfg.update(m_per_node=512, n_features=64)
    cfg["options"] = dict(cfg["options"], x_solver="pcg")
    out = phases.measure(cfg, traffic.load("cold_fits"), 2 ** 31 + 5,
                         devs=jax.devices(), peaks=PEAKS, cache=False,
                         keep=str(tmp_path))
    assert len(out["fits"]) == 2
    assert all(f["counters"]["xupdate_cg_steps"] > 0 for f in out["fits"])
    assert out["metrics"]["xupdate_cg_steps"] > 0
    assert out["metrics"]["polish_cg_steps"] > 0
    assert set(out["spans"]) == {"repro.validate", "repro.layout",
                                 "repro.setup", "repro.loop",
                                 "repro.finalize", "repro.polish"}
    assert out["fit_s"]["profiler_off"] > 0
    assert os.path.exists(tmp_path / "phases.xplane.pb")


def test_readers_take_a_program_trace_from_the_script(monkeypatch):
    """Run as a script, ``phases.py`` is ``__main__``, a second copy of the
    module: a trace that copy builds is read by the readers all the
    same."""
    import importlib.util
    import sys
    spec = importlib.util.spec_from_file_location(
        "phases_as_script", phases.__file__)
    script = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, "phases_as_script", script)
    spec.loader.exec_module(script)
    tr = script.ProgramTrace(
        [op(10, 5, A_OP), op(210, 5, A_OP)],
        [tracefile.Span("fit", 0, 1000)],
        [script.Module(0, "repro_loop", 0, 100),
         script.Module(0, "repro_polish", 200, 300)])
    v = view(tr, [fit(1)])
    assert loop_a_passes_per_iter.read(v) == 1.0
    assert polish_busy_frac.read(v) == pytest.approx(0.5)


@pytest.fixture(scope="module")
def program():
    """The small v5e trace of the named program (``record_trace.py``): each
    run's fit alone, as a trace of its own."""
    with open(os.path.join(DATA, "program_trace.json")) as f:
        meta = json.load(f)
    tr = phases.load(os.path.join(DATA, "program_trace.xplane.pb"))
    fits = [s for s in tr.spans if s.name == "fit"]
    runs = {name: phases.ProgramTrace(tr.ops, [f], tr.modules,
                                      tr.program_spans)
            for name, f in zip(meta["runs"], fits)}
    return meta, tr, runs


def test_program_trace_spans_nest_in_each_fit(program):
    from repro import runtime
    meta, tr, runs = program
    for name, run in runs.items():
        (fit_span,) = run.spans
        inside = [s.name for s in tr.program_spans
                  if fit_span.start_ns <= s.start_ns
                  and s.end_ns <= fit_span.end_ns]
        assert list(dict.fromkeys(inside)) == list(runtime.SPANS), name
    assert len(tr.program_spans) == 7 * len(runs)    # repro.layout twice


def test_program_trace_split_by_program(program):
    """Every op lies in a device program; the loop and the polish are
    ``repro_loop`` and ``repro_polish``; the readers find them in each
    run. The Mosaic PCG reads A twice per CG step and twice for the
    initial residual, so its loop's passes are even."""
    meta, tr, runs = program
    shape = tuple(meta["design_shape"])
    assert all(tr.module_of(o) != "none" for o in tr.device_ops(0))
    for name, run in runs.items():
        v = harness.RunView({}, [fit(meta["iters"][name])], run.window_s,
                            run, shape, PEAKS)
        assert {phases.LOOP, phases.POLISH} <= \
            {run.module_of(o) for o in run.device_ops(0)}
        assert 0 < polish_busy_frac.read(v) < 1
        assert loop_a_passes_per_iter.read(v) > 0
        assert phases.module_split(run, shape)[phases.LOOP]["executions"] \
            == 1
    pallas = runs["pallas"]
    loop_ops = phases.module_split(pallas, shape)[phases.LOOP]["a_ops"]
    assert loop_ops % 2 == 0
    assert loop_ops >= 4 * meta["iters"]["pallas"]
