"""The trace reduction and every per-layer reader, on hand-made events and
on a small trace recorded on a v5e (``data/small_trace.xplane.pb``, made
by ``record_trace.py``)."""
import json
import os

import pytest

import harness
import tracefile
from metrics import (a_stream_busy_frac, a_stream_roofline, collective_frac,
                     device_idle_frac, iter_ms, outer_iters)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PEAKS = {"hbm_bytes_per_s": 819e9}
SHAPE = (4, 1000, 256)
A_BYTES = 4 * 1000 * 256 * 4
NODE_BYTES = 1000 * 256 * 4


def op(start, dur, text, device=0):
    return tracefile.Op(device, float(start), float(dur), text)


def view(tr, fits=()):
    return harness.RunView({}, list(fits), 2.0, tr, SHAPE, PEAKS)


MOSAIC = ("%custom-call.3 = f32[4,1000,1]{2,1,0} custom-call("
          "f32[4,1000,256]{2,1,0:T(8,128)} %p0, f32[4,256,1]{2,1,0} %p1), "
          'custom_call_target="tpu_custom_call"')
FUSION_T = ("%fusion.7 = f32[256]{0} fusion(f32[256,1000]{0,1} %bitcast.2, "
            "f32[1000]{0} %p3), kind=kOutput, calls=%fused_computation.7")
VECTOR = "%add.1 = f32[256]{0} add(f32[256]{0} %a, f32[256]{0} %b)"


def test_busy_is_the_union_not_the_sum():
    tr = tracefile.Trace(
        [op(0, 100, VECTOR), op(50, 100, VECTOR), op(300, 100, VECTOR)],
        [tracefile.Span("fit", 0, 1000)])
    assert tr.busy_intervals(0) == [(0, 150), (300, 400)]
    assert tr.busy_s(0) == pytest.approx(250e-9)
    v = view(tr)
    assert device_idle_frac.read(v) == pytest.approx(1 - 250 / 1000)


def test_a_ops_found_by_shape_in_both_orders():
    assert tracefile.a_bytes(op(0, 1, MOSAIC), SHAPE) == A_BYTES
    assert tracefile.a_bytes(op(0, 1, FUSION_T), SHAPE) == NODE_BYTES
    assert tracefile.a_bytes(op(0, 1, VECTOR), SHAPE) == 0
    # the result shape, before the opcode, is not an operand
    own = "%copy.1 = f32[4,1000,256]{2,1,0} copy(f32[4]{0} %p)"
    assert tracefile.a_bytes(op(0, 1, own), SHAPE) == 0


def test_same_bytes_for_a_kernel_and_a_fusion_over_the_same_a():
    """A Mosaic custom call and an XLA fusion over A, at the same time,
    read the same share of the roofline; their names play no part."""
    fusion = ("%fusion.2 = f32[4,1000]{1,0} fusion(f32[4,1000,256]{2,1,0} "
              "%p0, f32[256]{0} %p1), kind=kLoop")
    for text in (MOSAIC, fusion):
        tr = tracefile.Trace([op(0, 1000, text)],
                             [tracefile.Span("fit", 0, 2000)])
        share = a_stream_roofline.read(view(tr))
        assert share == pytest.approx(100 * A_BYTES / 1e-6 / 819e9)


def test_idle_gaps_are_labelled_by_the_open_span():
    spans = [tracefile.Span("make_response", 0, 100),
             tracefile.Span("fit", 100, 900),
             tracefile.Span("fetch_result", 900, 1000)]
    tr = tracefile.Trace([op(10, 40, VECTOR), op(200, 600, MOSAIC),
                          op(920, 10, VECTOR)], spans)
    gaps = tr.idle_gaps(0)
    # gaps 0-10, 50-200 (midpoint in fit), 800-920, 930-1000
    assert [g[0] for g in gaps] == ["make_response", "fit", "fit",
                                    "fetch_result"]
    assert sum(g[1] for g in gaps) == pytest.approx((1000 - 650) * 1e-9)
    out = harness.breakdown(tr)
    assert out["idle_gaps"][0] == ["fit", pytest.approx(150e-9)]
    assert out["device_ops"][0] == ["%custom-call.3 custom-call "
                                    "f32[4,1000,1]", pytest.approx(600e-9)]


def test_busy_frac_counts_only_ops_that_read_a():
    tr = tracefile.Trace([op(0, 100, VECTOR), op(100, 100, MOSAIC),
                          op(150, 100, FUSION_T)],
                         [tracefile.Span("fit", 0, 1000)])
    assert a_stream_busy_frac.read(view(tr)) == pytest.approx(150 / 250)


def test_host_counter_readers():
    fits = [harness.FitRecord(0, 100, 0, None, None),
            harness.FitRecord(1, 300, 0, None, None)]
    v = view(None, fits=fits)
    assert outer_iters.read(v) == 200
    assert iter_ms.read(v) == pytest.approx(2.0 / 400 * 1e3)
    for reader in (a_stream_roofline, a_stream_busy_frac, device_idle_frac):
        assert reader.read(v) is None


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "small_trace.json")) as f:
        meta = json.load(f)
    tr = tracefile.load(os.path.join(DATA, "small_trace.xplane.pb"))
    return meta, tr


def test_recorded_trace_spans_and_busy_union(recorded):
    meta, tr = recorded
    assert tr.devices == [0]
    assert [s.name for s in tr.spans] == \
        ["make_response", "fit", "fetch_result"] * len(meta["runs"])
    ops = tr.device_ops(0)
    iv = tr.busy_intervals(0)
    assert all(b <= c for (_, b), (c, _) in zip(iv, iv[1:]))
    busy = sum(b - a for a, b in iv) * 1e-9
    assert busy == pytest.approx(tr.busy_s(0))
    assert busy <= sum(o.dur_ns for o in ops) * 1e-9
    assert 0 < tr.mean_busy_s() < tr.window_s


def test_recorded_a_ops_both_orders_and_both_kinds(recorded):
    """The recorded fits read A through Mosaic custom calls and through
    XLA fusions, and A appears as (N, m, n), as (N, n, m) and flattened;
    one read of the whole design counts the same bytes whichever op it
    is."""
    meta, tr = recorded
    shape = tuple(meta["design_shape"])
    N, m, n = shape
    whole = N * m * n * 4
    seen = {}
    for o in tr.ops:
        for dt, dims in tracefile.operand_shapes(o.text, hbm_only=True):
            if dims in tracefile.a_shapes(shape):
                kind = ("custom-call" if " custom-call(" in o.text
                        else "fusion" if " fusion(" in o.text else "other")
                seen.setdefault((kind, dims), tracefile.a_bytes(o, shape))
    assert seen[("custom-call", (N, m, n))] == whole
    assert seen[("fusion", (N, m, n))] == whole
    assert seen[("custom-call", (N * m, n))] == whole
    assert any(dims == (N, n, m) for _, dims in seen)


def test_recorded_idle_gaps_labelled(recorded):
    """The recorder pauses on the host for ``pause_s`` inside each
    ``fetch_result``: those are the longest idle gaps, so labelled."""
    meta, tr = recorded
    gaps = harness.breakdown(tr)["idle_gaps"]
    runs = len(meta["runs"])
    assert [g[0] for g in gaps[:runs]] == ["fetch_result"] * runs
    assert all(g[1] >= meta["pause_s"] for g in gaps[:runs])


def test_recorded_readers_stay_in_range(recorded):
    meta, tr = recorded
    v = harness.RunView({}, [], tr.window_s, tr, tuple(meta["design_shape"]),
                        {"hbm_bytes_per_s": 819e9})
    share = a_stream_roofline.read(v)
    assert 0 < share <= 105
    assert 0 < a_stream_busy_frac.read(v) <= 1
    assert 0 < device_idle_frac.read(v) < 1


def test_peaks_by_device_kind():
    assert harness.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.device_peaks("TPU v4")


def test_loops_are_not_ops():
    """A while loop's event spans its body's ops; it is neither busy time
    of its own nor an op that reads A."""
    loop = ("%while.2 = (f32[4,1000,256]{2,1,0}, s32[]) while("
            "(f32[4,1000,256]{2,1,0}, s32[]) %tuple.1), condition=%c, "
            "body=%b")
    assert tracefile.opcode(loop) == "while"
    assert tracefile.opcode(MOSAIC) == "custom-call"
    assert tracefile.a_bytes(op(0, 1, loop), SHAPE) == 0


ALL_REDUCE_START = ("%all-reduce-start.1 = f32[256]{0} all-reduce-start("
                    "f32[256]{0} %p0), replica_groups={{0,1,2,3}}, "
                    "to_apply=%add")
ALL_REDUCE_DONE = ("%all-reduce-done.1 = f32[256]{0} all-reduce-done("
                   "f32[256]{0} %all-reduce-start.1)")
ALL_REDUCE = ("%all-reduce.7 = f32[] all-reduce(f32[] %p1), "
              "replica_groups={{0,1,2,3}}, to_apply=%add")
# named like a collective, but a fusion and an add: not exchanges
NAMED_ONLY = ("%all-reduce.fusion.2 = f32[256]{0} fusion(f32[256]{0} %a), "
              "kind=kLoop, calls=%fused_computation.2")
ADD_NAMED = "%all-gather.9 = f32[256]{0} add(f32[256]{0} %a, f32[256]{0} %b)"


def test_collectives_found_by_opcode_not_name():
    for text in (ALL_REDUCE_START, ALL_REDUCE_DONE, ALL_REDUCE):
        assert tracefile.is_collective(op(0, 1, text)), text
    for text in (NAMED_ONLY, ADD_NAMED, VECTOR, MOSAIC):
        assert not tracefile.is_collective(op(0, 1, text)), text


def test_collective_frac_is_the_union_over_busy_then_the_mean():
    """Device 0: collectives over 0-150 (two overlapping halves, counted
    once) of 300 busy; device 1: 20 of 200 busy, beside a fusion and an
    add named like collectives that are not counted."""
    tr = tracefile.Trace(
        [op(0, 100, ALL_REDUCE_START), op(50, 100, ALL_REDUCE_DONE),
         op(150, 150, VECTOR),
         op(0, 20, ALL_REDUCE, device=1), op(20, 100, NAMED_ONLY, device=1),
         op(120, 80, ADD_NAMED, device=1)],
        [tracefile.Span("fit", 0, 1000)])
    assert collective_frac.read(view(tr)) == pytest.approx(
        (150 / 300 + 20 / 200) / 2)


def test_collective_frac_reads_nothing_without_collectives():
    tr = tracefile.Trace([op(0, 100, VECTOR), op(100, 100, MOSAIC)],
                         [tracefile.Span("fit", 0, 1000)])
    assert collective_frac.read(view(tr)) is None
    assert collective_frac.read(view(None)) is None


def test_a_shard_counts_only_over_several_chips():
    """One chip's shard of the nodes, (N / chips, m, n) or transposed, is
    A only when the cell runs over several chips: on one chip a
    (1, m, n) operand is no part of A's layout."""
    N, m, n = SHAPE
    shard = ("%fusion.4 = f32[1,1000]{1,0} fusion(f32[1,1000,256]{2,1,0} "
             "%p0, f32[256]{0} %p1), kind=kLoop")
    shard_t = ("%fusion.5 = f32[1,256]{1,0} fusion(f32[1,256,1000]{2,1,0} "
               "%p0, f32[1000]{0} %p1), kind=kLoop")
    assert (1, m, n) not in tracefile.a_shapes(SHAPE)
    assert {(1, m, n), (1, n, m)} <= tracefile.a_shapes(SHAPE, chips=4)
    for text in (shard, shard_t):
        assert tracefile.a_bytes(op(0, 1, text), SHAPE) == 0
        assert tracefile.a_bytes(op(0, 1, text), SHAPE, 4) == NODE_BYTES
    assert tracefile.a_bytes(op(0, 1, MOSAIC), SHAPE, 4) == A_BYTES
    tr = tracefile.Trace([op(0, 1000, shard, device=d) for d in range(4)],
                         [tracefile.Span("fit", 0, 2000)])
    four = harness.RunView({}, [], 2.0, tr, SHAPE, PEAKS, 4)
    assert a_stream_roofline.read(four) == pytest.approx(
        100 * NODE_BYTES / 1e-6 / 819e9)
    assert a_stream_roofline.read(view(tr)) is None


@pytest.mark.parametrize("name,roofline,busy", [
    ("small_trace", 48.99319270864169, 0.12620523389891192),
    ("program_trace", 48.9941994104884, 0.12616442090545196)])
def test_recorded_one_chip_readings_unchanged(name, roofline, busy):
    """The one-chip readers give, on the recorded traces, the values they
    gave before the readers learned of chips' shards."""
    with open(os.path.join(DATA, f"{name}.json")) as f:
        meta = json.load(f)
    tr = tracefile.load(os.path.join(DATA, f"{name}.xplane.pb"))
    v = harness.RunView({}, [], tr.window_s, tr, tuple(meta["design_shape"]),
                        {"hbm_bytes_per_s": 819e9})
    assert a_stream_roofline.read(v) == roofline
    assert a_stream_busy_frac.read(v) == busy
