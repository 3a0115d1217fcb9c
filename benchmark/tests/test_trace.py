"""The trace reduction: hand-counted on a made-up trace, and against an
independent sweep on a cut-down copy of a trace recorded on the chip."""

import gzip
import json
import os

import pytest

from benchmark import trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def recorded() -> dict:
    with gzip.open(os.path.join(DATA, "trace_q5core_3ticks.json.gz"),
                   "rt") as f:
        packed = json.load(f)
    names = packed["op_names"]
    for dev in packed["devices"]:
        dev["ops"] = [[names[i], s, d] for i, s, d in dev["ops"]]
    return packed


def test_union_merges_overlaps_and_nested():
    total, merged = trace.union_ns([[0, 10], [5, 12], [20, 30], [22, 25],
                                    [30, 31], [40, 40]])
    assert total == 12 + 11
    assert merged == [[0, 12], [20, 31]]


def test_reduce_hand_counted():
    # one device, window 0..1000 ns from two annotations; ops: a while
    # (100..400) with its body nested inside, one op that straddles the
    # window's end, one wholly outside it
    raw = {
        "devices": [{
            "plane": "/device:TPU:0",
            "ops": [["%while.1 = (u32[]) while(%t)", 100, 300],
                    ["%fusion.2 = u32[8] fusion(%a)", 150, 100],
                    ["%fusion.3 = u32[8] fusion(%a)", 600, 100],
                    ["%fusion.3 = u32[8] fusion(%a)", 950, 100],
                    ["%fusion.9 = u32[8] fusion(%a)", 2000, 50]],
            "programs": [["jit_epoch(123)", 100, 300],
                         ["jit_flush(77)", 600, 100],
                         ["jit_flush(77)", 950, 100]],
        }],
        "annotations": [["tick", 0, 500], ["tick.checkpoint", 500, 500]],
    }
    r = trace.reduce(raw)
    assert r["window_s"] == pytest.approx(1000e-9)
    # busy: 100..400, 600..700, 950..1000 (clipped) = 450 ns
    assert r["busy_s"] == pytest.approx(450e-9)
    assert r["program_s"] == pytest.approx(
        {"jit_epoch": 300e-9, "jit_flush": 150e-9})
    assert r["program_runs"] == {"jit_epoch": 1, "jit_flush": 2}
    ops = dict(r["device_ops"])
    assert ops["%while.1 while (u32[])"] == pytest.approx(300e-9)
    assert ops["%fusion.3 fusion u32[8]"] == pytest.approx(150e-9)
    assert "%fusion.9 fusion u32[8]" not in ops
    # idle: 0..100 (tick, before any program), 400..600 (after jit_epoch:
    # its middle, 500, is in the checkpoint tick), 700..950 (after flush)
    assert dict(r["idle_gaps"]) == pytest.approx({
        "tick after start": 100e-9,
        "tick.checkpoint after jit_epoch": 200e-9,
        "tick.checkpoint after jit_flush": 250e-9})
    idle = sum(s for _n, s in r["idle_gaps"])
    assert idle + r["busy_s"] == pytest.approx(r["window_s"])


def test_reduce_without_device_ops_is_nothing():
    assert trace.reduce({"devices": [], "annotations": []}) is None
    assert trace.reduce({"devices": [{"plane": "/device:TPU:0", "ops": [],
                                      "programs": []}],
                         "annotations": [["tick", 0, 10]]}) is None


def test_reduce_recorded_chip_trace():
    raw = recorded()
    r = trace.reduce(raw)
    notes = raw["annotations"]
    lo = notes[0][1]
    hi = notes[-1][1] + notes[-1][2]
    assert r["window_s"] == pytest.approx((hi - lo) / 1e9)
    # busy by an independent sweep over start/end points
    points = []
    for _n, s, d in raw["devices"][0]["ops"]:
        s, e = max(s, lo), min(s + d, hi)
        if e > s:
            points += [(s, 1), (e, -1)]
    points.sort()
    depth, busy, last = 0, 0, None
    for t, step in points:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    assert r["busy_s"] == pytest.approx(busy / 1e9, rel=1e-12)
    # three barriers: one fused epoch each, 190-200 ms of device time at
    # this table size, and the device idle for most of the checkpoint tick
    assert r["program_runs"]["jit_coscheduled_epoch"] == 3
    assert 0.5 < r["program_s"]["jit_coscheduled_epoch"] < 0.65
    assert 100 * (1 - r["busy_s"] / r["window_s"]) == pytest.approx(
        72.9, abs=0.5)
    label, seconds = r["idle_gaps"][0]
    assert label.startswith("tick.checkpoint after ")
    assert seconds > 1.0
    assert len(r["device_ops"]) == 10
    idle = sum(trace._idle_gaps(raw["devices"][0], notes, lo, hi).values())
    assert idle + r["busy_s"] == pytest.approx(r["window_s"])


def test_op_and_program_names():
    assert trace.program_name("jit_coscheduled_epoch(4878365113)") == \
        "jit_coscheduled_epoch"
    line = ("%while.187 = (u32[]{:T(128)}, u32[1,8388608]{1,0:T(1,128)}) "
            "while((u32[]{:T(128)}) %tuple.2), condition=%c, body=%b")
    assert trace.op_name(line).startswith("%while.187 while (u32[]")
    assert trace.op_name("not an HLO line") == "not an HLO line"
