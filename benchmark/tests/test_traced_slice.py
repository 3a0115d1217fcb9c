"""Where the traced run's profiled slice starts, on a made-up clock with a
tracer whose profiler is counted and not started: at window barrier
``skip`` wherever the window can hold ``skip + count`` barriers at the
run's pace, earlier where it cannot, and nowhere when no barrier is left
to trace. Then what a traced run says when its trace holds nothing, and
what a checkpoint reader gives over a slice without a checkpoint."""

import copy
import json
import os

import pytest

from benchmark import program_spans, run, trace, window

HERE = os.path.dirname(os.path.abspath(__file__))
SKIP, COUNT = 10, 20                    # traffic/catchup.json


class Clock:
    def __init__(self):
        self.now = 100.0

    def __call__(self) -> float:
        return self.now


class StubTracer(run.Tracer):
    """The harness's tracer, with the profiler's start and stop counted."""

    def __init__(self, warmup_s=()):
        super().__init__("", SKIP, COUNT, warmup_s)
        self.starts = 0

    def start(self) -> None:
        self.starts += 1
        self.active = True

    def stop(self) -> None:
        self.active = False


def run_window(barrier_s, seconds=30.0):
    """Drives barriers whose times ``barrier_s(i)`` gives, the warm-up's
    last three at the first barrier's time."""
    clock = Clock()
    tracer = StubTracer([barrier_s(0)] * 3)
    done = []

    def barrier():
        clock.now += barrier_s(len(done))
        done.append(1)

    win = window.drive(barrier, seconds, max_barriers=10_000, clock=clock,
                       before=tracer.before, around=tracer.around)
    tracer.stop()
    return tracer, win


@pytest.mark.parametrize("pace", [0.15, 0.9])
def test_a_window_that_holds_the_slice_traces_barriers_10_to_29(pace):
    tracer, win = run_window(lambda i: pace)
    assert len(win["barrier_s"]) >= SKIP + COUNT
    assert tracer.traced == list(range(SKIP, SKIP + COUNT))
    assert tracer.slice() == {"first_traced": SKIP, "traced_barriers": COUNT}
    assert tracer.starts == 1 and not tracer.active


def test_three_seconds_a_barrier_starts_at_0_and_traces_all_ten():
    tracer, win = run_window(lambda i: 3.0)
    assert len(win["barrier_s"]) == 10
    assert tracer.traced == list(range(10))
    assert tracer.slice() == {"first_traced": 0, "traced_barriers": 10}
    assert tracer.starts == 1


def test_fifty_seconds_a_barrier_traces_the_one_that_ran():
    tracer, win = run_window(lambda i: 50.0)
    assert len(win["barrier_s"]) == 1
    assert tracer.traced == [0]
    assert tracer.slice() == {"first_traced": 0, "traced_barriers": 1}


def test_a_deadline_reached_at_skip_never_starts_the_profiler():
    # barriers 0-8 at the warm-up's pace, then one that outlasts the
    # window: the pace never says the slice cannot fit, and barrier 10,
    # where the slice would start, never runs
    tracer, win = run_window(lambda i: 100.0 if i == 9 else 0.1)
    assert len(win["barrier_s"]) == SKIP
    assert tracer.starts == 0 and tracer.traced == []
    assert tracer.slice() == {"first_traced": None, "traced_barriers": 0}


def test_max_barriers_before_skip_never_starts_the_profiler():
    clock = Clock()
    tracer = StubTracer([0.1] * 3)

    def barrier():
        clock.now += 0.1

    win = window.drive(barrier, 30.0, max_barriers=SKIP, clock=clock,
                       before=tracer.before, around=tracer.around)
    assert win["stopped_by"] == "max_barriers"
    assert tracer.starts == 0 and tracer.traced == []


def said(capsys) -> tuple:
    out, err = capsys.readouterr()
    lines = [json.loads(ln) for ln in out.split("\n") if ln.startswith("{")]
    return lines, err


def test_read_with_nothing_traced_says_so_and_gives_nothing(capsys):
    tracer = StubTracer()
    assert tracer.read([]) is None
    lines, err = said(capsys)
    assert lines == [{"trace": {"empty": "no barrier was traced",
                                "first_traced": None, "traced_barriers": 0}}]
    assert "no barrier was traced" in err


def test_read_of_a_trace_without_device_operations_names_it(monkeypatch,
                                                            capsys):
    tracer, _ = run_window(lambda i: 10.0)
    assert tracer.traced == [0, 1, 2]
    monkeypatch.setattr(trace, "find_xplane", lambda log_dir: "host.xplane")
    monkeypatch.setattr(trace, "extract", lambda xplane, names: {
        "devices": [{"ops": [], "programs": []}],
        "annotations": [["tick", 0, 10], ["tick", 10, 10], ["tick", 20, 10]]})
    assert tracer.read([False, False, True]) is None
    lines, err = said(capsys)
    why = "no device operation in 3 traced barriers"
    assert lines == [{"trace": {"empty": why, "first_traced": 0,
                                "traced_barriers": 3}}]
    assert why in err


def recorded(name: str) -> dict:
    with open(os.path.join(HERE, "data", f"spans_{name}_5barriers.json")) \
            as f:
        rec = json.load(f)
    rec["epoch_spans"] = {int(e): spans
                          for e, spans in rec["epoch_spans"].items()}
    return rec


@pytest.mark.parametrize("fixture,metric", [
    ("q5core_fused", "state_delta_ms"),
    ("q5core_exec", "state_delta_ms"),
    ("q8", "join_state_delta_ms"),
    ("q5core_exec_mesh4", "mesh_state_delta_ms"),
    ("q101", "ojoin_state_delta_ms"),
    ("q104", "ajoin_state_delta_ms"),
])
def test_a_checkpoint_reader_over_a_slice_without_a_checkpoint_gives_none(
        fixture, metric, monkeypatch, capsys):
    """A slow deployment's window of one plain barrier, traced: the
    checkpoint's span metric is left out, where the whole recording
    reads it."""
    rec = recorded(fixture)
    monkeypatch.setattr(program_spans, "load", lambda: rec["epoch_spans"])
    reader = run.load_by_name("layer_metrics", metric)
    whole = {"barriers": copy.deepcopy(rec["barriers"]), "traced": [0, 1]}
    assert reader.read(whole) is not None
    plain = next(b for b in rec["barriers"] if not b["ledger"]["checkpoint"])
    one = {"barriers": [copy.deepcopy(plain)], "traced": [0]}
    assert reader.read(one) is None
    capsys.readouterr()
