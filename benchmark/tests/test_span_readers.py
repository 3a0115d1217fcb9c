"""The six per-layer readers of the program's spans, on span lists
recorded from the program (five barriers, one of them a checkpoint, of
each configuration at its tiny sizes: ``data/spans_q5core_*_5barriers.json``).
The wanted values were taken from the files by other code than the
readers' (sums by name written out by hand), so the arithmetic is checked
and not repeated."""

import copy
import json
import os
import subprocess
import sys

import pytest

from benchmark import program_spans, run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

WANT = {
    "fused": {"fused_host_ms": 1.319238, "device_wait_ms": 1.109931,
              "state_delta_ms": 99.138715, "operator_busy_ms": 0.679373,
              "tick_unaccounted_ms": 0.276544},
    "exec": {"source_feed_span_ms": 5.220058, "device_wait_ms": 0.287885,
             "state_delta_ms": 2.258033, "operator_busy_ms": 2.678265,
             "tick_unaccounted_ms": 0.288869},
}
CELL = {"fused": "q5core_fused_catchup", "exec": "q5core_exec_catchup"}


def recorded(which: str) -> dict:
    with open(os.path.join(
            HERE, "data", f"spans_q5core_{which}_5barriers.json")) as f:
        rec = json.load(f)
    rec["epoch_spans"] = {int(e): spans
                          for e, spans in rec["epoch_spans"].items()}
    return rec


def ctx_of(rec: dict, traced=(0, 1, 2)) -> dict:
    return {"barriers": copy.deepcopy(rec["barriers"]),
            "traced": list(traced)}


def read(metric: str, ctx: dict):
    return run.load_by_name("layer_metrics", metric).read(ctx)


@pytest.mark.parametrize("which,metric", [
    (which, metric) for which, metrics in WANT.items() for metric in metrics])
def test_reader_arithmetic_on_recorded_spans(which, metric, monkeypatch):
    rec = recorded(which)
    monkeypatch.setattr(program_spans, "load", lambda: rec["epoch_spans"])
    assert read(metric, ctx_of(rec)) == pytest.approx(
        WANT[which][metric], abs=1e-6)


def test_span_sums_by_hand(monkeypatch):
    """Two barriers small enough to add up in the head."""
    def span(i, name, dur_ms, parent=None, wait=None, epoch=1):
        return {"name": name, "id": i, "parent": parent, "wait": wait,
                "epoch": epoch, "start_ns": i, "dur_ns": int(dur_ms * 1e6),
                "args": {}, "cat": "epoch"}

    def barrier(epoch, checkpoint, scale):
        spans = [span(1, "session.tick", 100 * scale),
                 span(2, "source.feed", 7 * scale, 1),
                 span(3, "cosched.dispatch", 5 * scale, 1),
                 span(4, "cosched.flush_begin", 1 * scale, 1),
                 span(5, "cosched.epoch_wait", 40 * scale, 1, "device"),
                 span(6, "cosched.flush_decode", 4 * scale, 1),
                 span(7, "barrier.collect", 30 * scale, 1),
                 span(8, "Materialize.chunks", 11 * scale, 7),
                 span(9, "Materialize.barrier", 6 * scale, 7),
                 span(10, "Materialize.seal", 5 * scale, 9)]
        if checkpoint:
            spans += [span(11, "agg.state_delta", 9 * scale, 1),
                      span(12, "cosched.restack", 2 * scale, 1),
                      span(13, "cosched.restack", 1 * scale, 1)]
        for s in spans:
            s["epoch"] = epoch
        return {"wall_ms": 0.0, "ledger": {"epoch": epoch,
                                           "checkpoint": checkpoint}}, spans

    pairs = [barrier(1, False, 1), barrier(2, True, 2), barrier(3, False, 3)]
    monkeypatch.setattr(program_spans, "load",
                        lambda: {b["ledger"]["epoch"]: s for b, s in pairs})
    ctx = {"barriers": [b for b, _s in pairs], "traced": [0, 1, 2]}
    assert read("source_feed_span_ms", ctx) == 14          # 7, 14, 21
    assert read("fused_host_ms", ctx) == 20                # 10, 20, 30
    assert read("device_wait_ms", ctx) == 80               # 40, 80, 120
    assert read("state_delta_ms", ctx) == 24               # (9 + 2 + 1) x 2
    assert read("operator_busy_ms", ctx) == 34             # 17, 34, 51
    # 100 - (7 + 5 + 1 + 40 + 4 + 30) = 13; the checkpoint barrier:
    # 200 - 2 x (87 + 9 + 2 + 1) = 2; the third: 39 → median 13
    assert read("tick_unaccounted_ms", ctx) == 13


@pytest.mark.parametrize("metric", sorted(
    set(WANT["fused"]) | set(WANT["exec"])))
def test_nothing_where_the_program_has_no_spans(metric, monkeypatch):
    """The parent commit: no ``epoch_spans`` → the metric is left out."""
    monkeypatch.setattr(program_spans, "load", lambda: None)
    assert read(metric, ctx_of(recorded("fused"))) is None


def test_load_asks_the_program_and_tolerates_one_without_the_call(
        monkeypatch):
    from risingwave_tpu.common import tracing
    assert isinstance(program_spans.load(), dict)
    monkeypatch.delattr(tracing, "epoch_spans")
    assert program_spans.load() is None


@pytest.mark.parametrize("which,metric,gone", [
    ("exec", "source_feed_span_ms", "source.feed"),
    ("fused", "fused_host_ms", "cosched.flush_decode"),
    ("fused", "device_wait_ms", "cosched.epoch_wait"),
    ("exec", "device_wait_ms", "agg.flush_wait"),
    ("fused", "state_delta_ms", "agg.state_delta"),
    ("exec", "operator_busy_ms", "barrier.collect"),
    ("fused", "tick_unaccounted_ms", "session.tick"),
])
def test_a_missing_span_is_an_error(which, metric, gone, monkeypatch):
    rec = recorded(which)
    spans = {e: [s for s in v if s["name"] != gone]
             for e, v in rec["epoch_spans"].items()}
    monkeypatch.setattr(program_spans, "load", lambda: spans)
    with pytest.raises(LookupError, match=metric):
        read(metric, ctx_of(rec))


def test_too_few_covered_barriers_is_an_error(monkeypatch):
    rec = recorded("exec")
    epochs = sorted(rec["epoch_spans"])
    # the ring lost the two oldest barriers of the window
    held = {e: rec["epoch_spans"][e] for e in epochs[2:]}
    monkeypatch.setattr(program_spans, "load", lambda: held)
    assert read("device_wait_ms", ctx_of(rec, traced=(0, 1, 2))) is not None
    with pytest.raises(LookupError, match="trace_ring_capacity"):
        read("device_wait_ms", ctx_of(rec, traced=(0, 1, 2, 3)))


def test_one_summary_line_per_run(monkeypatch, capsys):
    rec = recorded("exec")
    monkeypatch.setattr(program_spans, "load", lambda: rec["epoch_spans"])
    ctx = ctx_of(rec)
    for metric in WANT["exec"]:
        read(metric, ctx)
    lines = [json.loads(line) for line in capsys.readouterr().out.split("\n")
             if line.startswith('{"program_spans"')]
    (line,) = lines
    summary = line["program_spans"]
    assert summary["window_barriers"] == summary["covered_barriers"] == 5
    assert summary["spans_per_barrier"] == 15
    assert set(summary["chunks_median_ms"]) == {
        "Project.chunks", "HashAgg.chunks", "Materialize.chunks"}
    assert "source.feed" in summary["median_ms_where_present"]


@pytest.mark.parametrize("which", sorted(CELL))
def test_tiny_rehearsal_reports_every_span_metric_its_cell_lists(which):
    spec = run.load_json(ROOT, "BENCHMARK.json")
    listed = {m["name"] for m in spec["per_layer"]
              if m["source"] == "program_span"
              and CELL[which] in m["workloads"]}
    assert set(WANT[which]) <= listed
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL[which], "--seed", "2147483777", "--seconds", "2",
         "--trace", "1", "--tiny"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 1, out.stderr[-2000:]      # a rehearsal
    line = next(ln for ln in out.stderr.split("\n")
                if ln.startswith("rehearsal, not a result: "))
    result = json.loads(line[len("rehearsal, not a result: "):])
    assert result["correct"]
    assert listed <= set(result["metrics"])
    assert result["metrics"]["tick_unaccounted_ms"]["value"] >= 0
