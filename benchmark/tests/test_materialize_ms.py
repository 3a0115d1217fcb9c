"""``materialize_ms`` (ISSUE 28) on the span lists the tests already hold
(five barriers of each configuration at its tiny sizes). The wanted values
were added up by hand from the files: per barrier ``Materialize.chunks`` +
``Materialize.barrier``, then the middle one of five."""

import copy
import json
import os

import pytest

from benchmark import program_spans, run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

#: recorded file → (cell, median of the five per-barrier sums)
WANT = {
    # 0.744241, 1.185723, 1.545714, 0.996174, 0.845032
    "spans_q5core_exec_5barriers.json": ("q5core_exec_catchup", 0.996174),
    # 0.762725, 0.679373, 0.583985, 0.614866, 2.111449
    "spans_q5core_fused_5barriers.json": ("q5core_fused_catchup", 0.679373),
    # 11.667785, 8.639846, 6.160749, 8.251466, 12.260203
    "spans_q8_5barriers.json": ("q8_catchup", 8.639846),
}


def recorded(name: str) -> dict:
    with open(os.path.join(HERE, "data", name)) as f:
        rec = json.load(f)
    rec["epoch_spans"] = {int(e): spans
                          for e, spans in rec["epoch_spans"].items()}
    return rec


def ctx_of(rec: dict) -> dict:
    return {"barriers": copy.deepcopy(rec["barriers"]), "traced": [0, 1, 2]}


def read(ctx: dict):
    return run.load_by_name("layer_metrics", "materialize_ms").read(ctx)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_recorded_spans(name, monkeypatch, capsys):
    rec = recorded(name)
    monkeypatch.setattr(program_spans, "load", lambda: rec["epoch_spans"])
    assert read(ctx_of(rec)) == pytest.approx(WANT[name][1], abs=1e-6)
    capsys.readouterr()


def test_nothing_for_a_program_without_the_spans(monkeypatch, capsys):
    rec = recorded("spans_q8_5barriers.json")
    gone = {e: [s for s in spans if not s["name"].startswith("Materialize.")]
            for e, spans in rec["epoch_spans"].items()}
    monkeypatch.setattr(program_spans, "load", lambda: gone)
    assert read(ctx_of(rec)) is None
    monkeypatch.setattr(program_spans, "load", lambda: None)
    assert read(ctx_of(rec)) is None
    capsys.readouterr()


def test_a_barrier_without_one_of_the_two_spans_is_an_error(monkeypatch,
                                                            capsys):
    rec = recorded("spans_q5core_exec_5barriers.json")
    first = min(rec["epoch_spans"])
    rec["epoch_spans"][first] = [
        s for s in rec["epoch_spans"][first]
        if s["name"] != "Materialize.barrier"]
    monkeypatch.setattr(program_spans, "load", lambda: rec["epoch_spans"])
    with pytest.raises(LookupError, match="materialize_ms"):
        read(ctx_of(rec))
    capsys.readouterr()


def test_the_entry_lists_the_three_cells_and_edits_nothing_else():
    spec = run.load_json(ROOT, "BENCHMARK.json")
    (entry,) = [m for m in spec["per_layer"] if m["name"] == "materialize_ms"]
    assert entry == {
        "name": "materialize_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "executors and epoch collection",
        "moves": "events_per_s",
        "workloads": ["q5core_fused_catchup", "q5core_exec_catchup",
                      "q8_catchup"]}
    assert set(entry["workloads"]) == {cell for cell, _ in WANT.values()}
