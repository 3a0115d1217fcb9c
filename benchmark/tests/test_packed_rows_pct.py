"""``packed_rows_pct`` (ISSUE 38): of the rows a checkpoint's commit hands
on, the share that came in packed layers — on the four-barrier window of
``test_tree_readers`` (barriers x2 and x4 are checkpoints), with the
``packed`` counts the packed delta's spans carry laid over it."""

import os

import pytest

from benchmark import program_spans, run
from test_tree_readers import ROOT, lines, read, window

METRIC = "packed_rows_pct"
CELLS = ["q5core_fused_catchup", "q5core_exec_catchup", "q8_catchup",
         "q5core_exec_mesh4_catchup", "q101_catchup", "q104_catchup"]
COUNTED = ("commit.pending", "segment.encode", "store.apply")


def packed_window(packed_by_epoch: dict, dict_tables=()):
    """The window with ``packed`` (and ``dict_tables`` on
    ``commit.pending``) on a checkpoint's three counted spans; an epoch
    that is not in ``packed_by_epoch`` keeps the parent's args."""
    ctx, by_epoch = window()
    for epoch, packed in packed_by_epoch.items():
        for s in by_epoch[epoch]:
            if s["name"] in COUNTED:
                s["args"]["packed"] = packed
            if s["name"] == "commit.pending":
                s["args"]["dict_tables"] = list(dict_tables)
    return ctx, by_epoch


@pytest.mark.parametrize("packed, want", [
    ({2: 120, 4: 120}, 100.0),
    ({2: 119, 4: 120}, (100.0 * 119 / 120 + 100.0) / 2),
    ({2: 60, 4: 30}, 37.5),
    ({2: 0, 4: 0}, 0.0),
], ids=["all", "one_row_by_insert", "a_table_fell_back", "no_codec"])
def test_share_of_the_rows_handed_on(packed, want, monkeypatch, capsys):
    ctx, by_epoch = packed_window(packed, dict_tables=[1, 9])
    monkeypatch.setattr(program_spans, "load", lambda: by_epoch)
    assert read(METRIC, ctx) == pytest.approx(want, abs=1e-9)
    out = lines(capsys)["packed_rows"]
    assert out["dict_tables"] == [1, 9]
    median = sum(packed.values()) / 2
    assert out["commit.pending"] == {"rows": 120, "packed": median}
    assert out["store.apply"] == {"rows": 120, "packed": median}
    assert out["segment.encode"] == {"rows": 120, "packed": median}


def test_an_empty_checkpoint_counts_as_packed(monkeypatch, capsys):
    ctx, by_epoch = packed_window({2: 0, 4: 120})
    for s in by_epoch[2]:
        if s["name"] == "commit.pending":
            s["args"]["rows"] = 0
    monkeypatch.setattr(program_spans, "load", lambda: by_epoch)
    assert read(METRIC, ctx) == 100.0


def test_nothing_on_the_parent_commit(monkeypatch, capsys):
    """Spans without the ``packed`` arg (the parent), no ``commit.pending``
    at all, no span ring at all: the metric is left out, nothing raised."""
    ctx, by_epoch = window()
    monkeypatch.setattr(program_spans, "load", lambda: by_epoch)
    assert read(METRIC, ctx) is None
    ctx, by_epoch = window(drop=COUNTED)
    monkeypatch.setattr(program_spans, "load", lambda: by_epoch)
    assert read(METRIC, ctx) is None
    ctx, _ = window()
    monkeypatch.setattr(program_spans, "load", lambda: None)
    assert read(METRIC, ctx) is None


def test_nothing_for_a_window_without_a_checkpoint(monkeypatch, capsys):
    ctx, by_epoch = packed_window({2: 120, 4: 120})
    for b in ctx["barriers"]:
        b["ledger"]["checkpoint"] = False
    monkeypatch.setattr(program_spans, "load", lambda: by_epoch)
    assert read(METRIC, ctx) is None


def test_a_checkpoint_that_lacks_the_count_while_another_has_it_is_an_error(
        monkeypatch, capsys):
    ctx, by_epoch = packed_window({4: 120})
    monkeypatch.setattr(program_spans, "load", lambda: by_epoch)
    with pytest.raises(LookupError, match=METRIC):
        read(METRIC, ctx)


def test_the_entry_is_the_last_and_names_what_exists():
    spec = run.load_json(ROOT, "BENCHMARK.json")
    (entry,) = [m for m in spec["per_layer"] if m["name"] == METRIC]
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "checkpoint",
        "moves": "barrier_p95_ms", "workloads": CELLS}
    assert set(CELLS) <= {w["name"] for w in spec["workloads"]}
    assert "checkpoint" in {m["layer"] for m in spec["per_layer"]
                            if m is not entry}
    assert entry["moves"] in {m["name"] for m in spec["end_to_end"]}
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", f"{METRIC}.py"))
