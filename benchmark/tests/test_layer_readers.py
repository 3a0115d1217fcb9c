"""The ledger and trace readers of the per-layer metrics on hand-made
barriers: medians over the right barriers, and nothing where there is
nothing to read."""

import os

import pytest

from benchmark import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def barrier(wall, inject, total, collect, commit, checkpoint):
    return {"wall_ms": wall, "ledger": {
        "inject_ms": inject, "total_ms": total, "collect_ms": collect,
        "commit_ms": commit, "checkpoint": checkpoint}}


BARRIERS = [barrier(100, 1, 59, 40, None, False),
            barrier(110, 1, 59, 44, None, False),
            barrier(120, 1, 59, 48, None, False),
            barrier(500, 1, 199, 50, 120, True),
            barrier(700, 1, 299, 52, 220, True)]


@pytest.mark.parametrize("name,want", [
    ("pre_barrier_ms", 60),             # 40, 50, 60, 300, 400
    ("source_feed_ms", 60),
    ("pre_barrier_checkpoint_ms", 350),
    ("collect_ms", 48),
    ("commit_ms", 170),
])
def test_ledger_readers(name, want):
    reader = run.load_by_name("layer_metrics", name)
    assert reader.read({"barriers": BARRIERS}) == want
    assert reader.read({"barriers": []}) is None


def test_device_idle_share():
    reader = run.load_by_name("layer_metrics", "device_idle_pct")
    assert reader.read({"trace": {"busy_s": 1.5, "window_s": 6.0}}) == 75.0
    assert reader.read({"trace": None}) is None


def test_every_listed_metric_has_a_reader_and_every_cell_reports_one():
    spec = run.load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"] for w in spec["workloads"]}
    reported = set()
    for metric in spec["per_layer"]:
        assert callable(run.load_by_name("layer_metrics",
                                         metric["name"]).read)
        assert set(metric["workloads"]) <= cells
        reported |= set(metric["workloads"])
    assert reported == cells
