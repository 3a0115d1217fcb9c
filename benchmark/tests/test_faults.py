"""A run's flow with the timed path broken underneath: ``correct`` has to
come out false for each fault a cell can have, and true without one.

Skips the harness's look for a chip (the device is handed in) and drives
the rest of ``run.run_cell`` on the CPU at the configurations' tiny
sizes, with ``system.System`` replaced by a stand-in that plants one
fault in the program's path:

* ``state_unchanged``  — one barrier of the window completes without
  applying its epoch (a step that returns its state unchanged)
* ``half_batch``       — one barrier ingests half of its chunks
* ``answer_altered``   — one row of the read-back is altered where it is
  produced
* ``checkpoint_skipped`` — the checkpoint barriers commit nothing (the
  durability guarantee; caught by the generic numbers, not the rows)

The exchange between chips does not exist in a one-chip cell.
"""

import json
import os

import pytest

from benchmark import run, system

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FAULT_AT = 13          # a barrier of the window (warm-up is 10)


def faulty(fault: str):
    class Faulty(system.System):
        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            self.n = 0

        def barrier(self):
            self.n += 1
            s = self.session
            if fault == "state_unchanged" and self.n == FAULT_AT:
                s.tick(generate=False)
            elif fault == "half_batch" and self.n == FAULT_AT:
                k = s.chunks_per_tick
                s.set_source_rate(k // 2)
                s.tick()
                s.set_source_rate(k)
            elif fault == "checkpoint_skipped":
                s.tick(checkpoint=False)
            else:
                s.tick()

        def read_back(self):
            rows = super().read_back()
            if fault == "answer_altered":
                first = list(rows[0])
                first[-1] += 1
                rows = [tuple(first)] + list(rows[1:])
            return rows

    return Faulty


def one_run(monkeypatch, cell_name: str, fault: str) -> dict:
    spec = run.load_json(ROOT, "BENCHMARK.json")
    cell, entry = run.find_cell(spec, cell_name)
    config = run.tiny_sizes(run.load_json(ROOT, entry["file"]))
    traffic = run.load_json(ROOT, "benchmark", "traffic",
                            f"{cell['traffic']}.json")
    monkeypatch.setattr(system, "System", faulty(fault))
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    return run.run_cell(spec, cell, config, traffic, device, None,
                        seed=1_000_000_007, seconds=60.0, traced=False)


CELLS = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["none", "state_unchanged", "half_batch",
                                   "answer_altered", "checkpoint_skipped"])
def test_fault_comes_out_not_correct(monkeypatch, capsys, cell, fault):
    result = one_run(monkeypatch, cell, fault)
    compared = result["compared"]
    assert list(result)[-1] == "compared"
    if fault == "none":
        assert result["correct"] is True
        assert all(c["value"] == 0 for c in compared.values()
                   if "limit" in c)
    elif fault == "checkpoint_skipped":
        assert result["correct"] is False
        assert compared["rows_wrong"]["value"] == 0
        assert compared["checkpoints_missing"]["value"] > 0
        assert compared["committed_epoch_lag"]["value"] > 0
    else:
        assert result["correct"] is False
        assert compared["rows_wrong"]["value"] > 0
    assert result["attempted"] > 10 and result["failed"] == 0
    assert set(result["metrics"]) == {"events_per_s", "barrier_p95_ms",
                                      "setup_s"}
    capsys.readouterr()
