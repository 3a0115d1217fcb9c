"""The four ``mesh_*`` per-layer readers (ISSUE 31) on what the program
records: five barriers (one checkpoint) of ``nexmark-q5core-exec-mesh4``
at its tiny sizes on four virtual CPU devices
(``data/spans_q5core_exec_mesh4_5barriers.json``), and a reduced trace
small enough to add up in the head. The wanted values were added up by
hand from the file, not by the readers' code."""

import copy
import json
import os

import pytest

from benchmark import program_spans, run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "q5core_exec_mesh4_catchup"
MESH_METRICS = ("mesh_agg_busy_ms", "mesh_state_delta_ms",
                "mesh_hot_shard_pct", "mesh_agg_epoch_roofline")

#: per barrier ``ShardedHashAgg.chunks`` + ``.barrier``: 7.378293,
#: 7.316695, 54.323848 (the checkpoint), 6.912869, 6.981666 — the middle
#: one; the checkpoint's one sharded ``agg.state_delta``; 100 x
#: rows_routed_max / rows_routed: 264, 477, 468, 365, 472 of 512 — the
#: middle one is 468
WANT = {"mesh_agg_busy_ms": 7.316695, "mesh_state_delta_ms": 45.894523,
        "mesh_hot_shard_pct": 100 * 468 / 512}


def recorded(name: str = "spans_q5core_exec_mesh4_5barriers.json") -> dict:
    with open(os.path.join(HERE, "data", name)) as f:
        rec = json.load(f)
    rec["epoch_spans"] = {int(e): spans
                          for e, spans in rec["epoch_spans"].items()}
    return rec


def ctx_of(rec: dict, **more) -> dict:
    return {"barriers": copy.deepcopy(rec["barriers"]), "traced": [0, 1, 2],
            **more}


def read(metric: str, ctx: dict):
    return run.load_by_name("layer_metrics", metric).read(ctx)


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_on_recorded_spans(metric, monkeypatch, capsys):
    rec = recorded()
    monkeypatch.setattr(program_spans, "load", lambda: rec["epoch_spans"])
    assert read(metric, ctx_of(rec)) == pytest.approx(WANT[metric], abs=1e-6)
    out = capsys.readouterr().out
    if metric == "mesh_state_delta_ms":
        line = json.loads([ln for ln in out.splitlines()
                           if "mesh_state_delta" in ln][0])
        assert line["mesh_state_delta"] == {
            "checkpoint_barriers": 1, "shards": 4, "dirty_groups": 251,
            "bytes_staged": 0, "bytes_fetched": 466948}
    if metric == "mesh_agg_busy_ms":
        line = json.loads([ln for ln in out.splitlines()
                           if "shard_split" in ln][0])
        assert line["shard_split"] == {"median_ms": pytest.approx(1.614004),
                                       "chunks": 2, "transfers": 4}


@pytest.mark.parametrize("metric", MESH_METRICS)
@pytest.mark.parametrize("program", ["no epoch_spans", "one chip",
                                     "parent of PR 31"])
def test_nothing_where_the_program_records_nothing_of_the_kind(
        metric, program, monkeypatch, capsys):
    """A program without the ring, a one-chip deployment, and the commit
    before the sharded executor had its spans and counts (it has the
    generic ``ShardedHashAgg.chunks`` / ``.barrier`` only): every reader
    but ``mesh_agg_busy_ms`` on the last gives nothing, and none raises —
    the parent's traced run still prints its line."""
    rec = recorded()
    if program == "no epoch_spans":
        spans = None
    elif program == "one chip":
        rec = recorded("spans_q5core_exec_5barriers.json")
        spans = rec["epoch_spans"]
    else:
        new = ("shard.split", "agg.flush_wait", "agg.state_delta")
        spans = {e: [dict(s, args={k: v for k, v in s["args"].items()
                                   if not k.startswith("rows_routed")})
                     for s in group if s["name"] not in new]
                 for e, group in rec["epoch_spans"].items()}
    monkeypatch.setattr(program_spans, "load", lambda: spans)
    trace = {"devices": 4, "program_s": {"jit_local_step": 0.05,
                                         "jit_flush_rank": 0.001}}
    got = read(metric, ctx_of(rec, trace=trace, config={}, peaks={}))
    if metric == "mesh_agg_busy_ms" and program == "parent of PR 31":
        assert got == pytest.approx(WANT[metric], abs=1e-6)
    else:
        assert got is None
    capsys.readouterr()


@pytest.mark.parametrize("metric,gone,on", [
    ("mesh_agg_busy_ms", "ShardedHashAgg.chunks", 0),
    ("mesh_state_delta_ms", "agg.state_delta", 0),
    ("mesh_hot_shard_pct", "ShardedHashAgg.barrier", 1),
])
def test_a_barrier_without_its_span_is_an_error(metric, gone, on,
                                                monkeypatch, capsys):
    """Barrier ``on`` loses the span (for the state delta: barrier 0 is
    made a second checkpoint barrier, which then has none)."""
    rec = recorded()
    rec["barriers"][0]["ledger"]["checkpoint"] = True
    epoch = rec["barriers"][on]["ledger"]["epoch"]
    rec["epoch_spans"][epoch] = [s for s in rec["epoch_spans"][epoch]
                                 if s["name"] != gone]
    monkeypatch.setattr(program_spans, "load", lambda: rec["epoch_spans"])
    with pytest.raises(LookupError, match=metric):
        read(metric, ctx_of(rec))
    capsys.readouterr()


# -- the roofline: the mesh's roof, the per-device mean of the programs ------

PROGRAMS = {"jit_sharded_agg_step": 0.050, "jit_flush_rank": 0.001,
            "jit_gather_flush_chunk": 0.002, "jit_flatten_shards": 0.0005,
            "jit_finish_flush": 0.0015}


def roofline_ctx(rec: dict, programs: dict) -> dict:
    config = run.load_json(ROOT, "benchmark", "configs",
                           "nexmark-q5core-exec-mesh4.json")
    peaks = run.load_json(ROOT, "benchmark", "peaks.json")[
        "device_kinds"]["TPU v5 lite"]
    return ctx_of(
        rec, traced=[0, 1], first_barrier=10, config=config, peaks=peaks,
        events_per_barrier=65536, groups_touched=[0] * 10 + [3200, 3000],
        trace={"devices": 4, "program_s": dict(programs, jit_other=9.0)})


def test_roofline_is_over_the_mesh_bandwidth(monkeypatch, capsys):
    rec = recorded()
    monkeypatch.setattr(program_spans, "load", lambda: rec["epoch_spans"])
    got = read("mesh_agg_epoch_roofline", roofline_ctx(rec, PROGRAMS))
    # 2 barriers x 65,536 events x 64 B + (3,200 + 3,000) groups x 24 B
    # over 4 x 819 GB/s, against 0.055 s a device
    least = (2 * 65536 * 64 + 6200 * 24) / (4 * 819e9)
    assert got == pytest.approx(100 * least / 0.055, rel=1e-9)
    assert 0.004 < got < 0.005
    line = json.loads([ln for ln in capsys.readouterr().out.splitlines()
                       if "mesh_agg_epoch_roofline" in ln][0])
    assert line["mesh_agg_epoch_roofline"]["devices"] == 4
    assert line["mesh_agg_epoch_roofline"]["device_s"] == pytest.approx(0.055)


def test_roofline_needs_every_named_program(monkeypatch, capsys):
    rec = recorded()
    monkeypatch.setattr(program_spans, "load", lambda: rec["epoch_spans"])
    some = {k: v for k, v in PROGRAMS.items() if k != "jit_flatten_shards"}
    with pytest.raises(LookupError, match="jit_flatten_shards"):
        read("mesh_agg_epoch_roofline", roofline_ctx(rec, some))
    ctx = roofline_ctx(rec, PROGRAMS)
    ctx["trace"] = None                       # a CPU rehearsal
    assert read("mesh_agg_epoch_roofline", ctx) is None
    capsys.readouterr()


def test_the_entries_list_the_one_cell_and_edit_nothing_else():
    spec = run.load_json(ROOT, "BENCHMARK.json")
    by_name = {m["name"]: m for m in spec["per_layer"]}
    want = {
        "mesh_agg_busy_ms": ("ms", "lower", "program_span",
                             "executors and epoch collection",
                             "events_per_s"),
        "mesh_state_delta_ms": ("ms", "lower", "program_span", "checkpoint",
                                "barrier_p95_ms"),
        "mesh_hot_shard_pct": ("%", "lower", "program_span", "exchange",
                               "events_per_s"),
        "mesh_agg_epoch_roofline": ("%", "higher", "device_trace",
                                    "epoch programs", "events_per_s"),
    }
    for name, (unit, better, source, layer, moves) in want.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": moves, "workloads": [CELL]}
    # the cell's own metrics are these four; every other that lists it is
    # shared with the one-chip cells
    assert {m["name"] for m in spec["per_layer"]
            if m["workloads"] == [CELL]} == set(want)
    cell, entry = run.find_cell(spec, CELL)
    assert cell["config"] == entry["name"]
    assert (cell["chips"], cell["traffic"]) == (4, "catchup")
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    config = run.load_json(ROOT, entry["file"])
    assert config["rw_toml"]["streaming.mesh_shape"] == 4
    assert "streaming.coschedule" not in config["rw_toml"]
    assert config["reduced"] == entry["reduced"]
    assert config["source"] == entry["source"]
    assert "one shard per group" in config["guarantees"]["placement"]
