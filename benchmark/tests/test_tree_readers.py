"""The seven readers of the span tree's leaves — the job task's clock
(``actor_run_ms``, ``executor_unowned_ms``) and the checkpoint by part (``delta_wait_ms``, ``delta_encode_ms``,
``delta_stage_ms``, ``commit_apply_ms``, ``commit_io_ms``) — on a window
of four barriers small enough to add up in the head: barrier ``i`` is the
base barrier below times ``i``, the second and the fourth are checkpoints.
"""

import json
import os

import pytest

from benchmark import program_spans, run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

CELLS = ["q5core_fused_catchup", "q5core_exec_catchup", "q8_catchup",
         "q5core_exec_mesh4_catchup", "q101_catchup"]
EXECUTORS = "executors and epoch collection"
#: metric -> (layer, moves, cells)
ENTRIES = {
    "actor_run_ms": (EXECUTORS, "events_per_s", CELLS),
    "executor_unowned_ms": (EXECUTORS, "events_per_s", CELLS),
    "delta_wait_ms": ("checkpoint", "barrier_p95_ms", CELLS),
    "delta_encode_ms": ("checkpoint", "barrier_p95_ms", CELLS),
    "delta_stage_ms": ("checkpoint", "barrier_p95_ms", CELLS),
    "commit_apply_ms": ("checkpoint", "barrier_p95_ms", CELLS),
    "commit_io_ms": ("checkpoint", "barrier_p95_ms", CELLS),
}
#: base barrier: actor.run 47 (82 on a checkpoint), the operators' own
#: 10 + 20 + 2 + 3 = 35 (70), so 12 unowned. Over barriers x1, x2, x3, x4
#: (x2 and x4 checkpoints): 47, 164, 141, 328 -> 152.5; 12, 24, 36, 48 -> 30.
#: A checkpoint's parts at x1: wait 5, encode 9, stage 12, pending 3 +
#: apply 8, put 6 + manifest 4; the median of x2 and x4 is x3.
WANT = {"actor_run_ms": 152.5, "executor_unowned_ms": 30,
        "delta_wait_ms": 15, "delta_encode_ms": 27, "delta_stage_ms": 36,
        "commit_apply_ms": 33, "commit_io_ms": 30}
#: the spans each metric reads: without them (the parent commit) nothing
READS = {"actor_run_ms": ("actor.run",),
         "executor_unowned_ms": ("actor.run",),
         "delta_wait_ms": ("delta.fetch_wait",),
         "delta_encode_ms": ("delta.encode",),
         "delta_stage_ms": ("delta.stage",),
         "commit_apply_ms": ("commit.pending", "store.apply"),
         "commit_io_ms": ("segment.put", "manifest.write")}
NEW_SPANS = {name for names in READS.values() for name in names} | {
    "segment.encode"}


def barrier(epoch: int, checkpoint: bool, scale: int):
    def span(i, name, dur_ms, parent=None, wait=None, **args):
        return {"name": name, "id": i, "parent": parent, "wait": wait,
                "epoch": epoch, "start_ns": i,
                "dur_ns": int(dur_ms * scale * 1e6), "args": args,
                "cat": "epoch"}

    more = 35 if checkpoint else 0
    spans = [span(1, "session.tick", 100 + more + (40 if checkpoint else 0)),
             span(2, "source.feed", 7, 1, chunks=16, capacity_rows=65536,
                  transfers=2, bytes_staged=1000, dispatches=1),
             span(3, "barrier.collect", 60 + more, 1),
             span(4, "actor.run", 47 + more, 3, task=0, messages=17),
             span(9, "HashAgg.chunks", 10, 3, node=2, chunks=16),
             span(10, "HashAgg.barrier", 20 + more, 3, node=2),
             span(11, "agg.flush_wait", 15, 10, "device"),
             span(12, "Materialize.chunks", 2, 3, node=0, fetches=16),
             span(13, "Materialize.barrier", 3, 3, node=0)]
    if checkpoint:
        spans += [
            span(14, "agg.state_delta", 30, 10, dirty_groups=100, windows=1,
                 bytes_fetched=4096, bytes_staged=5000),
            span(15, "delta.fetch_wait", 5, 14, "device", windows=1),
            span(16, "delta.encode", 9, 14, rows=100, bytes=5000, native=1),
            span(17, "delta.stage", 12, 14, puts=90, deletes=10),
            span(18, "checkpoint.commit", 40, 1),
            span(19, "commit.pending", 3, 18, rows=120),
            span(20, "DurableStateStore.commit", 25, 18, tables=3, rows=120,
                 bytes=9000, native=1),
            span(21, "segment.encode", 14, 20, rows=120, bytes=9000,
                 native=1),
            span(22, "segment.put", 6, 20, bytes=9000),
            span(23, "manifest.write", 4, 20, segments=5),
            span(24, "store.apply", 8, 18, rows=120)]
    return ({"wall_ms": 0.0,
             "ledger": {"epoch": epoch, "checkpoint": checkpoint}}, spans)


def window(drop=(), only_in=None):
    """The four barriers; ``drop`` names leave every barrier, or the
    barrier of epoch ``only_in`` alone."""
    pairs = [barrier(e, e in (2, 4), e) for e in (1, 2, 3, 4)]
    pairs = [(b, [s for s in spans if s["name"] not in drop
                  or (only_in is not None and b["ledger"]["epoch"] != only_in)])
             for b, spans in pairs]
    by_epoch = {b["ledger"]["epoch"]: spans for b, spans in pairs}
    return {"barriers": [b for b, _s in pairs], "traced": [0, 1, 2]}, by_epoch


def read(metric: str, ctx: dict):
    return run.load_by_name("layer_metrics", metric).read(ctx)


def lines(capsys) -> dict:
    out = {}
    for line in capsys.readouterr().out.split("\n"):
        if line.startswith("{"):
            out.update(json.loads(line))
    return out


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_arithmetic(metric, monkeypatch, capsys):
    ctx, by_epoch = window()
    monkeypatch.setattr(program_spans, "load", lambda: by_epoch)
    assert read(metric, ctx) == pytest.approx(WANT[metric], abs=1e-9)


def test_the_lines_print_the_counts_no_other_reader_does(monkeypatch,
                                                         capsys):
    ctx, by_epoch = window()
    monkeypatch.setattr(program_spans, "load", lambda: by_epoch)
    for metric in WANT:
        read(metric, ctx)
    out = lines(capsys)
    assert out["actor_run"] == {
        "tasks": 1, "messages": 17, "source_feed": {
            "chunks": 16, "capacity_rows": 65536, "transfers": 2,
            "bytes_staged": 1000, "dispatches": 1}}
    # every arg of the operators' spans but node
    assert out["operators"] == {"HashAgg.chunks": {"chunks": 16},
                                "Materialize.chunks": {"fetches": 16}}
    assert out["delta_wait"]["checkpoint_barriers"] == 2
    assert out["delta_wait"]["windows"] == 1
    # the delta by part: 30 = 5 + 9 + 12 + 4 of its own, at x3
    assert out["delta_wait"]["by_delta"] == {"agg.state_delta": {
        "ms": 90, "delta.fetch_wait": 15, "delta.encode": 27,
        "delta.stage": 36, "self_ms": 12, "dirty_groups": 100, "windows": 1,
        "bytes_fetched": 4096, "bytes_staged": 5000}}
    assert out["delta_encode"] == {"spans": 1, "rows": 100, "bytes": 5000,
                                   "native": 1}
    assert out["delta_stage"] == {"puts": 90, "deletes": 10}
    assert out["commit_apply"] == {
        "commit.pending": {"ms": 9, "rows": 120},
        "store.apply": {"ms": 24, "rows": 120}}
    # the writer's 25 = encode 14 + put 6 + manifest 4 + 1 of its own
    assert out["commit_io"] == {
        "bytes": 9000, "segments": 5, "segment_encode_ms": 42,
        "writer": {"ms": 75, "self_ms": 3, "tables": 3, "rows": 120,
                   "bytes": 9000, "native": 1}}


def test_two_deltas_of_a_barrier_are_summed_and_laid_out_by_side(
        monkeypatch, capsys):
    ctx, by_epoch = window()
    for epoch in (2, 4):
        spans = by_epoch[epoch]
        for side, base in (("left", 100), ("right", 200)):
            scale = epoch
            spans.append({"name": "join.state_delta", "id": base,
                          "parent": 10, "wait": None, "epoch": epoch,
                          "start_ns": base, "dur_ns": 20 * scale * 10**6,
                          "args": {"side": side, "dirty_rows": 7},
                          "cat": "storage"})
            for j, (name, ms) in enumerate((("delta.fetch_wait", 1),
                                            ("delta.encode", 2),
                                            ("delta.stage", 3)), start=1):
                spans.append({"name": name, "id": base + j, "parent": base,
                              "wait": None, "epoch": epoch,
                              "start_ns": base + j,
                              "dur_ns": ms * scale * 10**6, "args": {},
                              "cat": "storage"})
    monkeypatch.setattr(program_spans, "load", lambda: by_epoch)
    assert read("delta_wait_ms", ctx) == 15 + 2 * 3
    assert read("delta_encode_ms", ctx) == 27 + 2 * 6
    assert read("delta_stage_ms", ctx) == 36 + 2 * 9
    by_delta = lines(capsys)["delta_wait"]["by_delta"]
    assert sorted(by_delta) == ["agg.state_delta", "join.state_delta.left",
                                "join.state_delta.right"]
    assert by_delta["join.state_delta.right"] == {
        "ms": 60, "delta.fetch_wait": 3, "delta.encode": 6,
        "delta.stage": 9, "self_ms": 42, "dirty_rows": 7}


@pytest.mark.parametrize("metric", sorted(WANT))
def test_nothing_on_the_parent_commit(metric, monkeypatch, capsys):
    """A program without the new spans (every other span is there), and
    one without ``epoch_spans`` at all: the metric is left out."""
    ctx, by_epoch = window(drop=NEW_SPANS)
    monkeypatch.setattr(program_spans, "load", lambda: by_epoch)
    assert read(metric, ctx) is None
    ctx, _ = window()
    monkeypatch.setattr(program_spans, "load", lambda: None)
    assert read(metric, ctx) is None


@pytest.mark.parametrize("metric", sorted(
    m for m, (layer, _moves, _cells) in ENTRIES.items()
    if layer == "checkpoint"))
def test_nothing_for_a_window_without_a_checkpoint(metric, monkeypatch,
                                                   capsys):
    ctx, by_epoch = window()
    for b in ctx["barriers"]:
        b["ledger"]["checkpoint"] = False
    monkeypatch.setattr(program_spans, "load", lambda: by_epoch)
    assert read(metric, ctx) is None


@pytest.mark.parametrize("metric,gone,epoch", [
    (metric, name, epoch)
    for metric, names in sorted(READS.items()) for name in names
    for epoch in ((4,) if ENTRIES[metric][0] == "checkpoint" else (3, 4))]
    + [("executor_unowned_ms", "barrier.collect", 3)])
def test_a_barrier_that_lacks_its_span_while_another_has_it_is_an_error(
        metric, gone, epoch, monkeypatch, capsys):
    ctx, by_epoch = window(drop=(gone,), only_in=epoch)
    monkeypatch.setattr(program_spans, "load", lambda: by_epoch)
    with pytest.raises(LookupError, match=metric):
        read(metric, ctx)


def test_the_entries_name_files_layers_and_cells_that_exist():
    spec = run.load_json(ROOT, "BENCHMARK.json")
    by_name = {m["name"]: m for m in spec["per_layer"]}
    cells = {w["name"] for w in spec["workloads"]}
    older = [m for m in spec["per_layer"] if m["name"] not in ENTRIES]
    layers = {m["layer"] for m in older}
    reports = {m["name"] for m in spec["end_to_end"]}
    for name, (layer, moves, listed) in ENTRIES.items():
        assert by_name[name] == {
            "name": name, "unit": "ms", "better": "lower",
            "source": "program_span", "layer": layer, "moves": moves,
            "workloads": listed}
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", f"{name}.py"))
        assert layer in layers and moves in reports
        assert set(listed) <= cells
