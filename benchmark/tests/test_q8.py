"""The q8 deployment's own pieces (ISSUE 27): the plain reference against
a brute-force recomputation, its control, why the q5 cells' control
cannot serve, the four join readers on recorded spans and a made-up
trace, and one fault planted under a whole tiny run."""

import copy
import json
import os

import numpy as np
import pytest

from benchmark import program_spans, run, system

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SEED = 2_147_483_659            # more than 32 signed bits hold


@pytest.fixture(scope="module")
def config():
    return run.tiny_sizes(run.load_json(
        ROOT, "benchmark", "configs", "nexmark-q8.json"))


@pytest.fixture(scope="module")
def ref():
    return run.load_by_name("reference", "q8_host_stream")


def brute_force(ref, config: dict, seed: int, barriers: int,
                twice=None) -> set:
    """q8 by python sets over the replayed rows, one row at a time.
    ``twice``: a barrier whose first person chunk is delivered twice."""
    nx = config["nexmark"]
    persons, sellers = set(), set()
    for b, (pid, name, p_ts, seller, a_ts) in enumerate(
            ref._streams(config, seed, barriers)):
        n = config["rows_per_chunk"]["person"]
        order = list(range(len(pid)))
        if b == twice:
            order += list(range(n))
        for i in order:
            w = (int(p_ts[i]) - nx["start_time_us"]) // nx["window_us"]
            persons.add((int(pid[i]), int(name[i]), w))
        for s, t in zip(seller, a_ts):
            sellers.add((int(s), (int(t) - nx["start_time_us"])
                         // nx["window_us"]))
    return {(p, n, nx["start_time_us"] + w * nx["window_us"])
            for p, n, w in persons if (p, w) in sellers}


def test_reference_equals_brute_force_over_several_windows(ref, config):
    exp = ref.expected(config, SEED, 30)
    assert exp["windows"] >= 3
    assert {tuple(r) for r in exp["rows"].tolist()} == brute_force(
        ref, config, SEED, 30)
    assert len(exp["rows"]) > 1000
    # per barrier: every person is a new group; the new (seller, window)
    # groups are some of its auctions
    per = config["chunks_per_tick"] * config["rows_per_chunk"]["person"]
    assert len(exp["groups_touched"]) == 30
    assert all(per < g < 4 * per for g in exp["groups_touched"])


def test_rows_are_sql_rows_and_compare_is_exact(ref, config):
    exp = ref.expected(config, SEED, 12)
    sql_rows = [(int(i), f"person-{int(n)}", int(w))
                for i, n, w in exp["rows"][::-1]]
    got = ref.compare(exp, sql_rows)
    assert got == {"rows_wrong": 0, "events_off": 0,
                   "rows_expected": len(sql_rows)}
    renamed = [(sql_rows[0][0], "nobody", sql_rows[0][2])] + sql_rows[1:]
    assert ref.compare(exp, renamed)["rows_wrong"] == 2
    assert ref.compare(exp, sql_rows + sql_rows[:1])["rows_wrong"] == 1
    assert ref.compare(exp, sql_rows[1:]) == {
        "rows_wrong": 1, "events_off": 1, "rows_expected": len(sql_rows)}


def test_control_chunk_lost_comes_out_not_correct(ref, config):
    assert config["control"] == "chunk_lost"
    exp = ref.expected(config, SEED, 20)
    broken = ref.expected(config, SEED, 20, broken="chunk_lost")
    numbers = ref.compare(exp, broken["rows"])
    assert numbers["rows_wrong"] > 0 and numbers["events_off"] > 0
    with pytest.raises(ValueError):
        ref.expected(config, SEED, 20, broken="at_least_once")


def test_a_chunk_delivered_twice_changes_no_row_of_q8(ref, config):
    """Why ``at_least_once`` cannot be q8's control: both inputs pass a
    GROUP BY without an aggregate, so a replayed chunk vanishes."""
    assert brute_force(ref, config, SEED, 20, twice=19) == brute_force(
        ref, config, SEED, 20)


# -- the four readers ---------------------------------------------------------

WANT = {"join_busy_ms": 82.065469, "join_wait_ms": 70.909654,
        "join_state_delta_ms": 16.21219}


def recorded() -> dict:
    with open(os.path.join(HERE, "data", "spans_q8_5barriers.json")) as f:
        rec = json.load(f)
    rec["epoch_spans"] = {int(e): spans
                          for e, spans in rec["epoch_spans"].items()}
    return rec


def read(metric: str, ctx: dict):
    return run.load_by_name("layer_metrics", metric).read(ctx)


def ctx_of(rec: dict) -> dict:
    return {"barriers": copy.deepcopy(rec["barriers"]), "traced": [0, 1, 2]}


@pytest.mark.parametrize("metric", sorted(WANT))
def test_span_reader_on_recorded_spans(metric, monkeypatch, capsys):
    rec = recorded()
    monkeypatch.setattr(program_spans, "load", lambda: rec["epoch_spans"])
    assert read(metric, ctx_of(rec)) == pytest.approx(WANT[metric], abs=1e-6)
    capsys.readouterr()


@pytest.mark.parametrize("metric", sorted(WANT))
def test_span_reader_gives_nothing_for_a_program_without_the_span(
        metric, monkeypatch, capsys):
    """The parent commit under these files: no join span in the window (a
    q5 deployment, or a program older than the span) reads as nothing and
    does not raise; a program with no ring at all likewise."""
    rec = recorded()
    gone = {e: [s for s in spans if not s["name"].startswith(
        ("join.", "HashJoin."))] for e, spans in rec["epoch_spans"].items()}
    monkeypatch.setattr(program_spans, "load", lambda: gone)
    assert read(metric, ctx_of(rec)) is None
    monkeypatch.setattr(program_spans, "load", lambda: None)
    assert read(metric, ctx_of(rec)) is None
    capsys.readouterr()


def test_state_delta_reader_owes_the_span_on_every_checkpoint(monkeypatch,
                                                              capsys):
    rec = recorded()
    rec["barriers"][0]["ledger"]["checkpoint"] = True    # none recorded
    monkeypatch.setattr(program_spans, "load", lambda: rec["epoch_spans"])
    with pytest.raises(LookupError):
        read("join_state_delta_ms", ctx_of(rec))
    capsys.readouterr()


def roofline_ctx(program_s: dict) -> dict:
    return {"trace": {"program_s": program_s},
            "config": {"name": "nexmark-q8", "trace_programs": {
                "join_epoch": ["jit_join_step_left", "jit_join_gather"]}},
            "peaks": {"hbm_bytes_per_s": 819e9},
            "traced": [0, 1], "first_barrier": 10,
            "groups_touched": [0] * 10 + [5000, 3190, 999_999]}


def test_join_epoch_roofline_by_hand(capsys):
    # 8,190 rows x (2 x 32 + 28) B = 753,480 B = 0.92 us at 819 GB/s,
    # over 0.1 s of the named programs: 0.00092 %
    value = read("join_epoch_roofline", roofline_ctx(
        {"jit_join_step_left": 0.075, "jit_join_gather": 0.025,
         "jit_apply_chunk": 9.0}))
    assert value == pytest.approx(100 * (8190 * 92 / 819e9) / 0.1, rel=1e-9)
    line = json.loads(capsys.readouterr().out)["join_epoch_roofline"]
    assert line["rows_in"] == 8190 and line["traced_barriers"] == 2


def test_join_epoch_roofline_missing_program_ends_the_run(capsys):
    with pytest.raises(LookupError, match="jit_join_gather"):
        read("join_epoch_roofline",
             roofline_ctx({"jit_join_step_left": 0.075}))
    ctx = roofline_ctx({"jit_join_step_left": 0.075})
    ctx["config"] = {"name": "x", "trace_programs": {}}
    with pytest.raises(LookupError, match="trace_programs.join_epoch"):
        read("join_epoch_roofline", ctx)
    ctx["trace"] = None
    assert read("join_epoch_roofline", ctx) is None
    capsys.readouterr()


def test_every_new_metric_lists_only_the_q8_cell():
    spec = run.load_json(ROOT, "BENCHMARK.json")
    new = [m for m in spec["per_layer"] if m["name"].startswith("join_")]
    assert sorted(m["name"] for m in new) == [
        "join_busy_ms", "join_epoch_roofline", "join_state_delta_ms",
        "join_wait_ms"]
    assert all(m["workloads"] == ["q8_catchup"] for m in new)


# -- one fault under a whole tiny run -----------------------------------------

def test_a_person_chunk_lost_in_the_program_comes_out_not_correct(
        monkeypatch, capsys):
    """The stated guarantee broken where the program runs: at one barrier
    of the window the person source skips a chunk (its reader is moved on
    by one), as a source restored past its offset would."""
    class Lossy(system.System):
        n = 0

        def barrier(self):
            self.n += 1
            if self.n == 13:
                (feed,) = [f for f in self.session.feeds
                           if f.reader.table == "person"]
                feed.reader.next_chunk()
            self.session.tick()

    spec = run.load_json(ROOT, "BENCHMARK.json")
    cell, entry = run.find_cell(spec, "q8_catchup")
    tiny = run.tiny_sizes(run.load_json(ROOT, entry["file"]))
    traffic = run.load_json(ROOT, "benchmark", "traffic", "catchup.json")
    monkeypatch.setattr(system, "System", Lossy)
    result = run.run_cell(spec, cell, tiny, traffic,
                          {"platform": "cpu", "kind": "cpu", "count": 1},
                          None, seed=SEED, seconds=60.0, traced=False)
    assert result["correct"] is False
    assert result["compared"]["rows_wrong"]["value"] > 0
    assert result["compared"]["barriers_failed"]["value"] == 0
    capsys.readouterr()
