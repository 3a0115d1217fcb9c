"""The q104 deployment's own pieces (ISSUE 37): the plain reference
against a brute-force recomputation and against the program at the tiny
sizes, its control, why the q5 cells' control cannot serve, and the four
anti-join readers on recorded spans and a made-up trace."""

import collections
import copy
import json
import os

import numpy as np
import pytest

from benchmark import program_spans, run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SEED = 2_147_483_693            # more than 32 signed bits hold
NEW = ("ajoin_busy_ms", "ajoin_epoch_roofline", "ajoin_retract_pct",
       "ajoin_state_delta_ms")


@pytest.fixture(scope="module")
def config():
    return run.tiny_sizes(run.load_json(
        ROOT, "benchmark", "configs", "nexmark-q104.json"))


@pytest.fixture(scope="module")
def ref():
    return run.load_by_name("reference", "q104_host_stream")


def brute_force(ref, config: dict, seed: int, barriers: int,
                twice=None) -> dict:
    """q104 by a python dict over the replayed rows, one bid at a time:
    the view's rows, and per barrier the auctions ingested plus the bid
    groups that crossed an edge of the under-20 set. ``twice``: a barrier
    whose first bid chunk is counted twice."""
    items, bids = {}, collections.Counter()
    touched = []
    n = config["rows_per_chunk"]["bid"]
    for b, (aid, item, bid_auction, _price) in enumerate(
            ref.streams(config, seed, barriers)):
        items.update(zip(aid.tolist(), item.tolist()))
        before = dict(bids)
        order = bid_auction.tolist()
        if b == twice:
            order += order[:n]
        for a in order:
            bids[a] += 1
        crossed = sum(
            (before.get(a, 0) == 0 and bids[a] < ref.UNDER)         # entered
            or 0 < before.get(a, 0) < ref.UNDER <= bids[a]          # left
            for a in set(order))
        touched.append(len(aid) + crossed)
    return {"rows": {(a, i) for a, i in items.items()
                     if not 0 < bids[a] < ref.UNDER},
            "groups_touched": touched,
            "unbid": sum(bids[a] == 0 for a in items)}


def test_reference_equals_brute_force(ref, config):
    exp = ref.expected(config, SEED, 30)
    brute = brute_force(ref, config, SEED, 30)
    assert {tuple(r) for r in exp["rows"].tolist()} == brute["rows"]
    assert exp["groups_touched"] == brute["groups_touched"]
    assert exp["unbid_rows"] == brute["unbid"]
    per = config["chunks_per_tick"] * config["rows_per_chunk"]["auction"]
    # the view holds the unbid auctions and the few that reached 20
    assert 0 < exp["unbid_rows"] < len(exp["rows"]) < 30 * per // 2
    assert len(exp["rows"]) - exp["unbid_rows"] == np.isin(
        exp["rows"][:, 0], exp["hot_ids"]).sum() > 0
    # over a quarter of the auctions were in the view at a barrier's end
    # and out of it at the next (more within one barrier: the reference
    # reads barrier ends); under this connector a group leaves the set
    # only where a barrier cuts a hot auction's first epoch short, which
    # the tiny sizes never do
    assert exp["retracted"] > 30 * per // 4
    assert exp["returned"] == 0
    # the first barrier also meets the 99 ids under the first auction's
    assert all(per < g < 3 * per for g in exp["groups_touched"])


def test_rows_are_sql_rows_and_compare_is_exact(ref, config):
    exp = ref.expected(config, SEED, 12)
    sql_rows = [(f"item-{int(i)}", int(a)) for a, i in exp["rows"][::-1]]
    same = {"rows_wrong": 0, "unbid_rows_off": 0, "events_off": 0,
            "rows_expected": len(sql_rows)}
    assert ref.compare(exp, sql_rows) == same
    hot = next(i for i, r in enumerate(sql_rows) if r[1] in exp["hot_ids"])
    unbid = next(i for i, r in enumerate(sql_rows)
                 if r[1] not in exp["hot_ids"])
    # an auction the reference holds under 20 shown, an unbid one lost
    shown = sql_rows + [("item-7", int(exp["rows"][-1, 0]) + 1)]
    assert ref.compare(exp, shown) == {**same, "rows_wrong": 1,
                                       "unbid_rows_off": 1, "events_off": 1}
    lost = sql_rows[:unbid] + sql_rows[unbid + 1:]
    assert ref.compare(exp, lost) == {**same, "rows_wrong": 1,
                                      "unbid_rows_off": 1, "events_off": 1}
    # a 20-bid auction lost is wrong, but not the anti join's own lane
    cold = sql_rows[:hot] + sql_rows[hot + 1:]
    assert ref.compare(exp, cold) == {**same, "rows_wrong": 1,
                                      "events_off": 1}
    renamed = [("nothing", sql_rows[0][1])] + sql_rows[1:]
    assert ref.compare(exp, renamed)["rows_wrong"] == 2
    assert ref.compare(exp, sql_rows + sql_rows[:1])["rows_wrong"] == 1


def test_control_bid_chunk_lost_comes_out_not_correct(ref, config):
    assert config["control"] == "bid_chunk_lost"
    exp = ref.expected(config, SEED, 20)
    broken = ref.expected(config, SEED, 20, broken="bid_chunk_lost")
    numbers = ref.compare(exp, broken["rows"])
    # the side that retracts: an auction still shown that a bid of the
    # lost chunk should have removed
    assert numbers["rows_wrong"] > 0 and numbers["unbid_rows_off"] > 0
    assert len(broken["rows"]) == len(exp["rows"]) + numbers["rows_wrong"]
    with pytest.raises(ValueError):
        ref.expected(config, SEED, 20, broken="at_least_once")


def test_a_bid_chunk_counted_twice_changes_no_row_of_q104(ref, config):
    """Why ``at_least_once`` cannot be q104's control: a bid counted
    again moves no cold auction past 20 (about 1.67 bids each) and no
    unbid auction out of 0."""
    assert brute_force(ref, config, SEED, 20, twice=19)["rows"] \
        == brute_force(ref, config, SEED, 20)["rows"]


# -- the program at the tiny sizes, and the control under a whole run ---------

def tiny_run(control: str = "") -> dict:
    spec = run.load_json(ROOT, "BENCHMARK.json")
    cell, entry = run.find_cell(spec, "q104_catchup")
    tiny = run.tiny_sizes(run.load_json(ROOT, entry["file"]))
    traffic = run.load_json(ROOT, "benchmark", "traffic", "catchup.json")
    return run.run_cell(spec, cell, tiny, traffic,
                        {"platform": "cpu", "kind": "cpu", "count": 1},
                        None, seed=SEED, seconds=60.0, traced=False,
                        control=control)


def test_the_program_equals_the_reference_at_tiny_sizes(capsys):
    result = tiny_run()
    assert result["correct"] is True
    compared = result["compared"]
    assert compared["rows_expected"]["value"] > 500
    assert all(c["value"] == 0 for name, c in compared.items()
               if name != "rows_expected")
    assert set(compared) >= {"rows_wrong", "unbid_rows_off", "events_off",
                             "barriers_failed", "checkpoints_missing",
                             "committed_epoch_lag"}
    capsys.readouterr()


def test_the_control_under_a_whole_run_reads_not_correct(capsys):
    result = tiny_run(control="bid_chunk_lost")
    assert result["correct"] is False
    assert result["compared"]["rows_wrong"]["value"] > 0
    assert result["compared"]["unbid_rows_off"]["value"] > 0
    assert result["compared"]["barriers_failed"]["value"] == 0
    capsys.readouterr()


# -- the four readers ---------------------------------------------------------

def recorded() -> dict:
    with open(os.path.join(HERE, "data", "spans_q104_5barriers.json")) as f:
        rec = json.load(f)
    rec["epoch_spans"] = {int(e): spans
                          for e, spans in rec["epoch_spans"].items()}
    return rec


def by_hand(rec: dict, names: tuple, checkpoint_only: bool) -> float:
    """Median over the recorded barriers of the summed ms of ``names``."""
    values = sorted(
        sum(s["dur_ns"] for s in rec["epoch_spans"][b["ledger"]["epoch"]]
            if s["name"] in names) / 1e6
        for b in rec["barriers"]
        if b["ledger"]["checkpoint"] or not checkpoint_only)
    return values[len(values) // 2]


def read(metric: str, ctx: dict):
    return run.load_by_name("layer_metrics", metric).read(ctx)


def ctx_of(rec: dict) -> dict:
    return {"barriers": copy.deepcopy(rec["barriers"]), "traced": [0, 1, 2]}


SPAN_READERS = ("ajoin_busy_ms", "ajoin_retract_pct", "ajoin_state_delta_ms")


@pytest.mark.parametrize("metric", SPAN_READERS)
def test_span_reader_on_recorded_spans(metric, monkeypatch, capsys):
    rec = recorded()
    want = {"ajoin_busy_ms": by_hand(
                rec, ("HashJoin.chunks", "HashJoin.barrier"), False),
            "ajoin_state_delta_ms": by_hand(
                rec, ("join.state_delta",), True),
            # the median barrier of the five: 86 retractions of 190 rows
            "ajoin_retract_pct": 100.0 * 86 / 190}[metric]
    monkeypatch.setattr(program_spans, "load", lambda: rec["epoch_spans"])
    assert read(metric, ctx_of(rec)) == pytest.approx(want, abs=1e-6)
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines()]
    if metric == "ajoin_busy_ms":
        assert {"ajoin_busy": {"bucket_width": 1, "rewinds": 0,
                               "grows": 0}} in lines
    if metric == "ajoin_state_delta_ms":
        # the one recorded checkpoint: left 864 rows + right 715
        assert {"ajoin_state_delta": {
            "dirty_rows": 864 + 715, "windows": 2,
            "bytes_fetched": 212996 + 98308}} in lines
    if metric == "ajoin_retract_pct":
        assert {"ajoin_retract": {
            "rows_out": 189 + 180 + 190 + 188 + 191,
            "matched": 85 + 79 + 86 + 86 + 89,
            "unmatched": 20 + 21 + 26 + 21 + 25,
            "null_padded_out": 84 + 80 + 78 + 81 + 77,
            "rows_in_right": 117 + 116 + 130 + 122 + 133}} in lines


@pytest.mark.parametrize("metric", SPAN_READERS)
def test_span_reader_gives_nothing_for_a_program_without_the_span(
        metric, monkeypatch, capsys):
    """No join span in the window (a q5 deployment, or a program older
    than the span) reads as nothing and does not raise; a program with no
    ring at all likewise."""
    rec = recorded()
    gone = {e: [s for s in spans if not s["name"].startswith(
        ("join.", "HashJoin."))] for e, spans in rec["epoch_spans"].items()}
    monkeypatch.setattr(program_spans, "load", lambda: gone)
    assert read(metric, ctx_of(rec)) is None
    monkeypatch.setattr(program_spans, "load", lambda: None)
    assert read(metric, ctx_of(rec)) is None
    capsys.readouterr()


def test_readers_on_the_parent_whose_span_lacks_the_new_counts(monkeypatch,
                                                               capsys):
    """PR 37's parent has ``HashJoin.chunks`` without ``matched`` and
    ``unmatched``: the share reads as nothing, not an error, so the
    parent's traced run still gives a result; the other span readers
    read what they read."""
    rec = recorded()
    for spans in rec["epoch_spans"].values():
        for s in spans:
            if s["name"] == "HashJoin.chunks":
                s["args"] = {k: v for k, v in s["args"].items()
                             if k not in ("matched", "unmatched")}
    monkeypatch.setattr(program_spans, "load", lambda: rec["epoch_spans"])
    assert read("ajoin_retract_pct", ctx_of(rec)) is None
    assert read("ajoin_busy_ms", ctx_of(rec)) > 0
    assert read("ajoin_state_delta_ms", ctx_of(rec)) > 0
    assert '"bucket_width": 1' in capsys.readouterr().out


def test_retract_reader_owes_the_counts_on_every_barrier(monkeypatch,
                                                         capsys):
    rec = recorded()
    spans = rec["epoch_spans"][rec["barriers"][1]["ledger"]["epoch"]]
    for s in spans:
        if s["name"] == "HashJoin.chunks":
            del s["args"]["matched"]
    monkeypatch.setattr(program_spans, "load", lambda: rec["epoch_spans"])
    with pytest.raises(LookupError, match="matched"):
        read("ajoin_retract_pct", ctx_of(rec))
    capsys.readouterr()


def test_matched_and_unmatched_add_up_to_the_transitions_recorded():
    rec = recorded()
    joins = [s["args"] for spans in rec["epoch_spans"].values()
             for s in spans if s["name"] == "HashJoin.chunks"]
    assert len(joins) == 5
    for a in joins:
        assert a["matched"] + a["unmatched"] == a["transitions"] > 0
        assert a["rows_out"] == a["null_padded_out"] + a["transitions"]
    # the filter has a clock of its own, on both sides of the join
    names = {s["name"] for spans in rec["epoch_spans"].values()
             for s in spans}
    assert {"Filter.chunks", "Filter.barrier"} <= names


def roofline_ctx(program_s: dict) -> dict:
    return {"trace": {"program_s": program_s},
            "config": {"name": "nexmark-q104", "trace_programs": {
                "join_epoch": ["jit_join_step_right", "jit_join_gather"]}},
            "peaks": {"hbm_bytes_per_s": 819e9},
            "traced": [0, 1], "first_barrier": 10,
            "groups_touched": [0] * 10 + [5600, 5500, 999_999]}


def test_ajoin_epoch_roofline_by_hand(capsys):
    # 11,100 rows x (2 x 23 + 10) B = 621,600 B = 0.76 us at 819 GB/s,
    # over 0.2 s of the named programs
    value = read("ajoin_epoch_roofline", roofline_ctx(
        {"jit_join_step_right": 0.15, "jit_join_gather": 0.05,
         "jit_apply_chunk": 9.0}))
    assert value == pytest.approx(100 * (11100 * 56 / 819e9) / 0.2, rel=1e-9)
    line = json.loads(capsys.readouterr().out)["ajoin_epoch_roofline"]
    assert line["rows_in"] == 11100 and line["traced_barriers"] == 2


def test_ajoin_epoch_roofline_missing_program_ends_the_run(capsys):
    with pytest.raises(LookupError, match="jit_join_gather"):
        read("ajoin_epoch_roofline",
             roofline_ctx({"jit_join_step_right": 0.075}))
    ctx = roofline_ctx({"jit_join_step_right": 0.075})
    ctx["config"] = {"name": "x", "trace_programs": {}}
    with pytest.raises(LookupError, match="trace_programs.join_epoch"):
        read("ajoin_epoch_roofline", ctx)
    ctx["trace"] = None
    assert read("ajoin_epoch_roofline", ctx) is None
    capsys.readouterr()


def test_every_new_metric_lists_only_the_q104_cell():
    spec = run.load_json(ROOT, "BENCHMARK.json")
    new = [m for m in spec["per_layer"] if m["name"].startswith("ajoin_")]
    assert sorted(m["name"] for m in new) == sorted(NEW)
    assert all(m["workloads"] == ["q104_catchup"] for m in new)
    # any other metric that lists the cell is shared with other cells
    assert not [m["name"] for m in spec["per_layer"]
                if m.get("workloads") == ["q104_catchup"]
                and m["name"] not in NEW]
    cell, entry = run.find_cell(spec, "q104_catchup")
    assert cell["chips"] == 1 and cell["traffic"] == "catchup"
    config = run.load_json(ROOT, entry["file"])
    assert entry["source"] == config["source"] and entry["reduced"] == []
    assert "q104.slt.part" in entry["source"] and len(entry["source"]) <= 200
    # q101's sources and sizes, to the letter
    q101 = run.load_json(ROOT, "benchmark", "configs", "nexmark-q101.json")
    for key in ("nexmark", "ddl", "rw_toml", "chunks_per_tick",
                "rows_per_chunk", "max_events", "trace_programs", "tiny"):
        assert config[key] == q101[key], key
    assert "NOT IN" in config["mv"] and "COUNT(*) < 20" in config["mv"]
