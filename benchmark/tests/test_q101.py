"""The q101 deployment's own pieces (ISSUE 33): the plain reference
against a brute-force recomputation and against the program at the tiny
sizes, its control, why the q5 cells' control cannot serve, and the four
outer-join readers on recorded spans and a made-up trace."""

import copy
import json
import os

import numpy as np
import pytest

from benchmark import program_spans, run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SEED = 2_147_483_659            # more than 32 signed bits hold


@pytest.fixture(scope="module")
def config():
    return run.tiny_sizes(run.load_json(
        ROOT, "benchmark", "configs", "nexmark-q101.json"))


@pytest.fixture(scope="module")
def ref():
    return run.load_by_name("reference", "q101_host_stream")


def brute_force(ref, config: dict, seed: int, barriers: int,
                twice=None) -> set:
    """q101 by python dicts over the replayed rows, one row at a time.
    ``twice``: a barrier whose first bid chunk is counted twice."""
    items, best = {}, {}
    n = config["rows_per_chunk"]["bid"]
    for b, (aid, item, bid_auction, price) in enumerate(
            ref.streams(config, seed, barriers)):
        for a, i in zip(aid, item):
            items[int(a)] = int(i)
        order = list(range(len(price)))
        if b == twice:
            order += list(range(n))
        for i in order:
            a = int(bid_auction[i])
            best[a] = max(best.get(a, 0), int(price[i]))
    return {(a, i, best.get(a, ref.NULL)) for a, i in items.items()}


def test_reference_equals_brute_force(ref, config):
    exp = ref.expected(config, SEED, 30)
    assert {tuple(r) for r in exp["rows"].tolist()} == brute_force(
        ref, config, SEED, 30)
    per = config["chunks_per_tick"] * config["rows_per_chunk"]["auction"]
    assert len(exp["rows"]) == 30 * per
    assert 0 < exp["null_rows"] < len(exp["rows"]) // 2
    assert exp["maxima_replaced"] > 0
    # per barrier: every auction is a join input row, and so is every bid
    # group whose maximum moved (fewer than the auctions it can name)
    assert len(exp["groups_touched"]) == 30
    assert all(per < g < 3 * per for g in exp["groups_touched"])


def test_rows_are_sql_rows_and_compare_is_exact(ref, config):
    exp = ref.expected(config, SEED, 12)
    sql_rows = [(int(a), f"item-{int(i)}", None if p == ref.NULL else int(p))
                for a, i, p in exp["rows"][::-1]]
    same = {"rows_wrong": 0, "null_rows_off": 0, "events_off": 0,
            "rows_expected": len(sql_rows)}
    assert ref.compare(exp, sql_rows) == same
    padded = next(i for i, r in enumerate(sql_rows) if r[2] is None)
    matched = next(i for i, r in enumerate(sql_rows) if r[2] is not None)
    # a NULL where a maximum belongs, and a maximum where a NULL belongs
    lost = list(sql_rows)
    lost[matched] = sql_rows[matched][:2] + (None,)
    assert ref.compare(exp, lost) == {**same, "rows_wrong": 2,
                                      "null_rows_off": 1}
    found = list(sql_rows)
    found[padded] = sql_rows[padded][:2] + (100,)
    assert ref.compare(exp, found) == {**same, "rows_wrong": 2,
                                       "null_rows_off": 1}
    lower = list(sql_rows)
    lower[matched] = sql_rows[matched][:2] + (sql_rows[matched][2] - 1,)
    assert ref.compare(exp, lower) == {**same, "rows_wrong": 2}
    renamed = [(sql_rows[0][0], "nothing", sql_rows[0][2])] + sql_rows[1:]
    assert ref.compare(exp, renamed)["rows_wrong"] == 2
    assert ref.compare(exp, sql_rows[1:])["events_off"] == 1
    assert ref.compare(exp, sql_rows + sql_rows[:1])["rows_wrong"] == 1


def test_control_bid_chunk_lost_comes_out_not_correct(ref, config):
    assert config["control"] == "bid_chunk_lost"
    exp = ref.expected(config, SEED, 20)
    broken = ref.expected(config, SEED, 20, broken="bid_chunk_lost")
    numbers = ref.compare(exp, broken["rows"])
    # the side the outer join pads: a NULL it should not show, or a lower
    # maximum
    assert numbers["rows_wrong"] > 0 and numbers["null_rows_off"] > 0
    assert numbers["events_off"] == 0           # every auction is there
    with pytest.raises(ValueError):
        ref.expected(config, SEED, 20, broken="at_least_once")


def test_a_bid_chunk_counted_twice_changes_no_row_of_q101(ref, config):
    """Why ``at_least_once`` cannot be q101's control: a maximum does not
    move when a bid is counted again."""
    assert brute_force(ref, config, SEED, 20, twice=19) == brute_force(
        ref, config, SEED, 20)


# -- the program at the tiny sizes, and the control under a whole run ---------

def tiny_run(control: str = "") -> dict:
    spec = run.load_json(ROOT, "BENCHMARK.json")
    cell, entry = run.find_cell(spec, "q101_catchup")
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
    assert compared["rows_expected"]["value"] > 1000
    assert all(c["value"] == 0 for name, c in compared.items()
               if name != "rows_expected")
    assert set(compared) >= {"rows_wrong", "null_rows_off", "events_off",
                             "barriers_failed", "checkpoints_missing",
                             "committed_epoch_lag"}
    capsys.readouterr()


def test_the_control_under_a_whole_run_reads_not_correct(capsys):
    result = tiny_run(control="bid_chunk_lost")
    assert result["correct"] is False
    assert result["compared"]["rows_wrong"]["value"] > 0
    assert result["compared"]["null_rows_off"]["value"] > 0
    assert result["compared"]["barriers_failed"]["value"] == 0
    capsys.readouterr()


# -- the four readers ---------------------------------------------------------

WANT = {"ojoin_busy_ms": None, "ojoin_state_delta_ms": None,
        # barrier 10: (82 NULL-padded + 2 x 85 transitions) / 266 rows out
        "ojoin_null_pad_pct": 100.0 * (82 + 2 * 85) / 266}


def recorded() -> dict:
    with open(os.path.join(HERE, "data", "spans_q101_5barriers.json")) as f:
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


@pytest.mark.parametrize("metric", sorted(WANT))
def test_span_reader_on_recorded_spans(metric, monkeypatch, capsys):
    rec = recorded()
    want = {"ojoin_busy_ms": by_hand(
                rec, ("HashJoin.chunks", "HashJoin.barrier"), False),
            "ojoin_state_delta_ms": by_hand(
                rec, ("join.state_delta",), True)}.get(metric, WANT[metric])
    monkeypatch.setattr(program_spans, "load", lambda: rec["epoch_spans"])
    assert read(metric, ctx_of(rec)) == pytest.approx(want, abs=1e-6)
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines()]
    if metric == "ojoin_busy_ms":
        assert {"ojoin_busy": {"bucket_width": 1, "rewinds": 0,
                               "grows": 0}} in lines
    if metric == "ojoin_state_delta_ms":
        # the one recorded checkpoint: left 864 rows + right 722
        assert {"ojoin_state_delta": {
            "dirty_rows": 864 + 722, "windows": 2,
            "bytes_fetched": 212996 + 172036}} in lines


@pytest.mark.parametrize("metric", sorted(WANT))
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
    """PR 33's parent has ``HashJoin.chunks`` without ``bucket_width``,
    ``rows_out``, ``null_padded_out`` and ``transitions``: the busy reader
    still reads (and prints no width), the share reads as nothing."""
    rec = recorded()
    new = ("bucket_width", "rows_out", "null_padded_out", "transitions")
    for spans in rec["epoch_spans"].values():
        for s in spans:
            if s["name"] == "HashJoin.chunks":
                s["args"] = {k: v for k, v in s["args"].items()
                             if k not in new}
    monkeypatch.setattr(program_spans, "load", lambda: rec["epoch_spans"])
    assert read("ojoin_null_pad_pct", ctx_of(rec)) is None
    assert read("ojoin_busy_ms", ctx_of(rec)) > 0
    assert '"bucket_width": null' in capsys.readouterr().out


def test_null_pad_reader_owes_the_counts_on_every_barrier(monkeypatch,
                                                          capsys):
    rec = recorded()
    spans = rec["epoch_spans"][rec["barriers"][1]["ledger"]["epoch"]]
    for s in spans:
        if s["name"] == "HashJoin.chunks":
            del s["args"]["transitions"]
    monkeypatch.setattr(program_spans, "load", lambda: rec["epoch_spans"])
    with pytest.raises(LookupError, match="transitions"):
        read("ojoin_null_pad_pct", ctx_of(rec))
    capsys.readouterr()


def test_state_delta_reader_owes_the_span_on_every_checkpoint(monkeypatch,
                                                              capsys):
    rec = recorded()
    rec["barriers"][0]["ledger"]["checkpoint"] = True    # none recorded
    monkeypatch.setattr(program_spans, "load", lambda: rec["epoch_spans"])
    with pytest.raises(LookupError):
        read("ojoin_state_delta_ms", ctx_of(rec))
    capsys.readouterr()


def roofline_ctx(program_s: dict) -> dict:
    return {"trace": {"program_s": program_s},
            "config": {"name": "nexmark-q101", "trace_programs": {
                "join_epoch": ["jit_join_step_right", "jit_join_gather"]}},
            "peaks": {"hbm_bytes_per_s": 819e9},
            "traced": [0, 1], "first_barrier": 10,
            "groups_touched": [0] * 10 + [5600, 5500, 999_999]}


def test_ojoin_epoch_roofline_by_hand(capsys):
    # 11,100 rows x (2 x 23 + 10) B = 621,600 B = 0.76 us at 819 GB/s,
    # over 0.2 s of the named programs
    value = read("ojoin_epoch_roofline", roofline_ctx(
        {"jit_join_step_right": 0.15, "jit_join_gather": 0.05,
         "jit_apply_chunk": 9.0}))
    assert value == pytest.approx(100 * (11100 * 56 / 819e9) / 0.2, rel=1e-9)
    line = json.loads(capsys.readouterr().out)["ojoin_epoch_roofline"]
    assert line["rows_in"] == 11100 and line["traced_barriers"] == 2


def test_ojoin_epoch_roofline_missing_program_ends_the_run(capsys):
    with pytest.raises(LookupError, match="jit_join_gather"):
        read("ojoin_epoch_roofline",
             roofline_ctx({"jit_join_step_right": 0.075}))
    ctx = roofline_ctx({"jit_join_step_right": 0.075})
    ctx["config"] = {"name": "x", "trace_programs": {}}
    with pytest.raises(LookupError, match="trace_programs.join_epoch"):
        read("ojoin_epoch_roofline", ctx)
    ctx["trace"] = None
    assert read("ojoin_epoch_roofline", ctx) is None
    capsys.readouterr()


def test_every_new_metric_lists_only_the_q101_cell():
    spec = run.load_json(ROOT, "BENCHMARK.json")
    new = [m for m in spec["per_layer"] if m["name"].startswith("ojoin_")]
    assert sorted(m["name"] for m in new) == [
        "ojoin_busy_ms", "ojoin_epoch_roofline", "ojoin_null_pad_pct",
        "ojoin_state_delta_ms"]
    assert all(m["workloads"] == ["q101_catchup"] for m in new)
    config = run.load_json(ROOT, "benchmark", "configs", "nexmark-q101.json")
    entry = next(c for c in spec["configs"] if c["name"] == "nexmark-q101")
    assert entry["source"] == config["source"] and entry["reduced"] == []
    assert "q101.slt.part" in entry["source"]
    # 4 chunks of 256 NEXmark epochs a barrier, both sides multiples of the
    # rank kernel's tile
    assert config["rows_per_chunk"] == {"bid": 256 * 50, "auction": 256 * 3}
    assert config["chunks_per_tick"] == 4
    assert config["max_events"] % (4 * (12800 + 768)) == 0
