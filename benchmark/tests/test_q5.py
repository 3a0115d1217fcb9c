"""The q5 deployment's own pieces: the plain reference against a
brute-force recomputation and against the program at the tiny sizes, its
control, the join's per-barrier input against what the program's join
counted, and the four readers on recorded spans and a made-up trace."""

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
NEW = ("q5join_busy_ms", "q5join_candidate_rows", "q5join_epoch_roofline",
       "maxagg_busy_ms")


@pytest.fixture(scope="module")
def config():
    return run.tiny_sizes(run.load_json(
        ROOT, "benchmark", "configs", "nexmark-q5.json"))


@pytest.fixture(scope="module")
def ref():
    return run.load_by_name("reference", "q5_host_stream")


def brute_force(config: dict, seed: int, barriers: int) -> dict:
    """q5 by python dicts over the replayed bids, one bid at a time: the
    view's rows ``(window start, auction, num)`` and per barrier the
    join's changelog input (AuctionBids + MaxBids rows)."""
    from benchmark.reference.q5core_host_stream import bid_stream
    hop, nx = config["hop"], config["nexmark"]
    count, top = collections.Counter(), {}
    rows_in = []
    for auction, ts in bid_stream(config, seed, barriers):
        for a_row, t_row in zip(auction.tolist(), ts.tolist()):
            before, top_before = dict(count), dict(top)
            for a, t in zip(a_row, t_row):
                base = t - (t - nx["start_time_us"]) % hop["slide_us"]
                for i in range(hop["size_us"] // hop["slide_us"]):
                    count[(base - i * hop["slide_us"], a)] += 1
            for (w, _a), n in count.items():
                top[w] = max(top.get(w, 0), n)
            changed = [g for g in count if count[g] != before.get(g, 0)]
            left = sum(1 if g not in before else 2 for g in changed)
            right = sum(1 if w not in top_before else 2
                        for w in top if top[w] != top_before.get(w))
            rows_in.append(left + right)
    return {"rows": sorted((w, a, n) for (w, a), n in count.items()
                           if n >= top[w]),
            "rows_in": rows_in}


def test_reference_equals_brute_force(ref, config):
    exp = ref.expected(config, SEED, 24)
    brute = brute_force(config, SEED, 24)
    assert [tuple(r) for r in exp["windowed"].tolist()] == brute["rows"]
    assert [g[0] for g in exp["groups_touched"]] == brute["rows_in"]
    assert [lf + rt for lf, rt in zip(exp["rows_in_left"],
                                      exp["rows_in_right"])] \
        == brute["rows_in"]
    # every window of the view's first 2.4 s of event time: five of them
    # hold every bid, so the maximum rows repeat across windows
    assert len(exp["rows"]) >= 5
    assert (exp["rows"][:, 1] > 100).all()       # the hot auctions win


def test_rows_are_sql_rows_and_compare_is_exact(ref, config):
    exp = ref.expected(config, SEED, 12)
    sql_rows = [(int(a), int(n)) for a, n in exp["rows"][::-1]]
    same = {"rows_wrong": 0, "events_off": 0,
            "rows_expected": len(sql_rows)}
    assert ref.compare(exp, sql_rows) == same
    short = [(sql_rows[0][0], sql_rows[0][1] - 1)] + sql_rows[1:]
    assert ref.compare(exp, short) == {**same, "rows_wrong": 2,
                                       "events_off": 1}
    assert ref.compare(exp, sql_rows[1:])["rows_wrong"] == 1
    # a multiset: one row twice is one row too many
    assert ref.compare(exp, sql_rows + sql_rows[:1])["rows_wrong"] == 1


def test_control_at_least_once_comes_out_not_correct(ref, config):
    assert config["control"] == "at_least_once"
    exp = ref.expected(config, SEED, 20)
    broken = ref.expected(config, SEED, 20, broken="at_least_once")
    numbers = ref.compare(exp, broken["rows"])
    assert numbers["rows_wrong"] > 0 and numbers["events_off"] > 0
    with pytest.raises(ValueError):
        ref.expected(config, SEED, 20, broken="duplicate_barrier")


def test_a_lost_bid_chunk_can_leave_the_view_standing(ref):
    """Why ``bid_chunk_lost`` (q101's and q104's control) cannot be q5's:
    the view shows each window's maximum rows alone, and a lost chunk of
    4,096 bids often takes no bid from them. At the timed sizes, seed
    1000 after 40 barriers: the same view."""
    timed = run.load_json(ROOT, "benchmark", "configs", "nexmark-q5.json")
    exp = ref.expected(timed, 1000, 40)
    lost = ref.expected(timed, 1000, 40, broken="bid_chunk_lost")
    assert ref.compare(exp, lost["rows"])["rows_wrong"] == 0
    twice = ref.expected(timed, 1000, 40, broken="at_least_once")
    assert ref.compare(exp, twice["rows"])["rows_wrong"] > 0


# -- the program at the tiny sizes, and the control under a whole run ---------

def tiny_cell():
    spec = run.load_json(ROOT, "BENCHMARK.json")
    cell, entry = run.find_cell(spec, "q5_catchup")
    tiny = run.tiny_sizes(run.load_json(ROOT, entry["file"]))
    traffic = run.load_json(ROOT, "benchmark", "traffic", "catchup.json")
    return spec, cell, tiny, traffic


def tiny_run(control: str = "") -> dict:
    spec, cell, tiny, traffic = tiny_cell()
    return run.run_cell(spec, cell, tiny, traffic,
                        {"platform": "cpu", "kind": "cpu", "count": 1},
                        None, seed=SEED, seconds=60.0, traced=False,
                        control=control)


def test_the_program_equals_the_reference_at_tiny_sizes(capsys):
    result = tiny_run()
    assert result["correct"] is True
    compared = result["compared"]
    assert compared["rows_expected"]["value"] >= 5
    assert all(c["value"] == 0 for name, c in compared.items()
               if name != "rows_expected")
    assert set(compared) >= {"rows_wrong", "events_off", "barriers_failed",
                             "checkpoints_missing", "committed_epoch_lag"}
    capsys.readouterr()


def test_the_control_under_a_whole_run_reads_not_correct(capsys):
    result = tiny_run(control="at_least_once")
    assert result["correct"] is False
    assert result["compared"]["rows_wrong"]["value"] > 0
    assert result["compared"]["barriers_failed"]["value"] == 0
    capsys.readouterr()


def test_the_join_takes_in_what_the_reference_counts(ref, tmp_path):
    """Per barrier the program's join counts ``rows_in_left +
    rows_in_right`` (its ``HashJoin.chunks`` span): the AuctionBids and
    MaxBids changelog rows, which the reference counts from the bids
    alone; and the AuctionBids side holds its rows in the fan-out
    arena, the MaxBids side in the bucket arena."""
    from benchmark import system
    from risingwave_tpu.common import tracing
    _spec, _cell, tiny, _traffic = tiny_cell()
    barriers = 24
    sut = system.System(tiny, str(tmp_path / "data"), SEED)
    sut.create()
    for _ in range(barriers):
        sut.barrier()
    history = sut.barrier_history()
    spans = tracing.epoch_spans()
    sut.close()
    got, fanout_rows = [], []
    for h in history[-barriers:]:
        # the ring is the process's: an earlier run of this module left
        # spans under the same epochs, and this session's come last
        args = [s["args"] for s in spans[h["epoch"]]
                if s["name"] == "HashJoin.chunks"][-1:]
        got.append(sum(a["rows_in_left"] + a["rows_in_right"]
                       for a in args))
        fanout_rows.extend(a.get("fanout_rows", 0) for a in args)
    exp = ref.expected(tiny, SEED, barriers)
    assert got == [g[0] for g in exp["groups_touched"]]
    assert sum(exp["rows_in_right"]) > 0
    # the AuctionBids side's rows live in a fan-out arena from the first
    # barrier on: the span names the rows it holds
    assert all(n > 0 for n in fanout_rows) and len(fanout_rows) == barriers


# -- the four readers ---------------------------------------------------------

def recorded() -> dict:
    with open(os.path.join(HERE, "data", "spans_q5_5barriers.json")) as f:
        rec = json.load(f)
    rec["epoch_spans"] = {int(e): spans
                          for e, spans in rec["epoch_spans"].items()}
    return rec


def by_hand(rec: dict, names: tuple) -> float:
    """Median over the recorded barriers of the summed ms of ``names``."""
    values = sorted(
        sum(s["dur_ns"] for s in rec["epoch_spans"][b["ledger"]["epoch"]]
            if s["name"] in names) / 1e6
        for b in rec["barriers"])
    return values[len(values) // 2]


def read(metric: str, ctx: dict):
    return run.load_by_name("layer_metrics", metric).read(ctx)


def ctx_of(rec: dict) -> dict:
    return {"barriers": copy.deepcopy(rec["barriers"]), "traced": [0, 1, 2]}


SPAN_READERS = ("q5join_busy_ms", "q5join_candidate_rows", "maxagg_busy_ms")


@pytest.mark.parametrize("metric", SPAN_READERS)
def test_span_reader_on_recorded_spans(metric, monkeypatch, capsys):
    rec = recorded()
    want = {"q5join_busy_ms": by_hand(
                rec, ("HashJoin.chunks", "HashJoin.barrier")),
            "maxagg_busy_ms": by_hand(
                rec, ("MaterializedAgg.chunks", "MaterializedAgg.barrier")),
            # the five barriers' scans read 0, 0, 0, 40 and 270 rows
            "q5join_candidate_rows": 0.0}[metric]
    monkeypatch.setattr(program_spans, "load", lambda: rec["epoch_spans"])
    assert read(metric, ctx_of(rec)) == pytest.approx(want, abs=1e-6)
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines()]
    if metric == "q5join_busy_ms":
        assert {"q5join_busy": {
            "fanout_probes": 0, "candidate_rows": 0, "fanout_pairs": 0,
            "rows_out": 0, "fanout_rows": 4610,
            "fanout_fill_pct": pytest.approx(7.0343017578125),
            "rewinds": 0, "grows": 0}} in lines
    if metric == "maxagg_busy_ms":
        assert {"maxagg_busy": {"rows_in": 745,
                                "groups_recomputed": 5}} in lines


@pytest.mark.parametrize("metric", SPAN_READERS)
def test_span_reader_gives_nothing_for_a_program_without_the_span(
        metric, monkeypatch, capsys):
    """No join or max agg span in the window (a program without them)
    reads as nothing and does not raise; a program with no ring at all
    likewise."""
    rec = recorded()
    gone = {e: [s for s in spans if not s["name"].startswith(
        ("join.", "HashJoin.", "MaterializedAgg."))]
        for e, spans in rec["epoch_spans"].items()}
    monkeypatch.setattr(program_spans, "load", lambda: gone)
    assert read(metric, ctx_of(rec)) is None
    monkeypatch.setattr(program_spans, "load", lambda: None)
    assert read(metric, ctx_of(rec)) is None
    capsys.readouterr()


def test_readers_on_a_program_without_the_new_counts(monkeypatch, capsys):
    """A program whose spans lack the fan-out and max agg counts: the
    candidate rows read as nothing, the busy times read what they read
    and print the counts as null."""
    rec = recorded()
    for spans in rec["epoch_spans"].values():
        for s in spans:
            if s["name"] in ("HashJoin.chunks", "MaterializedAgg.chunks"):
                s["args"] = {k: v for k, v in s["args"].items()
                             if not k.startswith(("fanout_", "candidate_"))
                             and k not in ("rows_in", "groups_recomputed")}
    monkeypatch.setattr(program_spans, "load", lambda: rec["epoch_spans"])
    assert read("q5join_candidate_rows", ctx_of(rec)) is None
    assert read("q5join_busy_ms", ctx_of(rec)) > 0
    assert read("maxagg_busy_ms", ctx_of(rec)) > 0
    out = capsys.readouterr().out
    assert '"candidate_rows": null' in out and '"rows_in": null' in out


def test_candidate_reader_owes_the_count_on_every_barrier(monkeypatch,
                                                          capsys):
    rec = recorded()
    spans = rec["epoch_spans"][rec["barriers"][1]["ledger"]["epoch"]]
    for s in spans:
        if s["name"] == "HashJoin.chunks":
            del s["args"]["candidate_rows"]
    monkeypatch.setattr(program_spans, "load", lambda: rec["epoch_spans"])
    with pytest.raises(LookupError, match="candidate_rows"):
        read("q5join_candidate_rows", ctx_of(rec))
    capsys.readouterr()


def roofline_ctx(program_s: dict) -> dict:
    return {"trace": {"program_s": program_s},
            "config": {"name": "nexmark-q5", "trace_programs": {
                "join_epoch": ["jit_join_step_right", "jit_join_gather"]}},
            "peaks": {"hbm_bytes_per_s": 819e9},
            "traced": [0, 1], "first_barrier": 10,
            "groups_touched": [(0, 0)] * 10 + [(9000, 4), (8000, 2),
                                               (999_999, 9)]}


def test_q5join_epoch_roofline_by_hand(capsys):
    # 17,000 rows x (2 x 27 + 10) B + 6 rows x 45 B = 1,088,270 B over
    # 0.2 s of the named programs
    value = read("q5join_epoch_roofline", roofline_ctx(
        {"jit_join_step_right": 0.15, "jit_join_gather": 0.05,
         "jit_apply_chunk": 9.0}))
    want = 17000 * 64 + 6 * 45
    assert value == pytest.approx(100 * (want / 819e9) / 0.2, rel=1e-9)
    line = json.loads(capsys.readouterr().out)["q5join_epoch_roofline"]
    assert line["rows_in"] == 17000 and line["rows_out"] == 6
    assert line["traced_barriers"] == 2


def test_q5join_epoch_roofline_missing_program_ends_the_run(capsys):
    with pytest.raises(LookupError, match="jit_join_gather"):
        read("q5join_epoch_roofline",
             roofline_ctx({"jit_join_step_right": 0.075}))
    ctx = roofline_ctx({"jit_join_step_right": 0.075})
    ctx["config"] = {"name": "x", "trace_programs": {}}
    with pytest.raises(LookupError, match="trace_programs.join_epoch"):
        read("q5join_epoch_roofline", ctx)
    ctx["trace"] = None
    assert read("q5join_epoch_roofline", ctx) is None
    capsys.readouterr()


def test_every_new_metric_lists_only_the_q5_cell():
    spec = run.load_json(ROOT, "BENCHMARK.json")
    new = [m for m in spec["per_layer"] if m["name"] in NEW]
    assert sorted(m["name"] for m in new) == sorted(NEW)
    assert all(m["workloads"] == ["q5_catchup"] for m in new)
    assert all(m["moves"] == "events_per_s" for m in new)
    assert not [m["name"] for m in spec["per_layer"]
                if "q5_catchup" in m.get("workloads", [])
                and m["name"] not in NEW]
    cell, entry = run.find_cell(spec, "q5_catchup")
    assert cell["chips"] == 1 and cell["traffic"] == "catchup"
    assert len(cell["why"]) <= 200
    config = run.load_json(ROOT, entry["file"])
    assert entry["source"] == config["source"] and entry["reduced"] == []
    assert "q5.slt.part" in entry["source"] and len(entry["source"]) <= 200
    # the bid source, its draws and chunk size are q5-core-exec's
    exec_ = run.load_json(ROOT, "benchmark", "configs",
                          "nexmark-q5core-exec.json")
    for key in ("nexmark", "ddl", "rows_per_chunk"):
        assert config[key] == exec_[key], key
    assert config["rw_toml"]["streaming.chunk_capacity"] == 4096
    assert "num >= MaxBids.maxn" in config["mv"]
    assert config["mv"].count("HOP(bid, date_time, INTERVAL '2' SECOND, "
                              "INTERVAL '10' SECOND)") == 2
    assert np.all([k in config["assumed"] for k in config["rw_toml"]
                   if k.startswith("streaming.") and k not in (
                       "streaming.checkpoint_frequency",
                       "streaming.coschedule")])
