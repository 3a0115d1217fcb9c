"""The work function against hand-counted bytes, and the peaks table."""

import json
import os

import pytest

from benchmark import run, work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_grouped_agg_epoch_hand_counted():
    params = {"input_bytes_per_event": 16, "slot_key_bytes": 16,
              "slot_lane_bytes": 8, "flush_row_bytes": 24}
    # 65,536 events: 16 B of columns + a 24 B slot read and written = 64 B
    # each; 4,000 flushed groups of one 24 B row
    w = work.grouped_agg_epoch(params, 65536, 4000)
    assert w == {"flops": 0, "bytes": 65536 * 64 + 4000 * 24}
    assert w["bytes"] == 4_290_304


def test_least_seconds_names_the_binding_roof():
    peaks = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    secs, roof = work.least_seconds({"flops": 0, "bytes": 819e9}, peaks)
    assert (secs, roof) == (1.0, "hbm")
    secs, roof = work.least_seconds({"flops": 394e12, "bytes": 819e9}, peaks)
    assert (secs, roof) == (2.0, "flops")


def test_unknown_device_kind_raises():
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    assert work.load_peaks(table, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="TPU v9 imaginary"):
        work.load_peaks(table, "TPU v9 imaginary")


def roofline_ctx(program_s: dict) -> dict:
    config = {"name": "c", "work": "grouped_agg_epoch",
              "work_params": {"input_bytes_per_event": 16,
                              "slot_key_bytes": 16, "slot_lane_bytes": 8,
                              "flush_row_bytes": 24},
              "trace_programs": {"agg_epoch": ["jit_epoch", "jit_gather"]}}
    return {"trace": {"program_s": program_s}, "config": config,
            "traced": [0, 1], "first_barrier": 10,
            "groups_touched": [0] * 10 + [4000, 4000],
            "events_per_barrier": 65536,
            "peaks": {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}}


def test_roofline_reader_hand_counted(capsys):
    reader = run.load_by_name("layer_metrics", "agg_epoch_roofline")
    ctx = roofline_ctx({"jit_epoch": 0.19, "jit_gather": 0.01,
                        "jit_other": 5.0})
    # two barriers of 4,290,304 bytes at 819 GB/s over 0.2 device seconds
    assert reader.read(ctx) == pytest.approx(
        100 * (2 * 4_290_304 / 819e9) / 0.2)
    said = json.loads(capsys.readouterr().out)["agg_epoch_roofline"]
    assert said["program_s_summed"] == {"jit_epoch": 0.19, "jit_gather": 0.01}
    assert reader.read({**ctx, "trace": None}) is None


@pytest.mark.parametrize("program_s", [{"jit_epoch": 0.19},
                                       {"jit_gather": 0.01, "jit_x": 1.0}])
def test_roofline_reader_refuses_a_trace_that_lacks_a_program(program_s):
    reader = run.load_by_name("layer_metrics", "agg_epoch_roofline")
    with pytest.raises(LookupError, match="no device time"):
        reader.read(roofline_ctx(program_s))
    ctx = roofline_ctx({"jit_epoch": 0.19, "jit_gather": 0.01})
    del ctx["config"]["trace_programs"]
    with pytest.raises(LookupError, match="trace_programs"):
        reader.read(ctx)
