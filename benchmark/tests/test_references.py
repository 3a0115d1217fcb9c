"""The plain references at tiny size against straightforward dictionary
logic, and each configuration's control against its reference:
the control has to come out NOT correct."""

import collections
import json
import os

import numpy as np
import pytest

from benchmark import run

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return run.tiny_sizes(json.load(f))


def all_configs() -> list:
    return sorted(f[:-5] for f in os.listdir(os.path.join(HERE, "configs")))


Q5CORE = ["nexmark-q5core-fused", "nexmark-q5core-exec"]


@pytest.mark.parametrize("name", Q5CORE)
def test_q5core_reference_equals_a_dictionary_count(name):
    config = tiny(name)
    ref = run.load_by_name("reference", config["reference"])
    barriers, seed = 35, 3_000_000_019    # more than one block of barriers
    nx = config["nexmark"]
    counts = collections.Counter()
    touched = []
    n_events = 0
    for auction, ts in ref.bid_stream(config, seed, barriers):
        for a_row, t_row in zip(auction, ts):
            keys = [(int(t) // nx["window_us"] * nx["window_us"], int(a))
                    for a, t in zip(a_row, t_row)]
            counts.update(keys)
            touched.append(len(set(keys)))
            n_events += len(keys)
    per_barrier = config["rows_per_chunk"]["bid"] * config["chunks_per_tick"]
    assert n_events == barriers * per_barrier
    exp = ref.expected(config, seed, barriers)
    want = np.asarray(sorted((w, a, c) for (w, a), c in counts.items()),
                      np.int64)
    assert np.array_equal(exp["rows"], want)
    assert exp["groups_touched"] == touched
    assert ref.compare(exp, [tuple(r) for r in want[::-1].tolist()]) == {
        "rows_wrong": 0, "events_off": 0, "rows_expected": len(want)}
    # ~90 % of bids go to the hot auction of their moment
    assert 0.85 < sum(c for (_w, a), c in counts.items()
                      if a % 100 == 0) / n_events < 0.95


@pytest.mark.parametrize("name", Q5CORE)
def test_q5core_stream_is_the_seed_s(name):
    config = tiny(name)
    ref = run.load_by_name("reference", config["reference"])
    a = ref.expected(config, 11, 4)["rows"]
    assert np.array_equal(a, ref.expected(config, 11, 4)["rows"])
    assert not np.array_equal(a, ref.expected(config, 12, 4)["rows"])


def test_host_stream_replays_the_program_s_generator():
    """The replay imports nothing of the program; this test does, to show
    that the two streams are one."""
    from risingwave_tpu.connector.nexmark import (
        NexmarkConfig, NexmarkGenerator,
    )
    config = tiny("nexmark-q5core-exec")
    ref = run.load_by_name("reference", config["reference"])
    rows, k = config["rows_per_chunk"]["bid"], config["chunks_per_tick"]
    barriers, seed = 34, 2_400_000_011     # more than one block
    gen = NexmarkGenerator(NexmarkConfig(chunk_capacity=rows), seed=seed)
    auction, ts = [], []
    for _ in range(barriers * k):
        chunk = gen.next_bid_chunk()
        auction.append(np.asarray(chunk.columns[0].data))
        ts.append(np.asarray(chunk.columns[5].data))
    blocks = list(ref.bid_stream(config, seed, barriers))
    assert np.array_equal(np.concatenate([a.reshape(-1) for a, _t in blocks]),
                          np.concatenate(auction))
    assert np.array_equal(np.concatenate([t.reshape(-1) for _a, t in blocks]),
                          np.concatenate(ts))


@pytest.mark.parametrize("name", all_configs())
@pytest.mark.parametrize("seed", [11, 1_000_000_007, 3_000_000_019])
def test_control_comes_out_not_correct(name, seed):
    config = tiny(name)
    ref = run.load_by_name("reference", config["reference"])
    barriers = 20
    exp = ref.expected(config, seed, barriers)
    sound = ref.compare(exp, exp["rows"])
    assert sound["rows_wrong"] == 0 and sound["rows_expected"] > 0
    broken = ref.expected(config, seed, barriers,
                          broken=config["control"])["rows"]
    assert ref.compare(exp, broken)["rows_wrong"] > 0
    with pytest.raises(ValueError):
        ref.expected(config, seed, barriers, broken="no_such_control")
