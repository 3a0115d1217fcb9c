"""The fault a one-chip cell cannot have (``test_faults.py``'s last
line): the exchange between chips loses rows.

``exchange_drops`` — in ONE barrier of the window the rows shard 0 sends
to shard 1 are dropped inside the all-to-all's send buffer: the step
runs, nothing fails, every shard still owns exactly its own groups, and
the MV counts too few. ``correct`` has to come out false, by the rows.

The mesh cell needs four devices; on the CPU they are virtual ones, which
XLA makes only if it is told before JAX starts. This file says so as it is
imported (collection comes before any test runs), so a hand run of
``python -m pytest benchmark/tests -q`` gives ``test_faults.py``'s five
faults of the mesh cell their devices too; a run of that file alone needs
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` itself.
"""

import os

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4"
                               ).strip()

import pytest  # noqa: E402

from benchmark import run, system  # noqa: E402
from benchmark.tests.test_faults import FAULT_AT, ROOT  # noqa: E402

CELL = "q5core_exec_mesh4_catchup"


def exchange_drops(sender: int = 0, target: int = 1):
    """Make the exchange drop the rows ``sender`` has for ``target``, in
    every sharded step traced from now on; returns the undo. The step is
    a cached jit: both ways JAX's caches are cleared, so the next barrier
    traces its programs anew."""
    import jax
    import jax.numpy as jnp
    from risingwave_tpu.parallel import sharded_agg

    sendbuf = sharded_agg.chunk_sendbuf

    def lossy(chunk, n_shards, key_idx):
        send = sendbuf(chunk, n_shards, key_idx)
        me = jax.lax.axis_index(sharded_agg.SHARD_AXIS)
        lost = (jnp.arange(n_shards)[:, None] == target) & (me == sender)
        return send.replace(vis=send.vis & ~lost)

    sharded_agg.chunk_sendbuf = lossy
    jax.clear_caches()

    def undo():
        sharded_agg.chunk_sendbuf = sendbuf
        jax.clear_caches()
    return undo


class Lossy(system.System):
    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.n = 0

    def barrier(self):
        self.n += 1
        if self.n == FAULT_AT:
            undo = exchange_drops()
            try:
                self.session.tick()
            finally:
                undo()
        else:
            self.session.tick()


def test_rows_dropped_in_the_exchange_come_out_not_correct(monkeypatch,
                                                           capsys):
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=4"
                    " before JAX starts")
    spec = run.load_json(ROOT, "BENCHMARK.json")
    cell, entry = run.find_cell(spec, CELL)
    config = run.tiny_sizes(run.load_json(ROOT, entry["file"]))
    traffic = run.load_json(ROOT, "benchmark", "traffic",
                            f"{cell['traffic']}.json")
    monkeypatch.setattr(system, "System", Lossy)
    device = {"platform": "cpu", "kind": "cpu", "count": 4}
    result = run.run_cell(spec, cell, config, traffic, device, None,
                          seed=1_000_000_007, seconds=60.0, traced=False)
    compared = result["compared"]
    assert result["correct"] is False
    assert compared["rows_wrong"]["value"] > 0
    assert compared["events_off"]["value"] > 0
    # nothing else notices: every barrier completed, every checkpoint
    # committed
    assert result["failed"] == 0
    assert compared["barriers_failed"]["value"] == 0
    assert compared["checkpoints_missing"]["value"] == 0
    capsys.readouterr()
