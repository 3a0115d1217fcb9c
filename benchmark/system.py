"""The one place of the benchmark that knows the program.

Everything the harness takes from ``risingwave_tpu`` goes through
``System``: the served path (a ``Session`` over a layered config, SQL
text through ``run_sql``, one synchronous ``tick()`` per barrier), the
barrier ledger as the SQL relation ``rw_barrier_history`` exposes it, and
the durable store's committed epoch. The tests put a faulty stand-in in
its place (``benchmark/tests/test_faults.py``).
"""

from __future__ import annotations

import json
import os

#: the ledger columns the per-layer readers get, as rw_barrier_history
#: names them
LEDGER_COLUMNS = ("epoch", "checkpoint", "result", "total_ms", "inject_ms",
                  "pending_ms", "collect_ms", "commit_ms",
                  "storage_commit_ms")


def enable_compile_cache() -> str:
    """JAX's persistent cache: where ``JAX_COMPILATION_CACHE_DIR`` says,
    else the fixed ``.jax_cache/`` inside the checkout (the program's own
    resolver); every program is cached, however quick its compile."""
    import jax
    from risingwave_tpu.common.compile_cache import (
        enable_compile_cache as program_cache,
    )
    cache_dir = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


class System:
    """One deployment (a configuration file) opened on a fresh
    ``data_dir``."""

    def __init__(self, config: dict, data_dir: str, seed: int):
        from risingwave_tpu.common.config import load_config
        from risingwave_tpu.frontend import Session
        self.config = config
        self.data_dir = data_dir
        overrides = dict(config["rw_toml"])
        overrides["storage.data_dir"] = data_dir
        self.session = Session(rw_config=load_config(None, **overrides),
                               seed=seed,
                               chunks_per_tick=config["chunks_per_tick"])

    def create(self) -> None:
        for ddl in self.config["ddl"]:
            self.session.run_sql(ddl)
        self.session.run_sql(self.config["mv"])

    def barrier(self) -> None:
        """Feed every source its chunks, inject one barrier and wait for
        it: dispatch, collect, flush, materialize and, on a checkpoint
        barrier, commit."""
        self.session.tick()

    def read_back(self) -> list:
        return self.session.run_sql(self.config["select"])

    def barrier_history(self) -> list:
        """One dict per completed barrier, oldest first."""
        rows = self.session.run_sql(
            f"SELECT {', '.join(LEDGER_COLUMNS)} FROM rw_barrier_history")
        return [dict(zip(LEDGER_COLUMNS, r)) for r in rows]

    def committed_epoch(self):
        """The newest checkpoint the durable store holds, read from the
        store's own manifest on disk (None where there is none)."""
        path = os.path.join(self.data_dir, "manifest.json")
        if not os.path.exists(path):
            return None
        with open(path) as f:
            return json.load(f).get("committed_epoch")

    def close(self) -> None:
        self.session.close()
