"""ISSUE 3 satellite: a worker wedged before replying (SIGSTOP — socket
open, no frames) must trip the epoch deadline + heartbeat-TTL scoped
recovery instead of deadlocking ``wait_epoch``/``handle_create_job``
forever."""

import os
import signal

import pytest

from risingwave_tpu.common.config import FaultConfig
from risingwave_tpu.frontend import Session


@pytest.mark.slow
def test_wedged_worker_trips_scoped_recovery(tmp_path):
    s = Session(data_dir=str(tmp_path / "db"), workers=1,
                checkpoint_frequency=2,
                fault_config=FaultConfig(worker_epoch_timeout_s=2.0,
                                         worker_request_timeout_s=60.0))
    try:
        s.run_sql("CREATE TABLE t (k BIGINT PRIMARY KEY, v BIGINT)")
        s.run_sql("CREATE MATERIALIZED VIEW m AS "
                  "SELECT sum(v) AS n FROM t")        # worker-hosted
        s.run_sql("INSERT INTO t VALUES (1, 10)")
        s.run_sql("FLUSH")
        assert s.mv_rows("m") == [(10,)]

        w = s.workers[0]
        wedged_pid = w.proc.pid
        os.kill(wedged_pid, signal.SIGSTOP)           # wedged, not dead

        # barriers keep completing: the epoch deadline declares the
        # worker failed (fail-stop) and the TTL detector recovers the job
        # on subsequent ticks — none of these calls may hang
        s.run_sql("INSERT INTO t VALUES (2, 5)")
        recovered = False
        for _ in range(12):
            s.tick()
            if not w.dead and w.proc.pid != wedged_pid:
                recovered = True
                break
        assert recovered, "worker was not respawned after wedging"
        s.run_sql("FLUSH")
        assert s.mv_rows("m") == [(15,)]              # nothing lost
    finally:
        s.close()


def test_request_timeout_raises_instead_of_hanging(tmp_path):
    """A control request against a wedged worker raises WorkerDied after
    the configured deadline (short here) rather than awaiting forever."""
    import pytest

    from risingwave_tpu.frontend.remote import WorkerDied
    s = Session(data_dir=str(tmp_path / "db"), workers=1,
                fault_config=FaultConfig(worker_request_timeout_s=1.5,
                                         worker_epoch_timeout_s=2.0))
    try:
        w = s.workers[0]
        os.kill(w.proc.pid, signal.SIGSTOP)
        with pytest.raises(WorkerDied, match="timed out"):
            s._await(w.request({"type": "scan", "name": "nope"}))
        assert w.dead
    finally:
        s.close()


def test_worker_that_cannot_get_its_platform_fails_fast(tmp_path,
                                                        monkeypatch):
    """One JAX process per chip: a worker is spawned on the session's own
    JAX platform, named explicitly, and claims its backend BEFORE
    WORKER_READY. A worker that cannot get it (on a one-chip host the
    session holds the chip) must end the spawn in seconds with
    WorkerDied — the worker's own error on stderr — not wait out
    SPAWN_TIMEOUT_S, and never come up on another platform."""
    import time

    import jax

    from risingwave_tpu.frontend.remote import RemoteWorker, WorkerDied

    monkeypatch.setattr(jax, "default_backend",
                        lambda: "definitely_not_a_backend")
    w = RemoteWorker(str(tmp_path), 0, loop=None)
    t0 = time.monotonic()
    with pytest.raises(WorkerDied, match="exited during startup"):
        w.spawn()
    assert time.monotonic() - t0 < RemoteWorker.SPAWN_TIMEOUT_S / 2
    assert w.proc.poll() not in (None, 0)


def test_worker_that_closes_stdout_but_lingers_is_killed(tmp_path,
                                                         monkeypatch):
    """The spawn deadline also bounds the reap: a worker that closes
    stdout before WORKER_READY and then does not exit is killed when the
    deadline passes, instead of blocking ``spawn()`` in ``wait()``."""
    import sys
    import time

    from risingwave_tpu.frontend.remote import RemoteWorker, WorkerDied

    fake = tmp_path / "lingering_worker.sh"
    fake.write_text("#!/bin/sh\nexec 1>&-\nsleep 60\n")
    fake.chmod(0o755)
    monkeypatch.setattr(sys, "executable", str(fake))
    monkeypatch.setattr(RemoteWorker, "SPAWN_TIMEOUT_S", 2.0)
    w = RemoteWorker(str(tmp_path), 0, loop=None)
    t0 = time.monotonic()
    with pytest.raises(WorkerDied, match="exited during startup"):
        w.spawn()
    assert time.monotonic() - t0 < 10
    assert w.proc.poll() is not None          # reaped, nothing left running
