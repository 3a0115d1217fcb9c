"""Compactor worker: the dedicated, stateless LSM-compaction role.

Counterpart of the reference's standalone compactor node (reference:
src/storage/compactor/src/server.rs:57 — a stateless worker that pulls
``CompactTask``s from the meta's Hummock manager, rewrites overlapping
L0 runs into sorted L1 runs against the SHARED object store, and reports
results back; the meta commits the version swap). Completing the
four-role cluster shape: frontend / compute / compactor / meta.

Process protocol (length-prefixed JSON frames, rpc/wire.py):

    meta → compactor   {"type":"compact_task","rid",
                        "task": CompactTask.to_wire(), "delay_ms"?}
    compactor → meta   {"type":"reply","rid","ok":true,
                        "outputs":[names],"n_inputs","duration_ms"}
    meta → compactor   {"type":"stats","rid"} → counters + span drain
    meta → compactor   {"type":"shutdown","rid"}

The compactor never touches the version manifest: it only reads input
SSTs and writes output SSTs (orphans until the meta's version swap
references them), so a ``kill -9`` at ANY point leaves the store exactly
at its last committed version — the meta cancels the task and
reschedules; half-written outputs are vacuum food.

``CompactorClient`` is the meta/session-side handle: subprocess spawn +
synchronous request/reply socket (mirrors frontend/remote.py's
RemoteWorker, minus the data plane the compactor doesn't have).
"""

from __future__ import annotations

import asyncio
import os
import signal
import socket
import subprocess
import sys
import time
from typing import List, Optional

from ..rpc.wire import (
    read_frame, read_frame_sync, write_frame, write_frame_sync,
)
from ..storage.hummock import CompactTask, run_compact_task
from ..storage.object_store import open_object_store


class CompactorHost:
    """One compactor process: object store handle + task loop."""

    def __init__(self, data_dir: str, worker_id: int = 0):
        # retried IO: a transient read/write fault mid-merge costs a
        # backoff, not a failed task report + rescheduled compaction
        self.store = open_object_store(data_dir)
        self.worker_id = worker_id
        self.stats = {
            "tasks_completed": 0,
            "tasks_failed": 0,
            "ssts_written": 0,
            "busy_ms": 0.0,
        }

    def handle_compact(self, frame: dict) -> dict:
        task = CompactTask.from_wire(frame["task"])
        delay = frame.get("delay_ms")
        if delay:
            # test hook: widen the in-flight window deterministically so
            # chaos tests can kill -9 mid-task (tests/test_compactor.py)
            time.sleep(delay / 1000)
        t0 = time.perf_counter()
        try:
            outputs = run_compact_task(self.store, task)
        except Exception as e:  # noqa: BLE001 - shipped to the meta side
            self.stats["tasks_failed"] += 1
            return {"ok": False, "error": f"{type(e).__name__}: {e}"}
        dur = (time.perf_counter() - t0) * 1e3
        self.stats["tasks_completed"] += 1
        self.stats["ssts_written"] += len(outputs)
        self.stats["busy_ms"] += dur
        return {"ok": True, "outputs": outputs,
                "n_inputs": len(task.inputs),
                "duration_ms": round(dur, 3)}

    def handle_stats(self) -> dict:
        from ..common.tracing import GLOBAL_TRACE
        return {"ok": True, "worker_id": self.worker_id,
                "compactor": dict(self.stats),
                "spans": [s.to_dict() for s in GLOBAL_TRACE.drain()]}

    async def handle_conn(self, reader: asyncio.StreamReader,
                          writer: asyncio.StreamWriter) -> None:
        try:
            while True:
                frame = await read_frame(reader)
                if frame is None:
                    break                      # meta side went away
                t = frame.get("type")
                if t == "compact_task":
                    # the merge is CPU+IO bound: run it off the event
                    # loop so a long task doesn't starve stats requests
                    resp = await asyncio.get_running_loop()\
                        .run_in_executor(None, self.handle_compact, frame)
                elif t == "stats":
                    resp = self.handle_stats()
                elif t == "shutdown":
                    await write_frame(writer, {"type": "reply",
                                               "rid": frame.get("rid"),
                                               "ok": True})
                    break
                else:
                    resp = {"ok": False, "error": f"unknown frame {t!r}"}
                resp.update({"type": "reply", "rid": frame.get("rid")})
                await write_frame(writer, resp)
        finally:
            writer.close()


async def amain(data_dir: str, worker_id: int, port: int) -> None:
    host = CompactorHost(data_dir, worker_id)
    done = asyncio.Event()

    async def conn(reader, writer):
        try:
            await host.handle_conn(reader, writer)
        finally:
            done.set()

    server = await asyncio.start_server(conn, "127.0.0.1", port)
    actual = server.sockets[0].getsockname()[1]
    print(f"COMPACTOR_READY {actual}", flush=True)
    async with server:
        await done.wait()


def main(argv=None) -> None:
    import argparse
    ap = argparse.ArgumentParser(
        description="dedicated Hummock-lite compaction worker")
    ap.add_argument("--data-dir", required=True,
                    help="shared object-store root (same dir the "
                         "session's state store writes)")
    ap.add_argument("--worker-id", type=int, default=0)
    ap.add_argument("--port", type=int, default=0)
    args = ap.parse_args(argv)
    asyncio.run(amain(args.data_dir, args.worker_id, args.port))


# -- meta/session-side client -------------------------------------------------

class CompactorDied(RuntimeError):
    pass


class CompactorClient:
    """Spawn + drive one compactor process, synchronously (the caller is
    the session's background compaction pump thread, never the barrier
    path)."""

    SPAWN_TIMEOUT_S = 60.0

    def __init__(self, data_dir: str, worker_id: int = 0):
        self.data_dir = data_dir
        self.worker_id = worker_id
        self.proc: Optional[subprocess.Popen] = None
        self.sock: Optional[socket.socket] = None
        self.port: Optional[int] = None
        self._rid = 0
        self.dead = True

    def spawn(self) -> None:
        env = dict(os.environ)
        # host-only by design: the compactor never touches an
        # accelerator, and a chip belongs to one process (the session's)
        env["JAX_PLATFORMS"] = "cpu"
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "risingwave_tpu.worker.compactor",
             "--data-dir", self.data_dir,
             "--worker-id", str(self.worker_id), "--port", "0"],
            stdout=subprocess.PIPE, stderr=None, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))))
        assert self.proc.stdout is not None
        deadline = time.monotonic() + self.SPAWN_TIMEOUT_S
        import select
        buf = b""
        fd = self.proc.stdout.fileno()
        port = None
        while time.monotonic() < deadline:
            ready, _, _ = select.select(
                [fd], [], [], max(0.05, deadline - time.monotonic()))
            if not ready:
                continue
            chunk = os.read(fd, 4096)
            if not chunk:
                raise CompactorDied(
                    f"compactor {self.worker_id} exited during startup "
                    f"(rc={self.proc.poll()})")
            buf += chunk
            for line in buf.decode(errors="replace").splitlines():
                if line.startswith("COMPACTOR_READY"):
                    port = int(line.split()[1])
                    break
            if port is not None:
                break
        if port is None:
            self.proc.kill()
            raise CompactorDied(
                f"compactor {self.worker_id} startup timed out")
        self.port = port
        self.sock = socket.create_connection(("127.0.0.1", port))
        self.dead = False

    def respawn(self) -> None:
        """Fresh process over the same shared store (it is stateless —
        nothing to recover)."""
        self.terminate()
        self.spawn()

    def terminate(self) -> None:
        if self.sock is not None:
            try:
                self.sock.close()
            except OSError:
                pass
            self.sock = None
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self.dead = True

    def kill9(self) -> None:
        """Chaos hook: SIGKILL mid-task (tests/test_compactor.py)."""
        if self.proc is not None and self.proc.poll() is None:
            os.kill(self.proc.pid, signal.SIGKILL)
            self.proc.wait()
        self.dead = True

    # -- request/reply ---------------------------------------------------------

    def request(self, obj: dict, timeout: Optional[float] = None) -> dict:
        if self.dead or self.sock is None:
            raise CompactorDied("compactor is down")
        self._rid += 1
        obj = {**obj, "rid": self._rid}
        try:
            self.sock.settimeout(timeout)
            # compactor control frames ride the fault plane too
            # (rpc/faults.py link "s->c<k>"): a chaos schedule can drop
            # or delay the meta→compactor conversation deterministically
            write_frame_sync(self.sock, obj,
                             link=f"s->c{self.worker_id}")
            while True:
                resp = read_frame_sync(self.sock)
                if resp is None:
                    raise CompactorDied("compactor connection lost")
                if resp.get("rid") == self._rid:
                    return resp
        except (OSError, socket.timeout) as e:
            self.dead = True
            raise CompactorDied(f"compactor request failed: {e}") from e

    def compact(self, task: CompactTask,
                delay_ms: Optional[int] = None,
                timeout: Optional[float] = 600.0) -> List[str]:
        req: dict = {"type": "compact_task", "task": task.to_wire()}
        if delay_ms:
            req["delay_ms"] = delay_ms
        resp = self.request(req, timeout=timeout)
        if resp.get("ok") is False:
            raise RuntimeError(
                f"compactor {self.worker_id}: {resp.get('error')}")
        return list(resp["outputs"])

    def get_stats(self, timeout: float = 10.0) -> dict:
        return self.request({"type": "stats"}, timeout=timeout)

    def shutdown(self) -> None:
        try:
            self.request({"type": "shutdown"}, timeout=5.0)
        except (CompactorDied, RuntimeError):
            pass
        self.terminate()


if __name__ == "__main__":
    main()
