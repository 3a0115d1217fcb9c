"""Client side of the out-of-process UDF plane.

``UdfPlane`` is the process-global boundary every registered UDF call
crosses (``expr/udf.py`` routes here; the ``udf-boundary`` lint keeps
it that way). It owns the robustness contract the other planes already
have (docs/robustness.md "UDF isolation plane"):

* per-call DEADLINES (``[udf] call_timeout_s``) — a UDF that hangs,
  busy-loops, or segfaults its server never stalls the caller past the
  deadline;
* crash/timeout detection → KILL + seeded RESPAWN (the fresh server is
  re-seeded with every live registration) + bounded-retry REPLAY of the
  batch — UDF calls are pure per-row, so replaying a batch is safe;
* exhausted retries surface a TYPED error (``UdfTimeoutError`` /
  ``UdfCallError``) that fails the statement, never the epoch loop;
* GENERATION FENCING — every frame carries (gen, rid); a stale server
  incarnation's late or chaos-duplicated reply is dropped, counted,
  never taken for a fresh one;
* BACKPRESSURE — at most ``max_inflight`` batches inside the boundary;
  excess callers fail typed (``UdfOverloadedError``) after
  ``queue_timeout_s`` instead of queueing unboundedly.

The wire rides rpc/wire.py sync frames on the ``s->udf`` fault-plane
link (replies: ``udf->s``), so a seeded ChaosSchedule drops/delays/
duplicates UDF traffic exactly like any internal link. Failpoint sites:
``udf.spawn``, ``udf.call``, ``udf.reply``, ``udf.respawn`` client-side
and ``udf.server.eval`` in the server process.

``[udf] mode = "inproc"`` is the documented DEGRADED mode: the same
decode + evaluator code runs in-process (bit-exact with the wire path),
with none of the isolation.
"""

from __future__ import annotations

import atexit
import itertools
import os
import socket
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..common.config import UdfConfig
from ..common.failpoint import fail_point
from ..rpc.wire import read_frame_sync, write_frame_sync
from .registry import (
    UDF_SPECS, UdfNotPortableError, UdfSpec, get_udf, ship_function,
    spec_to_wire,
)
from .runtime import decode_string_args, eval_udf_batch

#: fault-plane link of the client→server direction (docs/robustness.md)
CALL_LINK = "s->udf"


class UdfError(RuntimeError):
    """Base of the plane's typed errors: fails the STATEMENT that
    evaluated the UDF; the epoch loop and every other job keep going."""


class UdfCallError(UdfError):
    """Retries exhausted: the batch could not be evaluated despite
    kill+respawn+replay."""


class UdfTimeoutError(UdfCallError):
    """Every attempt missed the per-call deadline (hanging/busy-looping
    user code, or a link eating frames faster than the retry budget)."""


class UdfOverloadedError(UdfError):
    """Backpressure: more than ``max_inflight`` batches were already
    inside the boundary for longer than ``queue_timeout_s``."""


class UdfServerError(UdfError):
    """The user function RAISED on the server. Deterministic, so it is
    surfaced immediately — no respawn/replay cycles are burned on it."""


class _LinkDown(Exception):
    """Internal: connection lost / EOF mid-conversation."""


class _CallTimeout(Exception):
    """Internal: the per-call deadline elapsed without a valid reply."""


class _ServerHandle:
    """One server incarnation: subprocess (or external addr) + sync
    socket. Mirrors worker/compactor client handles."""

    def __init__(self) -> None:
        self.proc: Optional[subprocess.Popen] = None
        self.sock: Optional[socket.socket] = None
        self.port: Optional[int] = None
        self.external = False

    def spawn(self, spawn_timeout_s: float,
              trace_path: Optional[str]) -> None:
        env = dict(os.environ)
        # host-only by design: UDF evaluation is host numpy, and a chip
        # belongs to one process (the session's)
        env["JAX_PLATFORMS"] = "cpu"
        # by-reference function shipping resolves modules against the
        # CLIENT's import path (test-local modules included)
        env["PYTHONPATH"] = os.pathsep.join(
            [p for p in sys.path if p] +
            [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
        argv = [sys.executable, "-m", "risingwave_tpu.udf.server",
                "--port", "0"]
        if trace_path:
            argv += ["--trace-path", trace_path]
        self.proc = subprocess.Popen(
            argv, stdout=subprocess.PIPE, stderr=None, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))))
        assert self.proc.stdout is not None
        import select
        deadline = time.monotonic() + spawn_timeout_s
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
                raise _LinkDown(
                    f"UDF server exited during startup "
                    f"(rc={self.proc.poll()})")
            buf += chunk
            for line in buf.decode(errors="replace").splitlines():
                if line.startswith("UDF_READY"):
                    port = int(line.split()[1])
                    break
            if port is not None:
                break
        if port is None:
            self.proc.kill()
            raise _LinkDown("UDF server startup timed out")
        self.port = port
        self.sock = socket.create_connection(("127.0.0.1", port))

    def connect_external(self, addr: str,
                         spawn_timeout_s: float) -> None:
        host, _, port = addr.rpartition(":")
        self.external = True
        self.sock = socket.create_connection(
            (host or "127.0.0.1", int(port)), timeout=spawn_timeout_s)
        self.sock.settimeout(None)

    @property
    def alive(self) -> bool:
        if self.sock is None:
            return False
        if self.proc is not None and self.proc.poll() is not None:
            return False
        return True

    def kill(self) -> None:
        """Kill -9 the incarnation (wedged servers don't get a graceful
        path — the whole point). External servers just lose the socket."""
        if self.sock is not None:
            try:
                self.sock.close()
            except OSError:
                pass
            self.sock = None
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self.proc = None


class UdfPlane:
    """Process-global UDF boundary (one per client process). Sessions
    configure it from ``[udf]``; registration and evaluation reach it
    through ``expr/udf.py``."""

    def __init__(self, config: Optional[UdfConfig] = None) -> None:
        self.config = config or UdfConfig()
        self.trace_dir: Optional[str] = None
        self._lock = threading.RLock()        # lifecycle + registry
        self._conn_lock = threading.RLock()   # one wire conversation
        self._sem = threading.BoundedSemaphore(
            max(1, self.config.max_inflight))
        self._sem_size = max(1, self.config.max_inflight)
        self._handle: Optional[_ServerHandle] = None
        self.generation = 0
        self._rid = itertools.count(1)
        self._inflight = 0
        self.stats: Dict[str, int] = {
            "calls": 0, "rows": 0, "retries": 0, "respawns": 0,
            "timeouts": 0, "user_errors": 0, "stale_replies_dropped": 0,
            "overloads": 0, "inflight_peak": 0, "spawns": 0,
        }

    # -- lifecycle -------------------------------------------------------------

    def configure(self, config: UdfConfig,
                  trace_dir: Optional[str] = None) -> None:
        with self._lock:
            self.config = config
            if trace_dir is not None:
                self.trace_dir = trace_dir
            if max(1, config.max_inflight) != self._sem_size:
                self._sem_size = max(1, config.max_inflight)
                self._sem = threading.BoundedSemaphore(self._sem_size)

    def register(self, spec: UdfSpec) -> None:
        """Validate portability EAGERLY (a spec that cannot ship must
        refuse at CREATE time, not at first call mid-epoch), record it,
        and ship it to a live server."""
        if self.config.mode != "inproc":
            from ..common.interchange import udf_type_to_wire
            for t in (*spec.arg_types, spec.return_type):
                udf_type_to_wire(t)
            ship_function(spec.fn)
        with self._lock:
            UDF_SPECS[spec.name] = spec
        with self._conn_lock:
            h = self._handle
            if h is not None and h.alive:
                try:
                    self._request(h, {"type": "udf_register",
                                      "spec": spec_to_wire(spec)},
                                  self.config.spawn_timeout_s)
                except (_LinkDown, _CallTimeout, OSError):
                    self._fail_server()   # next call respawns + replays

    def drop(self, name: str) -> None:
        with self._lock:
            UDF_SPECS.pop(name, None)
        with self._conn_lock:
            h = self._handle
            if h is not None and h.alive:
                try:
                    self._request(h, {"type": "udf_drop", "name": name},
                                  self.config.spawn_timeout_s)
                except (_LinkDown, _CallTimeout, OSError):
                    self._fail_server()

    def kill_server(self) -> None:
        """Chaos hook: SIGKILL the current server incarnation (the next
        call detects it, respawns, and replays)."""
        with self._lock:
            if self._handle is not None:
                self._handle.kill()

    def shutdown_server(self) -> None:
        """Tear the server down (tests / atexit). Registrations stay:
        the next call auto-respawns a seeded server."""
        self.kill_server()
        with self._lock:
            self._handle = None

    def server_pid(self) -> Optional[int]:
        with self._lock:
            h = self._handle
            return h.proc.pid if h is not None and h.proc is not None \
                else None

    # -- evaluation ------------------------------------------------------------

    def call(self, name: str, datas: List[np.ndarray],
             masks: List[np.ndarray]) -> Tuple[np.ndarray, np.ndarray]:
        """Evaluate one columnar batch of UDF ``name``. Inputs are host
        physical columns; returns the runtime column convention
        (udf/runtime.py). Raises only typed ``UdfError``s."""
        spec = get_udf(name)
        masks = [np.asarray(m, dtype=bool) for m in masks]
        datas = decode_string_args(spec, datas, masks)
        if self.config.mode == "inproc":
            # the documented degraded mode: same decode + same evaluator
            # as the server, in-process — none of the isolation
            return eval_udf_batch(spec, datas, masks)  # rwlint: allow(udf-boundary): [udf] mode="inproc" is the documented degraded mode — the one sanctioned in-process evaluation of user code
        # bind the semaphore object: configure() may swap self._sem for
        # a resized one mid-call, and releasing the NEW (full) semaphore
        # would raise an untyped ValueError out of the boundary
        sem = self._sem
        if not sem.acquire(timeout=self.config.queue_timeout_s):
            self.stats["overloads"] += 1
            raise UdfOverloadedError(
                f"UDF boundary at capacity ({self._sem_size} batches in "
                f"flight for > {self.config.queue_timeout_s}s) — raise "
                "[udf] max_inflight or shed load")
        with self._lock:
            self._inflight += 1
            self.stats["inflight_peak"] = max(
                self.stats["inflight_peak"], self._inflight)
        try:
            return self._call_process(spec, datas, masks)
        finally:
            with self._lock:
                self._inflight -= 1
            sem.release()

    def _call_process(self, spec: UdfSpec, datas, masks):
        from ..common.interchange import udf_batch_to_wire, wire_to_udf_col
        batch = udf_batch_to_wire(datas, masks, spec.arg_types)
        attempts = max(1, self.config.max_retries + 1)
        timed_out = False
        last: Optional[BaseException] = None
        for attempt in range(attempts):
            if attempt:
                self.stats["retries"] += 1
            try:
                with self._conn_lock:
                    h = self._ensure_server()
                    fail_point("udf.call")
                    reply = self._request(
                        h, {"type": "udf_call", "name": spec.name,
                            "batch": batch},
                        self.config.call_timeout_s)
            except _CallTimeout as e:
                self.stats["timeouts"] += 1
                timed_out, last = True, e
                self._fail_server()
                continue
            except (_LinkDown, ConnectionError, OSError) as e:
                last = e
                self._fail_server()
                continue
            if not reply.get("ok", False):
                if reply.get("error_kind") == "user":
                    self.stats["user_errors"] += 1
                    raise UdfServerError(
                        f"UDF {spec.name!r} raised: {reply.get('error')}")
                raise UdfCallError(
                    f"UDF server rejected {spec.name!r}: "
                    f"{reply.get('error')}")
            fail_point("udf.reply")
            self.stats["calls"] += 1
            self.stats["rows"] += int(batch.get("n") or 0)
            return wire_to_udf_col(reply["result"], spec.return_type)
        kind = UdfTimeoutError if timed_out else UdfCallError
        raise kind(
            f"UDF {spec.name!r} failed after {attempts} attempts "
            f"(deadline {self.config.call_timeout_s}s per call, server "
            f"respawned {attempts - 1}x): {last}")

    # -- server management (under _conn_lock) ----------------------------------

    def _ensure_server(self) -> _ServerHandle:
        h = self._handle
        if h is not None and h.alive:
            return h
        fail_point("udf.spawn")
        h = _ServerHandle()
        if self.config.addr:
            h.connect_external(self.config.addr,
                               self.config.spawn_timeout_s)
        else:
            trace_path = None
            if self.trace_dir:
                trace_path = os.path.join(self.trace_dir,
                                          "chaos_trace_udf.jsonl")
            h.spawn(self.config.spawn_timeout_s, trace_path)
        with self._lock:
            self.generation += 1
            self.stats["spawns"] += 1
            self._handle = h
        # seeded respawn: replay EVERY live registration so the new
        # incarnation is a function-complete replacement
        try:
            for spec in list(UDF_SPECS.values()):
                r = self._request(h, {"type": "udf_register",
                                      "spec": spec_to_wire(spec)},
                                  self.config.spawn_timeout_s)
                if not r.get("ok", False):
                    raise _LinkDown(
                        f"registration replay of {spec.name!r} refused: "
                        f"{r.get('error')}")
        except (_CallTimeout, _LinkDown, ConnectionError, OSError) as e:
            self._fail_server()
            raise _LinkDown(f"registration replay failed: {e}") from e
        return h

    def _fail_server(self) -> None:
        """The incarnation failed (deadline/crash/link): kill it so the
        next attempt respawns fresh. ``udf.respawn`` marks the moment."""
        fail_point("udf.respawn")
        self.stats["respawns"] += 1
        with self._lock:
            if self._handle is not None:
                self._handle.kill()
                self._handle = None

    def _request(self, h: _ServerHandle, obj: dict,
                 timeout: float) -> dict:
        """One fenced request/reply. Replies whose (gen, rid) don't
        match the CURRENT request are dropped (stale incarnation, or a
        chaos-duplicated frame) — counted, never returned."""
        if h.sock is None:
            raise _LinkDown("no server connection")
        rid = next(self._rid)
        gen = self.generation
        obj = {**obj, "rid": rid, "gen": gen}
        deadline = time.monotonic() + max(0.001, timeout)
        try:
            h.sock.settimeout(max(0.001, timeout))
            write_frame_sync(h.sock, obj, link=CALL_LINK)
            while True:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise socket.timeout()
                h.sock.settimeout(remaining)
                resp = read_frame_sync(h.sock)
                if resp is None:
                    raise _LinkDown("UDF server connection lost")
                if resp.get("rid") != rid or resp.get("gen") != gen:
                    with self._lock:
                        self.stats["stale_replies_dropped"] += 1
                    continue
                return resp
        except socket.timeout:
            raise _CallTimeout(
                f"no reply within {timeout}s") from None

    # -- observability ---------------------------------------------------------

    def snapshot(self) -> dict:
        with self._lock:
            h = self._handle
            return {
                "mode": self.config.mode,
                "generation": self.generation,
                "registered": len(UDF_SPECS),
                "server_alive": bool(h is not None and h.alive),
                "inflight": self._inflight,
                **dict(self.stats),
            }


_PLANE = UdfPlane()


def udf_plane() -> UdfPlane:
    return _PLANE


@atexit.register
def _shutdown_at_exit() -> None:   # pragma: no cover - interpreter exit
    try:
        _PLANE.kill_server()
    except Exception:  # noqa: BLE001
        pass
