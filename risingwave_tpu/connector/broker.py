"""Broker-shaped source: a partitioned append-log server + split reader.

Counterpart of the reference's Kafka-style broker sources (reference:
src/connector/src/source/base.rs:295-340 — SplitImpl::Kafka,
src/connector/src/source/kafka/). The in-tree ``BrokerServer`` is the
environment's stand-in for an external broker (no Kafka in the image): a
TCP server holding N append-only partitions per topic, with at-least-once
durable segments on disk, speaking a minimal line protocol:

    PUB <topic> <part> <b64>      -> OK <offset>
    FETCH <topic> <part> <off> <max> -> MSGS <n>\\n<b64>*n
    META <topic>                  -> PARTS <n>
    LEN <topic> <part>            -> OK <n>
    QUIT

``BrokerClient`` is fault-tolerant: every command transparently
reconnects with backoff (common/retry.py policy) when the broker drops
the connection or is briefly down. FETCH/META/LEN are idempotent and
simply retried; PUB replays after a lost reply are deduplicated by
offset position (``LEN`` tells the client how many of its unacked
messages landed — exact under the one-producer-per-partition discipline
the broker sink keeps).

``BrokerSourceReader`` implements the SplitReader contract over it: one
split per partition (``{topic}-{part}``), offsets are per-partition
sequence numbers, and ``seek`` makes replay deterministic — which is what
plugs it into the existing split-state checkpointing for exactly-once
resume (connector/base.py).

Payload formats: ``json`` (one object per message) and ``avro`` (binary
datum against an Avro record schema — connector/avro.py).
"""

from __future__ import annotations

import base64
import os
import socket
import socketserver
import threading
import time
from typing import Dict, List, Optional

from ..common.chunk import HostChunk, host_rows
from ..common.types import Schema
from .base import SplitReader
from .parsers import parse_json_line


class _Partition:
    __slots__ = ("messages", "path", "lock")

    def __init__(self, path: Optional[str]):
        self.messages: list[bytes] = []
        self.path = path
        self.lock = threading.Lock()
        if path is not None and os.path.exists(path):
            with open(path, "rb") as f:
                for line in f.read().splitlines():
                    if line:
                        self.messages.append(base64.b64decode(line))

    def append(self, payload: bytes) -> int:
        with self.lock:
            self.messages.append(payload)
            if self.path is not None:
                with open(self.path, "ab") as f:
                    f.write(base64.b64encode(payload) + b"\n")
                    f.flush()
                    os.fsync(f.fileno())
            return len(self.messages) - 1

    def read(self, offset: int, max_n: int) -> list[bytes]:
        with self.lock:
            return self.messages[offset:offset + max_n]

    def length(self) -> int:
        with self.lock:
            return len(self.messages)


class BrokerServer:
    """Append-log broker. ``data_dir=None`` keeps topics in memory only;
    with a directory, every partition is an fsynced base64-line segment
    that survives broker restarts."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 n_partitions: int = 2, data_dir: Optional[str] = None):
        self.n_partitions = n_partitions
        self.data_dir = data_dir
        self._topics: Dict[str, list[_Partition]] = {}
        self._lock = threading.Lock()
        # live handler connections: a broker RESTART must drop them (like
        # a real broker process dying) or clients would keep talking to a
        # zombie handler thread serving the closed server's partitions
        self._conns: set = set()
        broker = self

        class Handler(socketserver.StreamRequestHandler):
            def handle(self):
                with broker._lock:
                    broker._conns.add(self.connection)
                try:
                    while True:
                        line = self.rfile.readline()
                        if not line:
                            return
                        try:
                            reply = broker._command(line.decode().strip())
                        except Exception as e:  # malformed input must not
                            reply = f"ERR {e}"  # kill the acceptor thread
                        if reply is None:
                            return
                        self.wfile.write(reply.encode() + b"\n")
                        self.wfile.flush()
                finally:
                    with broker._lock:
                        broker._conns.discard(self.connection)

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._server = Server((host, port), Handler)
        self.host, self.port = self._server.server_address
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True)

    # -- lifecycle ------------------------------------------------------------

    def start(self) -> "BrokerServer":
        self._thread.start()
        return self

    def close(self) -> None:
        self._server.shutdown()
        self._server.server_close()
        # sever live client connections (process-death semantics): their
        # next command fails and the fault-tolerant client reconnects —
        # to whatever serves this address then
        with self._lock:
            conns = list(self._conns)
        for conn in conns:
            try:
                conn.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                conn.close()
            except OSError:
                pass

    @property
    def address(self) -> str:
        return f"{self.host}:{self.port}"

    # -- protocol -------------------------------------------------------------

    def _topic(self, name: str) -> list[_Partition]:
        with self._lock:
            t = self._topics.get(name)
            if t is None:
                paths = [None] * self.n_partitions
                if self.data_dir is not None:
                    os.makedirs(self.data_dir, exist_ok=True)
                    paths = [os.path.join(self.data_dir, f"{name}.{p}.log")
                             for p in range(self.n_partitions)]
                t = self._topics[name] = [
                    _Partition(p) for p in paths]
            return t

    def _command(self, line: str) -> Optional[str]:
        parts = line.split(" ")
        cmd = parts[0].upper() if parts else ""
        if cmd == "PUB":
            _, topic, part, b64 = parts
            off = self._topic(topic)[int(part)].append(
                base64.b64decode(b64))
            return f"OK {off}"
        if cmd == "FETCH":
            _, topic, part, off, max_n = parts
            msgs = self._topic(topic)[int(part)].read(int(off), int(max_n))
            return "\n".join([f"MSGS {len(msgs)}"] + [
                base64.b64encode(m).decode() for m in msgs])
        if cmd == "META":
            return f"PARTS {len(self._topic(parts[1]))}"
        if cmd == "LEN":
            _, topic, part = parts
            return f"OK {self._topic(topic)[int(part)].length()}"
        if cmd == "QUIT":
            return None
        raise ValueError(f"unknown command {cmd!r}")

    # -- local producer convenience (tests / sinks) ---------------------------

    def publish(self, topic: str, partition: int, payload: bytes) -> int:
        return self._topic(topic)[partition].append(payload)


class BrokerClient:
    """Line-protocol client used by the reader, the broker sink, and
    tests' producers. Fault-tolerant: a dropped connection (broker
    restart, transient socket error) is survived by transparent
    reconnect-with-backoff instead of leaving the client permanently
    dead. FETCH/META/LEN retry blindly (idempotent); PUB replays are
    deduplicated by offset position (see ``publish_many``)."""

    def __init__(self, address: str, timeout: float = 10.0,
                 reconnect_policy=None):
        host, port = address.rsplit(":", 1)
        self._host, self._port = host, int(port)
        self._timeout = timeout
        self._sock: Optional[socket.socket] = None
        self._rf = None
        if reconnect_policy is None:
            # single source of default numbers: the FaultConfig dataclass
            # (a bare client matches a fault-config-less session exactly)
            from ..common.config import FaultConfig
            reconnect_policy = FaultConfig().broker_retry_policy()
        self._policy = reconnect_policy
        #: next expected offset per (topic, partition) this client has
        #: published to — the publish-replay dedup cursor
        self._next_off: Dict[tuple, int] = {}
        # eager connect, but UNDER the reconnect policy: a broker that is
        # briefly down at construction time (restart racing a CREATE
        # SOURCE/SINK or recovery) is absorbed; a truly bad address still
        # surfaces once the budget is spent
        self._policy.run("broker.connect", self._ensure_conn)

    # -- connection management ------------------------------------------------

    def _ensure_conn(self) -> None:
        if self._sock is not None:
            return
        self._sock = socket.create_connection(
            (self._host, self._port), timeout=self._timeout)
        self._rf = self._sock.makefile("rb")

    def _drop_conn(self) -> None:
        if self._rf is not None:
            try:
                self._rf.close()
            except OSError:
                pass
            self._rf = None
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None

    def _readline(self) -> bytes:
        line = self._rf.readline()
        if not line:
            raise ConnectionError("broker closed the connection")
        return line

    def _roundtrip(self, line: str) -> str:
        """One request/reply on the current connection; connection-shaped
        failures drop the socket so the caller's retry reconnects."""
        try:
            self._ensure_conn()
            self._sock.sendall(line.encode() + b"\n")
            return self._readline().decode().strip()
        except (OSError, ConnectionError):
            self._drop_conn()
            raise

    def _rpc(self, line: str, site: str) -> str:
        """Idempotent command under the reconnect policy."""
        return self._policy.run(site, self._roundtrip, line)

    # -- commands --------------------------------------------------------------

    def publish(self, topic: str, partition: int, payload: bytes) -> int:
        return self.publish_many(topic, partition, [payload])

    def partition_len(self, topic: str, partition: int) -> int:
        """Current message count of a partition (the LEN command)."""
        r = self._rpc(f"LEN {topic} {partition}", "broker.len")
        if not r.startswith("OK "):
            raise RuntimeError(f"broker error: {r}")
        return int(r.split(" ")[1])

    def published_through(self, topic: str,
                          partition: int) -> Optional[int]:
        """This client's publish cursor (next expected offset) for a
        partition, maintained even across mid-batch failures — the
        broker sink's dedup bookkeeping reads it."""
        return self._next_off.get((topic, partition))

    def _settled_len(self, topic: str, partition: int) -> int:
        """Partition length AFTER the broker stops absorbing in-flight
        appends. A dropped connection's buffered PUB lines may still be
        draining server-side (the close sent FIN, not an abort), so a
        single LEN probe could undercount landed messages and cause a
        duplicate resend — poll until two reads agree."""
        n = self.partition_len(topic, partition)
        for _ in range(20):
            time.sleep(0.02)
            n2 = self.partition_len(topic, partition)
            if n2 == n:
                return n
            n = n2
        return n

    def publish_many(self, topic: str, partition: int,
                     payloads: list) -> int:
        """Pipelined publish: all PUB lines sent, then all replies read —
        one RTT per batch, not per message. Returns the last offset.

        Replay dedup: if the connection dies mid-batch, some messages may
        have been appended without their OK reaching us. After
        reconnecting, ``LEN`` reveals how many landed past our cursor —
        those are treated as acked and only the remainder is resent, so a
        broker restart never duplicates messages (exact under the
        one-producer-per-partition discipline the broker sink keeps;
        concurrent foreign producers would make any dedup unsound)."""
        if not payloads:
            return -1
        key = (topic, partition)
        unacked = [bytes(p) for p in payloads]
        if key not in self._next_off:
            # first publish on this partition: anchor the dedup cursor
            self._next_off[key] = self.partition_len(topic, partition)
        last = self._next_off[key] - 1

        def attempt() -> int:
            nonlocal last
            if not unacked:
                return last
            try:
                self._ensure_conn()
                lines = b"".join(
                    f"PUB {topic} {partition} "
                    f"{base64.b64encode(p).decode()}\n".encode()
                    for p in unacked)
                self._sock.sendall(lines)
                n_acked = 0
                try:
                    for _ in range(len(unacked)):
                        r = self._readline().decode().strip()
                        if not r.startswith("OK "):
                            # the rest of the batch's replies are still
                            # buffered: a reused client would consume
                            # them as later commands' replies — drop the
                            # connection before surfacing the error
                            self._drop_conn()
                            raise RuntimeError(f"broker error: {r}")
                        last = int(r.split(" ")[1])
                        self._next_off[key] = last + 1
                        n_acked += 1
                finally:
                    del unacked[:n_acked]
                return last
            except (OSError, ConnectionError):
                self._drop_conn()
                # dedup-by-offset: messages appended before the drop are
                # exactly those past our cursor (settled probe: the old
                # connection's buffered PUBs may still be draining). If
                # the broker is STILL down past the LEN sub-budget,
                # surface it as a connection error so the OUTER publish
                # policy keeps its own reconnect attempts (a RetryError
                # would be non-retryable and collapse the budget).
                from ..common.retry import RetryError
                try:
                    n = self._settled_len(topic, partition)  # reconnects
                except RetryError as re:
                    raise ConnectionError(
                        f"broker still unreachable probing replay "
                        f"position: {re}") from re
                landed = min(max(0, n - self._next_off[key]), len(unacked))
                del unacked[:landed]
                self._next_off[key] = n
                if unacked:
                    raise               # policy retries the remainder
                last = n - 1
                return last

        return self._policy.run("broker.publish", attempt)

    def fetch(self, topic: str, partition: int, offset: int,
              max_n: int) -> list[bytes]:
        def attempt() -> list[bytes]:
            try:
                self._ensure_conn()
                self._sock.sendall(
                    f"FETCH {topic} {partition} {offset} {max_n}\n"
                    .encode())
                r = self._readline().decode().strip()
                if not r.startswith("MSGS "):
                    raise RuntimeError(f"broker error: {r}")
                n = int(r.split(" ")[1])
                return [base64.b64decode(self._readline().strip())
                        for _ in range(n)]
            except (OSError, ConnectionError):
                self._drop_conn()     # idempotent: whole fetch re-runs
                raise

        return self._policy.run("broker.fetch", attempt)

    def n_partitions(self, topic: str) -> int:
        r = self._rpc(f"META {topic}", "broker.meta")
        if not r.startswith("PARTS "):
            raise RuntimeError(f"broker error: {r}")
        return int(r.split(" ")[1])

    def close(self) -> None:
        if self._sock is not None:
            try:
                self._sock.sendall(b"QUIT\n")
            except OSError:
                pass
        self._drop_conn()


def parse_broker_options(options: dict) -> tuple:
    """Shared WITH-option extraction for the broker source AND sink so
    the two cannot drift: returns (address, topic)."""
    address = options.get("broker.address",
                          options.get("bootstrap.servers"))
    topic = options.get("topic")
    if not address or not topic:
        raise ValueError(
            "broker connector requires broker.address and topic options")
    return str(address), str(topic)


class BrokerSourceReader(SplitReader):
    """SplitReader over a broker topic: split ``{topic}-{p}`` per
    partition, offset = next message sequence number. Satisfies the
    deterministic-seek contract: the broker log is append-only, so
    re-fetching [o, o+n) always yields the same payloads."""

    def __init__(self, schema: Schema, address: str, topic: str,
                 fmt: str = "json", avro_schema: Optional[str] = None,
                 avro_framing: str = "raw", rows_per_chunk: int = 256,
                 reconnect_policy=None):
        self.schema = schema
        self.topic = topic
        self.fmt = fmt.lower()
        self.rows_per_chunk = rows_per_chunk
        self._client = BrokerClient(address,
                                    reconnect_policy=reconnect_policy)
        self._n_parts = self._client.n_partitions(topic)
        self._offsets: Dict[str, int] = {
            f"{topic}-{p}": 0 for p in range(self._n_parts)}
        self._rr = 0
        self.dropped_events = 0
        if self.fmt == "avro":
            from .avro import AvroCodec
            if not avro_schema:
                raise ValueError("avro format requires an avro.schema "
                                 "option (the record schema JSON)")
            self._avro = AvroCodec(avro_schema, framing=avro_framing)
        elif self.fmt != "json":
            raise ValueError(f"unsupported broker format {self.fmt!r}")

    def splits(self) -> List[str]:
        return list(self._offsets)

    @property
    def offsets(self) -> Dict[str, int]:
        return dict(self._offsets)

    def seek(self, offsets: Dict[str, int]) -> None:
        for s, o in offsets.items():
            if s in self._offsets:
                self._offsets[s] = int(o)

    def _decode(self, payload: bytes) -> Optional[tuple]:
        """payload → PHYSICAL row tuple (strings interned), or None for
        undecodable messages (counted in dropped_events, offset still
        advances — a poisoned message must not wedge the source)."""
        if self.fmt == "avro":
            try:
                rec = self._avro.decode(payload)
            except Exception:
                self.dropped_events += 1
                return None
            vals = [rec.get(f.name) for f in self.schema]
        else:
            try:
                row = parse_json_line(payload.decode("utf-8", "replace"),
                                      self.schema)
            except (ValueError, TypeError):
                self.dropped_events += 1
                return None
            if row is None:
                return None
            vals = list(row)
        return tuple(
            None if v is None else f.type.to_physical(v)
            for f, v in zip(self.schema, vals))

    def next_host_chunk(self) -> Optional[HostChunk]:
        """Round-robin over partitions; one chunk per non-empty fetch."""
        for _ in range(self._n_parts):
            p = self._rr
            self._rr = (self._rr + 1) % self._n_parts
            split = f"{self.topic}-{p}"
            off = self._offsets[split]
            msgs = self._client.fetch(self.topic, p, off,
                                      self.rows_per_chunk)
            if not msgs:
                continue
            rows = []
            for m in msgs:
                r = self._decode(m)
                if r is not None:
                    rows.append(r)
            self._offsets[split] = off + len(msgs)
            if not rows:
                continue
            return host_rows(self.schema, rows,
                             capacity=max(self.rows_per_chunk, len(rows)),
                             physical=True)
        return None

    def close(self) -> None:
        self._client.close()
