"""Durable checkpoint log: epoch-delta segments + manifest on an
ObjectStore (local FS by default — storage/object_store.py).

The durable tier under MemoryStateStore — the role Hummock's SST upload +
version manifest plays in the reference (reference:
src/storage/src/hummock/sstable/builder.rs:87 SST build,
src/meta/src/hummock/manager/ commit_epoch version bump, docs/checkpoint.md:
26-44 "commit epoch makes sealed state durable"). Deliberately NOT an LSM:
executor state is already merged in device HBM, so each checkpoint writes
one compact *delta segment* (the rows dirtied since the previous checkpoint,
already deduplicated per key) and recovery is a linear replay of segments —
compaction pressure, which Hummock exists to manage, does not arise until
segment counts grow, at which point segments fold into one. Folding runs on
a BACKGROUND thread, off the barrier path (reference: standalone compactor,
src/storage/compactor/src/server.rs:57): the fold reads a snapshot of the
segment list, writes the folded segment, then swaps the manifest under the
lock — barrier-path appends interleave freely because they only append.

Write discipline (crash-safe at every point):
  1. put the segment object (fsync'd by the FS backend),
  2. publish the manifest via atomic_put (tmp + atomic rename).
A crash between 1 and 2 leaves an orphan segment the manifest never
references — ignored on recovery.

Values inside segments use the process-independent value encoding
(common/row.py: strings as bytes, not dictionary ids), so a fresh process
recovers cleanly.
"""

from __future__ import annotations

import json
import os
import struct
import threading
from typing import Optional

from ..common.packed import dict_view, packed_rows
from ..common.tracing import CAT_STORAGE, span
from .object_store import ObjectStore, open_object_store, wrap_object_store
from .state_store import MemoryStateStore

_MANIFEST = "manifest.json"

#: Plan/lowering format generation. State-table ids are assigned by a
#: deterministic walk of the OPTIMIZED plan, so a data_dir written by a
#: build with a different frontend pipeline may lay out state tables
#: differently (reference: Hummock's version/format compatibility gates,
#: src/meta/src/hummock/manager/versioning.rs). Bump when the planner/
#: optimizer changes the shape of built plans; recovery warns on mismatch.
#: v3: join state-table pks are join-key-prefixed (frontend/build.py
#: join_state_pk) — v2 join rows are keyed under the old stream-pk layout.
PLAN_FORMAT_VERSION = 3


def _segment_counts(rows: int = 0, nbytes: int = 0,
                    native: bool = False) -> dict:
    """The args a storage span carries of the segment it wrote: its rows,
    its length and whether the native codec laid it out (0 / 0 / 0 for an
    epoch that wrote none)."""
    return {"rows": rows, "bytes": nbytes, "native": int(native)}


def _part(name: str, **args):
    """One kind of work inside a store writer's span (``DurableStateStore
    .commit`` / ``.prepare`` / ``.commit_async``), of the enclosing span's
    epoch — a fold's thread has none: its parts carry no epoch and
    ``epoch_spans()`` skips them. No ledger stage: the writer's span
    holds their time."""
    return span(name, epoch=None, cat=CAT_STORAGE, tid="storage", **args)


def _layers_of(delta) -> list:
    """A table's delta as the segment writer takes it: its layers
    (common/packed.py) in application order, or one dict ``{key: value |
    None}``."""
    return delta if isinstance(delta, list) else [delta]


class CheckpointLog:
    def __init__(self, data_dir: Optional[str] = None,
                 object_store: Optional[ObjectStore] = None,
                 compact_after: Optional[int] = None,
                 retry_policy=None):
        if object_store is None:
            if data_dir is None:
                raise ValueError("need data_dir or object_store")
            object_store = open_object_store(data_dir, retry_policy)
        self.dir = data_dir
        # every IO below the manifest/segment discipline goes through the
        # retry layer (idempotent whole-object ops; common/retry.py)
        self.store = wrap_object_store(object_store, retry_policy)
        if compact_after is not None:
            self.COMPACT_AFTER = compact_after
        # serializes manifest read-modify-write cycles between the barrier
        # path and the background compactor
        self._mlock = threading.RLock()
        # one fold at a time: an explicit compact() call must not overlap
        # the background thread's (overlapping folds would double-delete
        # and race the folded-segment sequence number)
        self._fold_lock = threading.Lock()
        self._compact_thread: Optional[threading.Thread] = None
        self._compact_seq = 0
        self._format_warned = False

    # -- manifest -------------------------------------------------------------

    def exists(self) -> bool:
        return self.store.exists(_MANIFEST)

    def _read_manifest(self) -> dict:
        raw = self.store.get(_MANIFEST)
        if raw is None:
            return {"committed_epoch": 0, "segments": [], "ddl": [],
                    "dropped_tables": [], "prepared": {},
                    "plan_format": PLAN_FORMAT_VERSION}
        m = json.loads(raw)
        m.setdefault("dropped_tables", [])
        m.setdefault("prepared", {})
        stored = m.setdefault("plan_format", 1)
        if stored != PLAN_FORMAT_VERSION and not self._format_warned:
            self._format_warned = True
            import warnings
            warnings.warn(
                f"data dir was written by plan-format {stored}, this "
                f"build is {PLAN_FORMAT_VERSION}: state-table layout may "
                "not match the replayed DDL's rebuilt plans — if recovery "
                "misbehaves, rebuild the MVs from sources (DROP/CREATE)")
        return m

    def _write_manifest(self, manifest: dict) -> None:
        from ..common.failpoint import fail_point
        fail_point("checkpoint.manifest.write")
        payload = json.dumps(manifest).encode()
        try:
            fail_point("checkpoint.manifest.rename")
        except BaseException:
            # torn publish: the tmp object exists, the manifest does not
            # change — recovery ignores *.tmp (the pre-refactor on-disk
            # shape of a crash between tmp write and rename)
            self.store.put(_MANIFEST + ".tmp2", payload)
            raise
        self.store.atomic_put(_MANIFEST, payload)

    # -- segments -------------------------------------------------------------

    @staticmethod
    def _encode_segment_py(
            deltas: dict[int, dict[bytes, Optional[bytes]]]) -> bytes:
        """The segment format, row by row in Python: what runs where the
        native codec is absent or a key does not fit its ``<H`` length
        (``struct.error``, as ever)."""
        parts = [struct.pack("<I", len(deltas))]
        for table_id, buf in sorted(deltas.items()):
            parts.append(struct.pack("<II", table_id, len(buf)))
            for k, v in sorted(buf.items()):
                parts.append(struct.pack("<H", len(k)))
                parts.append(k)
                if v is None:
                    parts.append(b"\x00")
                else:
                    parts.append(b"\x01")
                    parts.append(struct.pack("<I", len(v)))
                    parts.append(v)
        return b"".join(parts)

    @staticmethod
    def _segment_native(deltas: dict) -> Optional[tuple]:
        """``(segment bytes, rows in it)`` with each table's layers sorted
        and laid out by the native codec (native/rowcodec.cpp
        ``rw_encode_segment_table``: a packed layer goes in as it is, the
        last row of a key wins); None where the codec is absent or refuses
        a table."""
        from ..native import codec
        native = codec()
        if native is None:
            return None
        parts: list = [struct.pack("<I", len(deltas))]
        rows = 0
        for table_id, delta in sorted(deltas.items()):
            encoded = native.encode_segment_table(_layers_of(delta))
            if encoded is None:
                return None
            block, n = encoded
            parts.append(struct.pack("<II", table_id, n))
            parts.append(block)
            rows += n
        return b"".join(parts), rows

    @staticmethod
    def _encode_segment_native(deltas: dict) -> Optional[bytes]:
        encoded = CheckpointLog._segment_native(deltas)
        return None if encoded is None else encoded[0]

    @staticmethod
    def _dict_deltas(deltas: dict) -> dict[int, dict[bytes, Optional[bytes]]]:
        """What ``_encode_segment_py`` takes: every table's layers folded
        into one dict."""
        return {t: dict_view(_layers_of(d)) for t, d in deltas.items()}

    @staticmethod
    def _encode_segment(deltas: dict) -> bytes:
        # a segment is never empty (its table count), so None alone is falsy
        return (CheckpointLog._encode_segment_native(deltas)
                or CheckpointLog._encode_segment_py(
                    CheckpointLog._dict_deltas(deltas)))

    def _write_segment(self, name: str, deltas: dict) -> dict:
        """Encode and durably put one segment of ``{table_id: layers |
        dict}``; returns what the caller's span reports of it
        (``_segment_counts``)."""
        from ..common.failpoint import fail_point
        fail_point("checkpoint.segment.write")
        with _part("segment.encode") as encode:
            encoded = self._segment_native(deltas)
            native = encoded is not None
            if native:
                payload, rows = encoded
            else:
                by_dict = self._dict_deltas(deltas)
                payload = self._encode_segment_py(by_dict)
                rows = sum(map(len, by_dict.values()))
            counts = _segment_counts(rows, len(payload), native)
            encode.set(**counts, packed=sum(
                packed_rows(_layers_of(d)) for d in deltas.values()))
        try:
            # simulates a torn segment (crash mid-write): a truncated
            # object lands on disk. Safe because the manifest that would
            # reference this segment is only written after the segment
            # completes — recovery never reads an unreferenced object.
            fail_point("checkpoint.segment.write.partial")
        except BaseException:
            self.store.put(name, payload[:4])
            raise
        with _part("segment.put", bytes=len(payload)):
            self.store.put(name, payload)
        return counts

    def _read_segment(self, name: str) -> dict[int, dict[bytes, Optional[bytes]]]:
        data = self.store.get(name)
        if data is None:
            raise FileNotFoundError(name)
        return self._decode_segment(data)

    @staticmethod
    def _decode_segment(data: bytes) -> dict[int, dict[bytes, Optional[bytes]]]:
        pos = 0
        (n_tables,) = struct.unpack_from("<I", data, pos)
        pos += 4
        out: dict[int, dict[bytes, Optional[bytes]]] = {}
        for _ in range(n_tables):
            table_id, n = struct.unpack_from("<II", data, pos)
            pos += 8
            buf: dict[bytes, Optional[bytes]] = {}
            for _ in range(n):
                (klen,) = struct.unpack_from("<H", data, pos)
                pos += 2
                k = data[pos:pos + klen]
                pos += klen
                live = data[pos]
                pos += 1
                if live:
                    (vlen,) = struct.unpack_from("<I", data, pos)
                    pos += 4
                    buf[k] = data[pos:pos + vlen]
                    pos += vlen
                else:
                    buf[k] = None
            out[table_id] = buf
        return out

    # -- public surface -------------------------------------------------------

    # folding threshold: bounds segment-count growth AND the O(segments)
    # manifest rewrite per commit
    COMPACT_AFTER = 64

    def append_epoch(self, epoch: int, deltas: dict) -> dict:
        from ..common.failpoint import fail_point
        fail_point("checkpoint.commit")
        counts = _segment_counts()
        if deltas:
            name = f"epoch_{epoch:012d}.seg"
            counts = self._write_segment(name, deltas)
        with _part("manifest.write") as write, self._mlock:
            manifest = self._read_manifest()
            if deltas:
                manifest["segments"].append(name)
            # empty delta: bump the committed epoch only (idle FLUSH ticks
            # must not grow the segment list)
            manifest["committed_epoch"] = epoch
            self._write_manifest(manifest)
            n_segments = len(manifest["segments"])
            write.set(segments=n_segments)
        if n_segments > self.COMPACT_AFTER:
            self._spawn_compact()
        return counts

    # -- two-phase epochs (spanning jobs) -------------------------------------
    # A job whose fragment graph spans worker processes needs the cluster
    # checkpoint cut to be CONSISTENT across several independent stores.
    # Phase 1 (barrier ack) therefore makes the epoch's deltas DURABLE
    # without committing them: the segment object is written and recorded
    # in the manifest's ``prepared`` map. Phase 2 (the session's commit
    # frame) promotes it into the committed chain. A process killed
    # between ack and commit can then be ROLLED FORWARD at recovery to
    # whatever epoch the rest of the cluster committed — without this,
    # one participant recovering a checkpoint behind its peers forks the
    # job's history (reference: Hummock solves the same problem by giving
    # the META node one atomic version for the whole cluster;
    # src/meta/src/hummock/manager/ commit_epoch).

    def prepare_epoch(self, epoch: int, deltas: dict) -> dict:
        """Phase 1: durably stage an epoch's deltas without committing."""
        from ..common.failpoint import fail_point
        fail_point("checkpoint.prepare")
        name = None
        counts = _segment_counts()
        if deltas:
            name = f"epoch_{epoch:012d}.prepared.seg"
            counts = self._write_segment(name, deltas)
        with _part("manifest.write") as write, self._mlock:
            manifest = self._read_manifest()
            manifest["prepared"][str(epoch)] = name
            self._write_manifest(manifest)
            write.set(segments=len(manifest["segments"]))
        return counts

    def prepared_epochs(self) -> list[int]:
        with self._mlock:
            return sorted(int(e) for e in self._read_manifest()["prepared"])

    def recovery_info(self) -> tuple[int, list[int]]:
        """(committed epoch, prepared epochs) — what this store durably
        holds, for the session's recovery negotiation."""
        with self._mlock:
            m = self._read_manifest()
        return (int(m["committed_epoch"]),
                sorted(int(e) for e in m["prepared"]))

    def settle_prepared(self, decided_epoch: int,
                        discard_beyond: bool = True) -> None:
        """Roll prepared epochs ≤ ``decided_epoch`` forward into the
        committed chain. With ``discard_beyond`` (the RECOVERY path),
        prepared epochs beyond it are DELETED — the cluster never
        decided them, and committing them would replay rows the rest of
        the graph does not have. The normal phase-2 path passes False:
        with pipelined checkpoints a LATER epoch may already be durably
        prepared when this epoch's commit frame arrives, and it must
        survive for its own commit."""
        from ..common.failpoint import fail_point
        fail_point("checkpoint.settle")
        victims: list[str] = []
        with self._mlock:
            manifest = self._read_manifest()
            prepared = manifest["prepared"]
            if not prepared:
                return
            for e in sorted(int(x) for x in prepared):
                name = prepared[str(e)]
                if e <= decided_epoch:
                    prepared.pop(str(e))
                    if name is not None:
                        manifest["segments"].append(name)
                    manifest["committed_epoch"] = max(
                        manifest["committed_epoch"], e)
                elif discard_beyond:
                    prepared.pop(str(e))
                    if name is not None:
                        victims.append(name)
            self._write_manifest(manifest)
        for name in victims:
            self.store.delete(name)

    def log_ddl(self, sql: str) -> None:
        with self._mlock:
            manifest = self._read_manifest()
            manifest["ddl"].append(sql)
            self._write_manifest(manifest)

    def drop_table(self, table_id: int) -> None:
        """Tombstone a table id: recovery and compaction skip its rows
        (the durable analogue of dropping the object's state)."""
        with self._mlock:
            manifest = self._read_manifest()
            if table_id not in manifest["dropped_tables"]:
                manifest["dropped_tables"].append(table_id)
                self._write_manifest(manifest)

    def ddl(self) -> list[str]:
        with self._mlock:
            return list(self._read_manifest().get("ddl", []))

    def _fold(self, segments: list, dropped: set) -> dict:
        tables: dict[int, dict[bytes, bytes]] = {}
        for name in segments:
            for table_id, buf in self._read_segment(name).items():
                if table_id in dropped:
                    continue
                tbl = tables.setdefault(table_id, {})
                for k, v in buf.items():
                    if v is None:
                        tbl.pop(k, None)
                    else:
                        tbl[k] = v
        return tables

    def load_tables(self) -> tuple[int, dict[int, dict[bytes, bytes]]]:
        """Replay all manifest-referenced segments in commit order.

        A concurrent compactor (this process's or another reader-turned-
        writer on the same directory) may delete a base segment between our
        manifest read and the segment read. Segments are immutable and the
        manifest swap is atomic, so re-reading the manifest and replaying
        converges — retry instead of surfacing FileNotFoundError."""
        for attempt in range(8):
            with self._mlock:
                manifest = self._read_manifest()
            try:
                tables = self._fold(manifest["segments"],
                                    set(manifest["dropped_tables"]))
                return manifest["committed_epoch"], tables
            except FileNotFoundError:
                if attempt == 7:   # still racing: surface the real error
                    raise
        raise AssertionError("unreachable")

    # -- compaction (background, off the barrier path) ------------------------
    # (reference: the standalone compactor worker; compaction tasks run
    #  concurrently with checkpoints, src/storage/compactor/src/server.rs:57)

    def _spawn_compact(self) -> None:
        t = self._compact_thread
        if t is not None and t.is_alive():
            return
        t = threading.Thread(target=self._compact_guarded, daemon=True,
                             name="checkpoint-compactor")
        self._compact_thread = t
        t.start()

    def _compact_guarded(self) -> None:
        try:
            self.compact()
        except Exception as e:   # never fatal: old segments remain valid,
            import sys           # but a persistent failure must be visible
            sys.stderr.write(
                f"checkpoint compaction failed (segments keep "
                f"accumulating until it succeeds): {e!r}\n")

    def wait_compaction(self) -> None:
        """Join any in-flight background fold (tests / orderly shutdown)."""
        t = self._compact_thread
        if t is not None and t.is_alive():
            t.join()

    def compact(self) -> None:
        """Fold segments into one (the stand-in for LSM compaction);
        dropped tables' rows are discarded in the fold.

        Safe concurrently with ``append_epoch``: the fold works on a
        SNAPSHOT of the segment list (segments are immutable and appends
        only add), and the manifest swap under the lock keeps any segments
        appended meanwhile."""
        with self._fold_lock:
            self._compact_locked()

    def _compact_locked(self) -> None:
        # Like load_tables, the fold can race a CROSS-process compactor
        # deleting base segments after our manifest read — re-read and
        # retry; segments are immutable so a retry converges.
        for attempt in range(8):
            with self._mlock:
                manifest = self._read_manifest()
                base = list(manifest["segments"])
                dropped = set(manifest["dropped_tables"])
                epoch = manifest["committed_epoch"]
            if len(base) <= 1:
                return
            try:
                tables = self._fold(base, dropped)
                break
            except FileNotFoundError:
                if attempt == 7:
                    raise
        # _compact_seq is process-local and resets on restart, and a plain
        # exists-probe would be check-then-write racy across processes: a
        # per-process random token makes the folded name unique, so no fold
        # (post-restart or concurrent) can overwrite a live segment.
        self._compact_seq += 1
        import uuid
        name = (f"epoch_{epoch:012d}.c{self._compact_seq}"
                f"-{uuid.uuid4().hex[:8]}.compacted.seg")
        self._write_segment(name, {t: dict(b) for t, b in tables.items()})
        with self._mlock:
            manifest = self._read_manifest()
            base_set = set(base)
            manifest["segments"] = [name] + [
                s for s in manifest["segments"] if s not in base_set]
            self._write_manifest(manifest)
        for n in base:
            if n != name:
                self.store.delete(n)


# -- vnode-migration handoff segments (elastic scaling plane) ----------------
# A live rescale (meta/rescale.py, docs/scaling.md) moves only the vnode
# ranges whose owner changes. The SOURCE worker writes each moving
# range's committed rows as ONE handoff segment on shared storage (the
# same wire format as checkpoint segments) and the migration protocol
# hands the DESTINATION a *reference* — the path — instead of shipping
# rows through the session or replaying sources (reference: scale.rs:657
# moving Hummock SST references between parallel units).


def write_handoff(path: str,
                  deltas: dict[int, dict[bytes, Optional[bytes]]]) -> None:
    """Durably write one handoff segment (fsync before rename so a ref
    never names a torn object)."""
    payload = CheckpointLog._encode_segment(deltas)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(payload)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def read_handoff(path: str) -> dict[int, dict[bytes, Optional[bytes]]]:
    with open(path, "rb") as f:
        return CheckpointLog._decode_segment(f.read())


class DurableStateStore(MemoryStateStore):
    """MemoryStateStore whose epoch commits are persisted through a
    CheckpointLog; a fresh instance over the same directory recovers the
    committed state (reference: StateStoreImpl selecting the Hummock backend,
    src/storage/src/store_impl.rs:49-64)."""

    def __init__(self, data_dir: Optional[str] = None,
                 object_store: Optional[ObjectStore] = None,
                 compact_after: Optional[int] = None,
                 retry_policy=None,
                 recover_at: Optional[int] = None):
        super().__init__()
        self.log = CheckpointLog(data_dir, object_store=object_store,
                                 compact_after=compact_after,
                                 retry_policy=retry_policy)
        self._prepared_epochs: set[int] = set()
        # off-critical-path checkpoint encode (pipelined tick): at most
        # ONE deferred commit in flight; ordering is preserved by
        # joining before starting the next (segments are replayed in
        # manifest order, so a later epoch's segment must never land
        # without its predecessor)
        self._commit_thread: Optional[threading.Thread] = None
        self._commit_error: Optional[BaseException] = None
        if self.log.exists():
            if recover_at is not None:
                # spanning-job recovery: the session names the epoch the
                # CLUSTER decided; prepared-but-uncommitted epochs up to
                # it roll forward, later ones are discarded — every
                # participant recovers the same cut
                self.log.settle_prepared(recover_at)
            epoch, tables = self.log.load_tables()
            self._committed = tables
            self.committed_epoch = epoch

    def _pending_deltas(self, epoch: int) -> dict[int, list]:
        """``pending_tables(epoch)`` under its span: the layers are handed
        on unmerged — the segment writer folds them (the last row of a key
        wins)."""
        with span("commit.pending", epoch=epoch, cat=CAT_STORAGE,
                  tid="storage") as pending:
            deltas = self.pending_tables(epoch)
            pending.set(
                rows=sum(len(layer) for layers in deltas.values()
                         for layer in layers),
                packed=sum(map(packed_rows, deltas.values())),
                dict_tables=sorted(
                    t for t, layers in deltas.items()
                    if any(isinstance(layer, dict) for layer in layers)))
        return deltas

    def prepare(self, epoch: int) -> None:
        """Phase 1 of the cluster checkpoint: durably stage pending
        deltas ≤ ``epoch`` (the in-memory view is untouched; ``commit``
        later applies and publishes them)."""
        if epoch <= self.committed_epoch or epoch in self._prepared_epochs:
            return
        self.join_commits()          # manifest ops stay strictly ordered
        deltas = self._pending_deltas(epoch)
        with span("DurableStateStore.prepare", epoch=epoch,
                  stage="storage_prepare", cat=CAT_STORAGE, tid="storage",
                  tables=len(deltas)) as sp:
            sp.set(**self.log.prepare_epoch(epoch, deltas))
        self._prepared_epochs.add(epoch)

    def commit_async(self, epoch: int) -> None:
        """Commit ``epoch`` with the delta serialization + segment/
        manifest IO on a worker thread (the pipelined tick's
        off-critical-path checkpoint encode). The in-memory commit
        applies HERE, synchronously — readers see the epoch at once —
        while durability lands in the background and is joined at the
        next commit, at ``join_commits()`` (the session calls it before
        any 2PC phase-2 frame and on FLUSH/close), or at the next
        synchronous commit. A crash before the join recovers at the
        previous checkpoint and replays deterministically — the same
        window as crashing just before a synchronous commit. 2PC
        participants (prepared epochs) stay fully synchronous: their
        durability IS the phase-1 ack."""
        if epoch <= self.committed_epoch:
            return
        self.join_commits()          # strict segment ordering + errors
        if any(e <= epoch for e in self._prepared_epochs):
            self.commit(epoch)
            return
        deltas = self._pending_deltas(epoch)
        MemoryStateStore.commit(self, epoch)

        def _encode_and_publish() -> None:
            # on its own thread: the conductor's commit span is named
            try:
                with span("DurableStateStore.commit_async", epoch=epoch,
                          stage="storage_commit", parent="checkpoint.commit",
                          cat=CAT_STORAGE, tid="storage",
                          tables=len(deltas)) as sp:
                    sp.set(**self.log.append_epoch(epoch, deltas))
            except BaseException as e:  # noqa: BLE001 - surfaced at join
                self._commit_error = e

        t = threading.Thread(target=_encode_and_publish, daemon=True,
                             name="checkpoint-encode")
        self._commit_thread = t
        t.start()

    def join_commits(self) -> None:
        t = self._commit_thread
        if t is not None and t.is_alive():
            t.join()
        self._commit_thread = None
        err = self._commit_error
        if err is not None:
            self._commit_error = None
            raise RuntimeError(
                "deferred checkpoint encode failed; the epoch is "
                "committed in memory but NOT durable") from err

    def commit(self, epoch: int) -> None:
        if epoch <= self.committed_epoch:
            return
        self.join_commits()
        prepared = {e for e in self._prepared_epochs if e <= epoch}
        if prepared:
            # phase 2: promote the durably staged segment(s); epochs
            # prepared BEYOND this commit (pipelined checkpoints) keep
            # their staged segments for their own commit frames
            with span("DurableStateStore.settle", epoch=epoch,
                      stage="storage_settle", cat=CAT_STORAGE,
                      tid="storage", prepared=len(prepared)):
                self.log.settle_prepared(epoch, discard_beyond=False)
            self._prepared_epochs -= prepared
        else:
            deltas = self._pending_deltas(epoch)
            with span("DurableStateStore.commit", epoch=epoch,
                      stage="storage_commit", cat=CAT_STORAGE,
                      tid="storage", tables=len(deltas)) as sp:
                sp.set(**self.log.append_epoch(epoch, deltas))
        super().commit(epoch)

    def import_tables(self, deltas: dict[int, dict[bytes, bytes]],
                      epoch: int) -> int:
        """Apply a migration handoff straight into the COMMITTED tier
        (memory + a durable segment): the rows were committed at
        ``epoch`` by their previous owner, so they enter this store as
        already-committed state, not as a pending epoch a later barrier
        must settle. Returns the number of rows imported."""
        deltas = {tid: dict(rows) for tid, rows in deltas.items() if rows}
        if not deltas:
            return 0
        self.join_commits()
        n = 0
        for tid, rows in deltas.items():
            tbl = self._committed.setdefault(tid, {})
            self._keys_dirty.add(tid)
            for k, v in rows.items():
                tbl[k] = v
            n += len(rows)
        self.log.append_epoch(max(epoch, self.committed_epoch), deltas)
        self.committed_epoch = max(self.committed_epoch, epoch)
        return n

    def drop_table(self, table_id: int) -> None:
        self.join_commits()
        super().drop_table(table_id)
        self.log.drop_table(table_id)
