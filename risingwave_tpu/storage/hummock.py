"""Hummock-lite: shared-storage LSM state tiering.

The storage half of the four-role cluster shape (frontend / compute /
compactor / meta — reference: docs/architecture-design.md:9-20). Where
``DurableStateStore`` (storage/checkpoint.py) writes per-epoch delta
SEGMENTS folded by an in-process thread, this tier writes per-epoch
**L0 SSTables** (storage/sstable.py) to an ObjectStore and hands all
rewriting to a compaction role scheduled by a meta-side version manager
(meta/hummock.py):

  * checkpoint flush  → one sorted L0 run per epoch (put, then the
    version manifest commits via atomic_put — a crash in between leaves
    an orphan object, never a torn version),
  * batch/backup read → pin a version; its runs survive any concurrent
    compaction until unpinned,
  * compaction        → a ``CompactTask`` rewrites every L0 run (plus
    overlapping L1) into fresh non-overlapping L1 runs, off the barrier
    path, in-process or on a dedicated compactor worker
    (worker/compactor.py),
  * vacuum            → deletes SSTs unreferenced by any pinned or
    current version.

Read path (newest wins): memory overlay → L0 newest→oldest → L1. A
tombstone found at any level STOPS the search; bottom-level compaction
drops tombstones and dropped tables' rows for good.
"""

from __future__ import annotations

import dataclasses
import json
import threading
import uuid
from typing import Dict, Iterator, List, Optional, Tuple

from ..common.packed import dict_view
from .checkpoint import PLAN_FORMAT_VERSION
from .object_store import ObjectStore, open_object_store, wrap_object_store
from .sstable import Sstable, SstBuilder, load_sst, merge_iter
from .state_store import MemoryStateStore

SST_PREFIX = "hummock/sst/"
VERSION_KEY = "hummock/version.json"


# -- version ------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class HummockVersion:
    """One immutable storage version: epoch → ordered run lists
    (reference: HummockVersion in the meta manager — the layer map every
    read resolves against). ``l0`` is newest-first overlapping runs;
    ``l1`` is non-overlapping sorted runs. Also carries the manifest
    duties the segment log's manifest carried (DDL log, dropped-table
    tombstones, plan format) so a Hummock data dir is self-describing."""

    vid: int
    committed_epoch: int
    l0: tuple = ()
    l1: tuple = ()
    ddl: tuple = ()
    dropped_tables: tuple = ()
    plan_format: int = PLAN_FORMAT_VERSION

    @classmethod
    def initial(cls) -> "HummockVersion":
        return cls(vid=0, committed_epoch=0)

    def replace(self, **kw) -> "HummockVersion":
        return dataclasses.replace(self, **kw)

    def all_runs(self) -> Tuple[str, ...]:
        return tuple(self.l0) + tuple(self.l1)

    def read_order(self) -> List[str]:
        """Runs in lookup priority order: L0 newest→oldest, then L1."""
        return list(self.l0) + list(self.l1)

    def fold_order(self) -> List[str]:
        """Runs in replay order (oldest first; later apply wins)."""
        return list(self.l1) + list(reversed(self.l0))

    def to_bytes(self) -> bytes:
        return json.dumps(dataclasses.asdict(self)).encode()

    @classmethod
    def from_bytes(cls, raw: bytes) -> "HummockVersion":
        d = json.loads(raw)
        return cls(vid=d["vid"], committed_epoch=d["committed_epoch"],
                   l0=tuple(d.get("l0", ())), l1=tuple(d.get("l1", ())),
                   ddl=tuple(d.get("ddl", ())),
                   dropped_tables=tuple(d.get("dropped_tables", ())),
                   plan_format=d.get("plan_format", 1))

    def summary(self) -> dict:
        return {"vid": self.vid, "committed_epoch": self.committed_epoch,
                "l0": list(self.l0), "l1": list(self.l1),
                "dropped_tables": list(self.dropped_tables)}


# -- compaction task ----------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CompactTask:
    """One merge assignment from the version manager to a compactor.
    ``inputs`` are in lookup priority order (newest first) so the merge's
    duplicate-key rule is exactly the read path's."""

    task_id: int
    inputs: tuple
    dropped_tables: tuple = ()
    #: True when the task covers every live run: tombstones and dropped
    #: tables' rows may be discarded instead of rewritten
    bottom: bool = False
    base_vid: int = 0

    def to_wire(self) -> dict:
        return {"task_id": self.task_id, "inputs": list(self.inputs),
                "dropped_tables": list(self.dropped_tables),
                "bottom": self.bottom, "base_vid": self.base_vid}

    @classmethod
    def from_wire(cls, d: dict) -> "CompactTask":
        return cls(task_id=int(d["task_id"]), inputs=tuple(d["inputs"]),
                   dropped_tables=tuple(d.get("dropped_tables", ())),
                   bottom=bool(d.get("bottom", False)),
                   base_vid=int(d.get("base_vid", 0)))


def run_compact_task(store: ObjectStore, task: CompactTask,
                     target_sst_bytes: int = 4 << 20,
                     block_target_bytes: int = 4096) -> List[str]:
    """Execute one merge task: k-way merge the input runs (newest wins),
    drop dropped-table rows, drop tombstones iff bottom, and emit fresh
    L1 SSTs split at ``target_sst_bytes``. Pure function of the object
    store — runs identically in-process (background thread) and on the
    dedicated compactor worker. Crash-safe at every point: outputs are
    orphans until the meta-side version swap references them."""
    from ..common.failpoint import fail_point
    from ..common.tracing import CAT_STORAGE, span
    fail_point("compactor.task.start")
    dropped = set(task.dropped_tables)
    runs = [load_sst(store, name) for name in task.inputs]
    outputs: List[str] = []
    builder: Optional[SstBuilder] = None
    size = 0
    with span("compactor.task", epoch=None, cat=CAT_STORAGE,
              tid="compactor", task_id=task.task_id,
              inputs=len(task.inputs)):
        def flush_output() -> None:
            nonlocal builder, size
            if builder is None or builder.n_entries == 0:
                builder = None
                size = 0
                return
            name = (f"{SST_PREFIX}c{task.task_id:06d}-"
                    f"{len(outputs):03d}-{uuid.uuid4().hex[:8]}.sst")
            fail_point("compactor.output.write")
            store.put(name, builder.finish())
            outputs.append(name)
            builder = None
            size = 0

        for table_id, key, value in merge_iter(runs):
            fail_point("compactor.merge.step")
            if table_id in dropped:
                continue
            if value is None and task.bottom:
                continue
            if builder is None:
                builder = SstBuilder(block_target_bytes)
            builder.add(table_id, key, value)
            size += len(key) + (len(value) if value else 0) + 16
            if size >= target_sst_bytes:
                flush_output()
        flush_output()
    return outputs


# -- pinned snapshot reads ----------------------------------------------------

class PinnedSnapshot:
    """Consistent reads over one pinned version's runs: every lookup and
    scan resolves against the SAME SSTs no matter what compaction
    publishes meanwhile (reference: batch scans over a pinned
    HummockVersion, storage_table.rs reads at an epoch). Reads go through
    the object store — this is the path a serving replica or batch node
    without the writer's memory tier would use."""

    def __init__(self, manager, pin_id: int, version: HummockVersion,
                 store: ObjectStore):
        self._manager = manager
        self.pin_id = pin_id
        self.version = version
        self._store = store
        self._cache: Dict[str, Sstable] = {}
        self._folded: Optional[Dict[int, Dict[bytes, bytes]]] = None

    def _sst(self, name: str) -> Sstable:
        sst = self._cache.get(name)
        if sst is None:
            sst = load_sst(self._store, name)
            self._cache[name] = sst
        return sst

    def get(self, table_id: int, key: bytes) -> Optional[bytes]:
        if table_id in self.version.dropped_tables:
            return None
        for name in self.version.read_order():
            found, value = self._sst(name).lookup(table_id, key)
            if found:
                return value            # None = tombstone: stop here
        return None

    def fold_tables(self) -> Dict[int, Dict[bytes, bytes]]:
        """Materialize every table at this version (recovery/backup/
        batch full-scan base). Cached: the version is immutable, so a
        multi-table scan through one pin folds once, not once per
        table."""
        if self._folded is not None:
            return self._folded
        dropped = set(self.version.dropped_tables)
        tables: Dict[int, Dict[bytes, bytes]] = {}
        for name in self.version.fold_order():
            for table_id, key, value in self._sst(name).iter_entries():
                if table_id in dropped:
                    continue
                tbl = tables.setdefault(table_id, {})
                if value is None:
                    tbl.pop(key, None)
                else:
                    tbl[key] = value
        self._folded = tables
        return tables

    def iter_table(self, table_id: int) -> Iterator[Tuple[bytes, bytes]]:
        yield from sorted(self.fold_tables().get(table_id, {}).items())

    def unpin(self) -> None:
        self._manager.unpin_version(self.pin_id)

    def __enter__(self) -> "PinnedSnapshot":
        return self

    def __exit__(self, *exc) -> None:
        self.unpin()


# -- the store ----------------------------------------------------------------

class _LogFacade:
    """The slice of CheckpointLog's surface the Session drives
    (storage/checkpoint.py): DDL log, drop tombstones, background-fold
    lifecycle. Keeps ``session.store.log.*`` working unchanged across
    both durable tiers."""

    def __init__(self, store: "HummockStateStore"):
        self._store = store

    def exists(self) -> bool:
        return self._store.manager.exists()

    def ddl(self) -> List[str]:
        return self._store.manager.ddl()

    def log_ddl(self, sql: str) -> None:
        self._store.manager.log_ddl(sql)

    def drop_table(self, table_id: int) -> None:
        self._store.manager.drop_table(table_id)

    def compact(self) -> None:
        self._store.compact()

    def wait_compaction(self) -> None:
        self._store.wait_compaction()


class HummockStateStore(MemoryStateStore):
    """MemoryStateStore whose checkpoints persist as L0 SSTs under a
    meta-managed version (the Hummock backend of the reference's
    StateStoreImpl selection, store_impl.rs:49-64). Construction over a
    non-empty directory recovers the last committed version."""

    def __init__(self, data_dir: Optional[str] = None,
                 object_store: Optional[ObjectStore] = None,
                 l0_compact_trigger: Optional[int] = None,
                 inline_compaction: bool = True,
                 retry_policy=None):
        super().__init__()
        if object_store is None:
            if data_dir is None:
                raise ValueError("need data_dir or object_store")
            object_store = open_object_store(data_dir, retry_policy)
        # SST/manifest IO under the retry layer (idempotent whole-object
        # ops; common/retry.py) — the version manager shares the SAME
        # wrapped handle so vacuum and publish retry identically
        self.object_store = wrap_object_store(object_store, retry_policy)
        object_store = self.object_store
        from ..meta.hummock import HummockManager
        self.manager = HummockManager(object_store, l0_compact_trigger)
        self.log = _LogFacade(self)
        #: False routes compaction to a dedicated compactor worker the
        #: session drives (worker/compactor.py); True folds in a
        #: background thread like the segment log
        self.inline_compaction = inline_compaction
        self._compact_thread: Optional[threading.Thread] = None
        self._format_warned = False
        if self.manager.exists():
            epoch, tables = self._load_tables()
            self._committed = tables
            self.committed_epoch = epoch

    # -- recovery -------------------------------------------------------------

    def _load_tables(self) -> Tuple[int, Dict[int, Dict[bytes, bytes]]]:
        """Fold the current version's runs. A CROSS-process compactor may
        vacuum a run between our manifest read and the SST fetch; the
        manifest swap is atomic and runs are immutable, so re-reading
        converges — the same retry discipline as CheckpointLog."""
        for attempt in range(8):
            raw = self.object_store.get(VERSION_KEY)
            v = (HummockVersion.from_bytes(raw) if raw is not None
                 else HummockVersion.initial())
            if (v.plan_format != PLAN_FORMAT_VERSION
                    and not self._format_warned):
                self._format_warned = True
                import warnings
                warnings.warn(
                    f"data dir was written by plan-format {v.plan_format},"
                    f" this build is {PLAN_FORMAT_VERSION}: state-table "
                    "layout may not match the replayed DDL's rebuilt "
                    "plans — if recovery misbehaves, rebuild the MVs "
                    "(DROP/CREATE)")
            try:
                snap = PinnedSnapshot(self.manager, -1, v,
                                      self.object_store)
                return v.committed_epoch, snap.fold_tables()
            except FileNotFoundError:
                if attempt == 7:
                    raise
        raise AssertionError("unreachable")

    def refresh(self) -> int:
        """Adopt the latest PUBLISHED version: re-fold committed state
        and chase the committing process's epoch (serving sessions call
        this on every checkpoint notification — docs/control-plane.md).
        Local pending buffers are untouched; readers have none. Returns
        the committed epoch now visible."""
        if not self.manager.exists():
            return self.committed_epoch
        epoch, tables = self._load_tables()
        self.manager.reload()
        self._committed = tables
        self.committed_epoch = epoch
        return epoch

    def version_runs(self) -> list:
        """The SST runs the currently adopted version references —
        what a reader session reports to meta as its remote pin."""
        return sorted(self.manager.version.all_runs())

    # -- write path -----------------------------------------------------------

    def commit(self, epoch: int) -> None:
        if epoch <= self.committed_epoch:
            return
        from ..common.tracing import CAT_STORAGE, span
        deltas = {t: dict_view(layers)
                  for t, layers in self.pending_tables(epoch).items()}
        with span("HummockStateStore.commit", epoch=epoch,
                  cat=CAT_STORAGE, tid="storage", tables=len(deltas)):
            name = self._write_l0(epoch, deltas) if deltas else None
            try:
                self.manager.commit_epoch(epoch, name)
            except BaseException:
                if name is not None:
                    # failed publish: the uploaded object is a true
                    # orphan again — release it to vacuum
                    self.manager.abort_upload(name)
                raise
        super().commit(epoch)
        if self.inline_compaction:
            self._maybe_spawn_compact()

    def _write_l0(self, epoch: int,
                  deltas: Dict[int, Dict[bytes, Optional[bytes]]]) -> str:
        from ..common.failpoint import fail_point
        fail_point("hummock.sst.write")
        b = SstBuilder()
        for table_id in sorted(deltas):
            for key in sorted(deltas[table_id]):
                b.add(table_id, key, deltas[table_id][key])
        payload = b.finish()
        name = (f"{SST_PREFIX}e{epoch:012d}-"
                f"{uuid.uuid4().hex[:8]}.sst")
        # register BEFORE the put: a concurrently running vacuum (the
        # compaction pump's) must not delete the object in the window
        # between this put and the version publish referencing it. A
        # failed put aborts the registration HERE so the torn orphan is
        # not shielded from vacuum for the process lifetime.
        self.manager.begin_upload(name)
        try:
            try:
                # torn object mid-write: the version never references it,
                # so recovery ignores it and vacuum deletes it
                fail_point("hummock.sst.write.partial")
            except BaseException:
                self.object_store.put(name, payload[:16])
                raise
            self.object_store.put(name, payload)
        except BaseException:
            self.manager.abort_upload(name)
            raise
        return name

    def drop_table(self, table_id: int) -> None:
        super().drop_table(table_id)
        self.manager.drop_table(table_id)

    # -- reads at a pinned version --------------------------------------------

    def pin(self) -> PinnedSnapshot:
        pin_id, version = self.manager.pin_version()
        return PinnedSnapshot(self.manager, pin_id, version,
                              self.object_store)

    # -- compaction + vacuum --------------------------------------------------

    def _maybe_spawn_compact(self) -> None:
        t = self._compact_thread
        if t is not None and t.is_alive():
            return
        task = self.manager.get_compact_task()
        if task is None:
            return
        t = threading.Thread(target=self._compact_guarded, args=(task,),
                             daemon=True, name="hummock-compactor")
        self._compact_thread = t
        t.start()

    def _compact_guarded(self, task: CompactTask) -> None:
        try:
            outputs = run_compact_task(self.object_store, task)
            self.manager.report_compact_task(task.task_id, outputs)
            self.manager.vacuum()
        except Exception as e:  # never fatal: old runs stay valid
            self.manager.cancel_compact_task(task.task_id)
            import sys
            sys.stderr.write(
                f"hummock compaction failed (L0 keeps accumulating "
                f"until it succeeds): {e!r}\n")

    def wait_compaction(self) -> None:
        t = self._compact_thread
        if t is not None and t.is_alive():
            t.join()

    def compact(self, force: bool = True) -> None:
        """Synchronous full compaction cycle (tests / ctl): schedule,
        run, report, vacuum."""
        self.wait_compaction()
        task = self.manager.get_compact_task(force=force)
        if task is None:
            return
        try:
            outputs = run_compact_task(self.object_store, task)
        except BaseException:
            self.manager.cancel_compact_task(task.task_id)
            raise
        self.manager.report_compact_task(task.task_id, outputs)
        self.manager.vacuum()

    def vacuum(self) -> List[str]:
        return self.manager.vacuum()
