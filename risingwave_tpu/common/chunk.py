"""Columnar chunk format — the unit of dataflow.

TPU-first re-design of the reference's ``DataChunk``/``StreamChunk``
(reference: src/common/src/array/data_chunk.rs:59,
src/common/src/array/stream_chunk.rs:37-76): a chunk is a struct-of-arrays of
**fixed-capacity** device buffers plus a visibility mask, so every operator
step compiles once per (schema, capacity) and never again, regardless of how
many rows actually arrived (SURVEY.md §7 "Dynamic shapes vs XLA static
shapes").

Layout per chunk of capacity C:
  * ``ops``  int8[C]   — Insert / Delete / UpdateDelete / UpdateInsert
  * ``vis``  bool[C]   — row visibility (capacity padding ⇒ False)
  * per column: ``data`` dtype[C] and ``mask`` bool[C] (True = non-null)

UpdateDelete/UpdateInsert adjacency carries the same meaning as the
reference's stream-chunk op pairs (array/stream_chunk.rs:37-45): an update is
two adjacent rows with the same key.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Iterable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from .types import DataType, Schema

# Op codes (match the reference's Op enum order, array/stream_chunk.rs:37).
OP_INSERT = 0
OP_DELETE = 1
OP_UPDATE_DELETE = 2
OP_UPDATE_INSERT = 3

DEFAULT_CHUNK_CAPACITY = 1024


@struct.dataclass
class Column:
    data: jax.Array  # dtype[C]
    mask: jax.Array  # bool[C]; True = non-null


@struct.dataclass
class StreamChunk:
    """A batch of row-level change events (+ visibility padding)."""

    ops: jax.Array  # int8[C]
    vis: jax.Array  # bool[C]
    columns: tuple[Column, ...]

    # -- static views ---------------------------------------------------------

    @property
    def capacity(self) -> int:
        return self.ops.shape[0]

    def cardinality(self) -> jax.Array:
        """Number of visible rows (traced value)."""
        return jnp.sum(self.vis)

    # -- functional updates ---------------------------------------------------

    def with_vis(self, vis: jax.Array) -> "StreamChunk":
        return self.replace(vis=vis)

    def mask_vis(self, keep: jax.Array) -> "StreamChunk":
        return self.replace(vis=self.vis & keep)

    def project(self, indices: Sequence[int]) -> "StreamChunk":
        return self.replace(columns=tuple(self.columns[i] for i in indices))

    def with_columns(self, columns: Sequence[Column]) -> "StreamChunk":
        return self.replace(columns=tuple(columns))

    def append_columns(self, columns: Sequence[Column]) -> "StreamChunk":
        return self.replace(columns=self.columns + tuple(columns))

    # Insert/delete sign per row: +1 for Insert/UpdateInsert, -1 for
    # Delete/UpdateDelete, 0 for invisible. The universal "delta weight" used
    # by aggregation and materialization.
    def signs(self) -> jax.Array:
        pos = (self.ops == OP_INSERT) | (self.ops == OP_UPDATE_INSERT)
        return jnp.where(self.vis, jnp.where(pos, 1, -1).astype(jnp.int32), 0)


@struct.dataclass
class ChunkBatch:
    """K stacked StreamChunks — every array carries a leading [K] axis.

    The dispatch-amortization unit: one host→device dispatch covers K chunks
    (a ``lax.scan`` over the leading axis inside the consuming executor's
    jitted step), instead of K dispatches. Stateless executors transform
    the whole batch with one vmapped step; executors without a batched
    path fall back to per-chunk iteration (``at``)."""

    chunk: StreamChunk  # arrays: [K, C, ...]

    @property
    def num_chunks(self) -> int:
        return self.chunk.ops.shape[0]

    @property
    def chunk_capacity(self) -> int:
        return self.chunk.ops.shape[1]

    def at(self, i: int) -> StreamChunk:
        return jax.tree_util.tree_map(lambda x: x[i], self.chunk)


def stack_chunks(chunks: Sequence[StreamChunk]) -> ChunkBatch:
    return ChunkBatch(jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *chunks))


@dataclasses.dataclass(frozen=True)
class HostChunk:
    """One chunk's columns still on the host: what a connector hands over
    and ``stage_chunks`` takes to the device. ``arrays[i][:n]`` are the
    physical values of column ``i``, ``masks[i][:n]`` its validity (``None``:
    no column has a null), ``ops[:n]`` the row ops (``None``: all Insert)."""

    schema: Schema
    arrays: Sequence[np.ndarray]
    n: int
    capacity: int
    masks: Optional[Sequence[np.ndarray]] = None
    ops: Optional[np.ndarray] = None

    def __post_init__(self):
        if self.n > self.capacity:
            raise ValueError(f"{self.n} rows > capacity {self.capacity}")

    def dtypes(self) -> tuple:
        return tuple(f.type.np_dtype for f in self.schema)

    def has_flags(self) -> bool:
        """A null or a non-Insert op: something ``n`` alone cannot give."""
        n = self.n
        return ((self.masks is not None
                 and not all(m[:n].all() for m in self.masks))
                or (self.ops is not None and bool(np.any(self.ops[:n]))))


@dataclasses.dataclass
class StagedCounts:
    """What ``stage_chunks`` handed to the device, added up in the caller's
    own object (``Session.tick`` passes one per ``source.feed`` span, whose
    args these are): ``transfers`` — host column buffers, one per dtype
    stack; ``bytes_staged`` — their bytes; ``dispatches`` — unpack programs
    run; ``row_ids`` — rows given a ``_row_id`` in those programs. Each
    dispatch carries small arguments that are counted with it and not as
    transfers: the int32 row count per chunk and, with a row-id sequence,
    the int64 first id per chunk."""

    transfers: int = 0
    bytes_staged: int = 0
    dispatches: int = 0
    row_ids: int = 0


@dataclasses.dataclass
class RowIdSequence:
    """Where a source's hidden ``_row_id`` stands: a host counter owned by
    whoever feeds the source. An id is ``shard_id << 48 | seq`` (reference:
    src/common/src/util/row_id.rs — a prefix per parallel source so their
    ids never collide, a serial number below it); ``next`` is the ``seq``
    of the next row. Recovery restarts it above every id handed out before
    the crash (``SplitReader.rows_emitted``)."""

    SEQ_BITS = 48

    shard_id: int = 0
    next: int = 0

    def take(self, n: int) -> int:
        """The id of the next row; the ``n`` rows from it on are taken."""
        first = (self.shard_id << self.SEQ_BITS) | self.next
        self.next += n
        return first

    @classmethod
    def seq_of(cls, row_id: int) -> int:
        """The serial number under an id's shard prefix."""
        return row_id & ((1 << cls.SEQ_BITS) - 1)


def row_id_data(first, rank) -> jax.Array:
    """THE ``_row_id`` arithmetic, for staged and for device chunks alike:
    ``first`` is the id of the chunk's first visible row (``RowIdSequence.
    take``), ``rank`` a row's position among the visible rows. Invisible
    rows get ids too; nobody reads them. The column's mask is ``vis``."""
    return first + rank.astype(jnp.int64)


@jax.jit
def append_row_ids(first: jax.Array, chunk: StreamChunk):
    """``_row_id`` for a chunk that is ALREADY on the device (a test's push
    into a reader-less source, a table's INSERT): the column appended and
    filled in one dispatch. ``first`` rides as a device scalar because only
    the device knows how many rows are visible. -> (the first id after
    this chunk, the chunk with its ids)."""
    vis = chunk.vis
    seen = jnp.cumsum(vis)
    return first + seen[-1], chunk.append_columns(
        (Column(row_id_data(first, seen - vis), vis),))


def unpack_chunk(layout, ns, firsts, *stacks) -> tuple:
    """Device side of ``stage_chunks``: slice the staged ``[K, rows, cap]``
    stacks back into K chunks' arrays — per chunk ``(ops, vis, datas,
    masks)``; a column without a mask here has ``vis`` as its mask. With
    ``with_ids`` in the layout ``firsts`` holds each chunk's first id, and
    ``datas`` ends in the ``_row_id`` column (never a mask of its own)."""
    stack_of, flags, cap, k, with_ids = layout
    rows = jnp.arange(cap, dtype=jnp.int32)
    out = []
    for c in range(k):
        live = rows < ns[c]
        datas = tuple(stacks[s][c, r] for s, r in stack_of)
        if with_ids:
            datas += (row_id_data(firsts[c], rows),)
        if flags:
            # the int8 stack: one row per column mask, then the ops
            int8s = stacks[-1][c]
            masks = tuple(int8s[i] != 0 for i in range(len(stack_of)))
            ops = int8s[len(stack_of)]
        else:
            masks = ()
            ops = jnp.zeros(cap, jnp.int8)  # all Insert (append-only source)
        out.append((ops, live, datas, masks))
    return tuple(out)


_unpack = jax.jit(unpack_chunk, static_argnums=(0,))


def _stage_run(run: Sequence[HostChunk], counts: Optional[StagedCounts],
               row_ids: Optional[RowIdSequence]) -> list:
    """``stage_chunks`` for chunks of ONE dtype layout and capacity."""
    dtypes, cap, k = run[0].dtypes(), run[0].capacity, len(run)
    order = list(dict.fromkeys(dtypes))
    rows = [0] * len(order)
    stack_of = []                   # column -> (its dtype's stack, row)
    for d in dtypes:
        s = order.index(d)
        stack_of.append((s, rows[s]))
        rows[s] += 1
    bufs = [np.zeros((k, r, cap), dt) for dt, r in zip(order, rows)]
    flags = any(h.has_flags() for h in run)
    if flags:
        bufs.append(np.zeros((k, len(dtypes) + 1, cap), np.int8))
    for c, h in enumerate(run):
        for i, (s, r) in enumerate(stack_of):
            bufs[s][c, r, :h.n] = h.arrays[i][:h.n]
            if flags:
                bufs[-1][c, i, :h.n] = (True if h.masks is None
                                        else h.masks[i][:h.n])
        if flags and h.ops is not None:
            bufs[-1][c, len(dtypes), :h.n] = h.ops[:h.n]
    ns = np.array([h.n for h in run], np.int32)
    firsts = (None if row_ids is None else
              np.array([row_ids.take(h.n) for h in run], np.int64))
    if counts is not None:
        counts.transfers += len(bufs)
        counts.bytes_staged += sum(b.nbytes for b in bufs)
        counts.dispatches += 1
        counts.row_ids += 0 if firsts is None else int(ns.sum())
    # an output array costs the host about as much as a small transfer (55 us
    # each on a v5e, PERF.md §6 PR 30): ONE array is a chunk's vis and the
    # mask of every column that brought none (all of them without nulls;
    # the _row_id always), not a copy each
    return [StreamChunk(ops, live, tuple(
                Column(d, m)
                for d, m in zip(datas, masks + (live,) * (len(datas) - len(masks)))))
            for ops, live, datas, masks
            in _unpack((tuple(stack_of), flags, cap, k, firsts is not None),
                       ns, firsts, *bufs)]


def stage_chunks(host: Sequence[HostChunk],
                 counts: Optional[StagedCounts] = None,
                 row_ids: Optional[RowIdSequence] = None) -> list:
    """The one way host columns become device chunks.

    Every run of chunks with one dtype layout and capacity (a feed's chunks
    of a barrier) is stacked by dtype into one zero-padded ``[K, rows,
    capacity]`` host buffer per distinct dtype and handed to the device in
    ONE jitted call (``jit_unpack_chunk``: the buffers ride as its arguments
    and the row counts as a runtime vector, so it compiles once per layout,
    capacity and K) which slices the K chunks' columns back out. Nothing
    that can be computed is transferred: without nulls every mask, like
    ``vis``, is ``arange(capacity) < n``, and without a non-Insert op
    ``ops`` is zeros, made on the device; only a run that has either stages
    one more int8 stack with all masks and the ops. The buffers are
    allocated per call and never written again, so the asynchronous
    transfer reads what was staged. ``counts``, where given, is added to.

    ``row_ids``, where given, is the sequence of a source whose chunks
    these are: every chunk gets one more column, the hidden ``_row_id``
    (``row_id_data``: the chunk's first id, one small runtime vector
    more, plus the row's position), made by the same dispatch, and the
    sequence advances by each chunk's rows as the chunk is staged."""
    out = []
    for _, run in itertools.groupby(
            host, key=lambda h: (h.dtypes(), h.capacity)):
        out.extend(_stage_run(list(run), counts, row_ids))
    return out


def host_rows(
    schema: Schema,
    rows: Sequence[Sequence[Any]],
    ops: Optional[Sequence[int]] = None,
    capacity: int = DEFAULT_CHUNK_CAPACITY,
    physical: bool = False,
) -> HostChunk:
    """Python rows → host columns (``make_chunk`` before the device)."""
    n = len(rows)
    datas, masks = [], []
    for ci, field in enumerate(schema):
        t = field.type
        data = np.full(n, t.null_sentinel(), t.np_dtype)
        mask = np.zeros(n, bool)
        for ri, row in enumerate(rows):
            v = row[ci]
            if v is not None:
                data[ri] = v if physical else t.to_physical(v)
                mask[ri] = True
        datas.append(data)
        masks.append(mask)
    return HostChunk(schema, datas, n, capacity, masks,
                     None if ops is None else np.asarray(list(ops), np.int8))


def make_chunk(
    schema: Schema,
    rows: Sequence[Sequence[Any]],
    ops: Optional[Sequence[int]] = None,
    capacity: int = DEFAULT_CHUNK_CAPACITY,
    physical: bool = False,
) -> StreamChunk:
    """Host constructor: python rows → padded device chunk.

    ``physical=True`` takes raw physical values (state-table storage form)
    and skips logical encoding — the recovery-reload fast path."""
    return stage_chunks([host_rows(schema, rows, ops, capacity, physical)])[0]


def empty_chunk(schema: Schema, capacity: int = DEFAULT_CHUNK_CAPACITY) -> StreamChunk:
    return make_chunk(schema, [], capacity=capacity)


def physical_chunk(schema: Schema, rows: Sequence[Sequence[Any]],
                   capacity: int) -> StreamChunk:
    """Rows of raw *physical* values → chunk (see make_chunk(physical=True))."""
    return make_chunk(schema, rows, capacity=capacity, physical=True)


def chunk_to_rows(
    chunk: StreamChunk, schema: Schema, with_ops: bool = False,
    physical: bool = False,
) -> list:
    """Device chunk → visible python rows (host sync; tests & egress only).

    ``physical=True`` skips logical decoding (dictionary lookups, decimal
    descaling) and returns raw physical scalars — the fast path for writing
    into state tables, which store physical values."""
    ops = np.asarray(chunk.ops)
    vis = np.asarray(chunk.vis)
    datas = [np.asarray(c.data) for c in chunk.columns]
    masks = [np.asarray(c.mask) for c in chunk.columns]
    if physical:
        # column at a time: one ``tolist`` a column, not one ``item`` a cell
        at = np.flatnonzero(vis)
        cols = [d[at].tolist() if m[at].all() else
                [v if ok else None for v, ok in zip(d[at].tolist(),
                                                    m[at].tolist())]
                for d, m in zip(datas[:len(schema)], masks)]
        rows = list(zip(*cols)) if cols else [()] * len(at)
        return list(zip(ops[at].tolist(), rows)) if with_ops else rows
    out = []
    for i in range(chunk.capacity):
        if not vis[i]:
            continue
        if physical:
            row = tuple(
                datas[ci][i].item() if masks[ci][i] else None
                for ci in range(len(schema))
            )
        else:
            row = tuple(
                schema[ci].type.to_python(datas[ci][i]) if masks[ci][i] else None
                for ci in range(len(schema))
            )
        out.append((int(ops[i]), row) if with_ops else row)
    return out


def compact_chunk_host(chunk: StreamChunk) -> StreamChunk:
    """Pack visible rows to the front (host-side; not for jitted paths)."""
    vis = np.asarray(chunk.vis)
    idx = np.nonzero(vis)[0]
    cap = chunk.capacity
    pad = np.zeros(cap - len(idx), np.int64)
    sel = np.concatenate([idx, pad]).astype(np.int64)
    new_vis = np.zeros(cap, bool)
    new_vis[: len(idx)] = True
    return StreamChunk(
        jnp.asarray(np.asarray(chunk.ops)[sel]),
        jnp.asarray(new_vis),
        tuple(
            Column(jnp.asarray(np.asarray(c.data)[sel]), jnp.asarray(np.asarray(c.mask)[sel]))
            for c in chunk.columns
        ),
    )


def _update_units(chunk: StreamChunk):
    """Rows grouped into emission units: a visible U- immediately followed by
    a visible U+ forms one 2-row unit (the reference's chunk builder reserves
    two slots so update pairs never split across chunks,
    src/common/src/array/stream_chunk.rs:37-45); every other visible row is a
    1-row unit. Returns (unit_index int64[C], attached bool[C], unit_start)."""
    ops, vis = chunk.ops, chunk.vis
    prev_ud = jnp.concatenate([
        jnp.zeros(1, jnp.bool_),
        (ops[:-1] == OP_UPDATE_DELETE) & vis[:-1],
    ])
    attached = vis & (ops == OP_UPDATE_INSERT) & prev_ud
    unit_start = vis & ~attached
    unit_index = jnp.cumsum(unit_start) - 1  # valid where vis
    return unit_index, attached, unit_start


def count_units(chunk: StreamChunk) -> jax.Array:
    """Number of emission units in the chunk (jit-friendly scalar)."""
    _, _, unit_start = _update_units(chunk)
    return jnp.sum(unit_start)


def gather_units_window(chunk: StreamChunk, lo: jax.Array, out_capacity: int) -> StreamChunk:
    """Pack the units with index in [lo, lo + out_capacity//2) into a fresh
    chunk of ``out_capacity`` rows (2 slots per unit; vis masks the gaps).

    Pure and shape-static: drive from the host as
    ``for lo in range(0, int(count_units(c)), out_capacity//2)``."""
    G = out_capacity // 2
    C = out_capacity
    unit_index, attached, _ = _update_units(chunk)
    in_win = chunk.vis & (unit_index >= lo) & (unit_index < lo + G)
    pos = jnp.where(
        in_win, 2 * (unit_index - lo) + attached.astype(jnp.int64), C
    ).astype(jnp.int32)
    ops = jnp.zeros(C, jnp.int8).at[pos].set(chunk.ops, mode="drop")
    vis = jnp.zeros(C, jnp.bool_).at[pos].set(True, mode="drop")
    cols = tuple(
        Column(
            jnp.zeros(C, c.data.dtype).at[pos].set(c.data, mode="drop"),
            jnp.zeros(C, jnp.bool_).at[pos].set(c.mask, mode="drop"),
        )
        for c in chunk.columns
    )
    return StreamChunk(ops, vis, cols)


def flatten_shards(chunk: StreamChunk) -> StreamChunk:
    """A shard-batched chunk ([n, cap, ...] arrays) → ONE chunk of
    n*cap rows (row-major concat; vis already masks invalid rows). The
    sharded executors' egress path: one device op replaces the per-shard
    host slicing loop (VERDICT r3 item 9)."""
    def f(x):
        return x.reshape((-1,) + x.shape[2:])
    return jax.tree_util.tree_map(f, chunk)


def pad_chunk(chunk: StreamChunk, new_capacity: int) -> StreamChunk:
    """Grow a chunk's capacity with invisible padding rows (no-op if already
    at least ``new_capacity``)."""
    cap = chunk.capacity
    if cap >= new_capacity:
        return chunk
    extra = new_capacity - cap

    def pad(a):
        return jnp.concatenate([a, jnp.zeros((extra,) + a.shape[1:], a.dtype)])

    return StreamChunk(
        pad(chunk.ops), pad(chunk.vis),
        tuple(Column(pad(c.data), pad(c.mask)) for c in chunk.columns),
    )


def concat_rows(chunks: Iterable[StreamChunk], schema: Schema) -> list:
    rows = []
    for c in chunks:
        rows.extend(chunk_to_rows(c, schema))
    return rows
