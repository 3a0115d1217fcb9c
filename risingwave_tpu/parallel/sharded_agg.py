"""Sharded (multi-chip) grouped aggregation: vnode shuffle + per-shard upsert.

This is the TPU-native replacement for the reference's hash-dispatch exchange
between parallel HashAgg actors (reference: hash dispatcher
src/stream/src/executor/dispatch.rs:532, vnode partitioning
docs/consistent-hash.md): instead of serialize→gRPC→deserialize per edge, the
shuffle is a ``lax.all_to_all`` over the mesh's ICI *inside the jitted step*,
fused with the grouped-aggregation update (SURVEY.md §2.9, §5 "Distributed
communication backend").

Layout: every state array carries a leading shard axis sharded over the mesh
(``P('shard')``); inside ``shard_map`` each device sees its own [cap] slice
and runs the same pure AggCore code as the single-chip executor.

Routing: row → vnode (hash of group key) → owner shard (contiguous ranges).
Each local chunk of capacity C builds an [n, C] send buffer (a local chunk
has at most C rows for any one target, so per-target capacity C is always
sufficient — no ragged sizes, no recompiles), all-to-alls it, and upserts the
received [n*C] rows.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..common.chunk import Column, StreamChunk
from ..common.hashing import vnode_of, vnode_to_shard
from ..expr.agg import AggCall, count_star
from ..ops.grouped_agg import AggCore, AggState

SHARD_AXIS = "shard"


def shard_map_compat(f, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off — the hash
    shuffles communicate via explicit ``all_to_all``/``psum``, which the
    checker cannot always follow."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(n_devices: int) -> Mesh:
    devs = jax.devices()
    if len(devs) < n_devices:
        from ..common.config import MeshUnavailableError
        raise MeshUnavailableError(
            f"mesh needs {n_devices} devices, process has {len(devs)} "
            f"(on CPU force a virtual mesh with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return Mesh(np.array(devs[:n_devices]), (SHARD_AXIS,))


def chunk_sendbuf(chunk: StreamChunk, n_shards: int,
                  key_idx: Sequence[int]) -> StreamChunk:
    """Per-target send buffers for the hash shuffle: a StreamChunk whose
    leaves are [n_shards, C] — row block ``t`` holds this shard's rows
    owned by shard ``t`` (vnode hash of the key columns), front-packed.
    Pure elementwise/sort work, no collectives — so the multi-job group
    epoch can ``vmap`` it over a leading job axis and hand-batch the ONE
    all_to_all itself (ops/fused_sharded.shuffle_group_chunks)."""
    C = chunk.capacity
    key_cols = [chunk.columns[i] for i in key_idx]
    vn = vnode_of(key_cols)
    tgt = vnode_to_shard(vn, n_shards)
    # invisible rows route to a virtual bucket n (dropped)
    tgt_eff = jnp.where(chunk.vis, tgt, n_shards)
    order = jnp.argsort(tgt_eff)                   # stable
    sorted_tgt = tgt_eff[order]
    bucket_start = jnp.searchsorted(sorted_tgt, jnp.arange(n_shards))
    rank = jnp.arange(C) - bucket_start[jnp.clip(sorted_tgt, 0, n_shards - 1)]
    dest_row = jnp.where(sorted_tgt < n_shards, rank, C)  # drop invisible

    def to_sendbuf(arr):
        src = arr[order]
        buf = jnp.zeros((n_shards, C), arr.dtype)
        return buf.at[jnp.clip(sorted_tgt, 0, n_shards - 1), dest_row].set(
            src, mode="drop")

    return StreamChunk(
        to_sendbuf(chunk.ops), to_sendbuf(chunk.vis),
        tuple(Column(to_sendbuf(c.data), to_sendbuf(c.mask))
              for c in chunk.columns))


def shuffle_chunk_local(chunk: StreamChunk, n_shards: int,
                        key_idx: Sequence[int]) -> StreamChunk:
    """Inside-shard_map hash shuffle: returns the [n*C] chunk of rows this
    shard owns after the all-to-all. Pure; requires SHARD_AXIS binding."""
    C = chunk.capacity
    send = chunk_sendbuf(chunk, n_shards, key_idx)

    def a2a(x):
        return jax.lax.all_to_all(x, SHARD_AXIS, split_axis=0, concat_axis=0,
                                  tiled=True).reshape(n_shards * C)

    return jax.tree_util.tree_map(a2a, send)


def _squeeze_shard_axis(chunk: StreamChunk) -> StreamChunk:
    return jax.tree_util.tree_map(lambda x: x[0], chunk)


def build_sharded_agg_step(core: AggCore, mesh: Mesh,
                           unpack=_squeeze_shard_axis):
    """The jitted per-chunk program of the sharded agg over ``mesh``:
    ``(state, routed, chunk) -> (state, routed, rows_in)``, every array
    with a leading [n_shards] axis sharded over the mesh. ``unpack`` turns
    a shard's local view of the third argument into its local chunk: a
    stacked StreamChunk by default, the executor's packed stacks
    (``parallel/executors.unpack_like``) on the SQL path. A function of its
    own so that it can be compiled for a described mesh with no device
    attached (tests/test_pallas_compile.py)."""
    n = mesh.devices.size
    gk = tuple(core.group_keys)

    def sharded_agg_step(state: AggState, routed, chunk):
        # shard_map keeps the sharded leading axis as size-1; work on the
        # squeezed local view and restore the axis on the way out
        state = jax.tree_util.tree_map(lambda x: x[0], state)
        chunk = unpack(chunk)
        with jax.named_scope("shard_shuffle"):
            owned = shuffle_chunk_local(chunk, n, gk)
        new_state = core.apply_chunk(state, owned)
        routed = routed + jnp.sum(owned.vis, dtype=routed.dtype)
        rows_in = jax.lax.psum(jnp.sum(chunk.vis.astype(jnp.int32)),
                               SHARD_AXIS)
        new_state = jax.tree_util.tree_map(lambda x: x[None], new_state)
        return new_state, routed, rows_in

    return jax.jit(shard_map_compat(
        sharded_agg_step, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P())))


class ShardedHashAgg:
    """Data-parallel grouped agg over a device mesh.

    State arrays have shape [n_shards, ...] sharded on the leading axis; the
    jitted ``step`` does shuffle + upsert in one XLA program per chunk batch
    (one local chunk per shard per step; a device trace shows it as
    ``jit_sharded_agg_step``, the exchange under the scope
    ``shard_shuffle``). ``routed`` ([n_shards] int64, sharded like the
    state) is each shard's running count of the rows the exchange handed
    it, added up inside the step: how evenly the vnode map spreads the
    stream, read at the barrier with the flush's own fetch."""

    def __init__(self, mesh: Mesh, key_types, group_keys: Sequence[int],
                 agg_calls: Sequence[AggCall], table_capacity: int = 1 << 14,
                 out_capacity: int = 1024):
        self.mesh = mesh
        self.n = mesh.devices.size
        self.core = AggCore(key_types, group_keys, agg_calls, table_capacity,
                            out_capacity)
        self._sharding = NamedSharding(mesh, P(SHARD_AXIS))

        def local_init():
            return self.core.init_state()

        # replicate init per shard by vmapping over a dummy leading axis
        init = jax.vmap(lambda _: local_init())(jnp.arange(self.n))
        self.state = jax.device_put(
            init, jax.tree_util.tree_map(lambda _: self._sharding, init))
        self.routed = jax.device_put(jnp.zeros(self.n, jnp.int64),
                                     self._sharding)

        self._step = build_sharded_agg_step(self.core, mesh)

    def step(self, chunk_batch, program=None):
        """``chunk_batch``: arrays with leading [n_shards] axis (one local
        chunk per shard) — a stacked StreamChunk, or whatever form the
        ``program`` (a ``build_sharded_agg_step`` with an ``unpack`` of its
        own) takes."""
        self.state, self.routed, rows = (program or self._step)(
            self.state, self.routed, chunk_batch)
        return rows

    # -- host-side helpers ----------------------------------------------------

    def batch_chunks(self, chunks: Sequence[StreamChunk]) -> StreamChunk:
        """Stack n single-shard chunks into one sharded batch."""
        assert len(chunks) == self.n
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *chunks)
        return jax.device_put(
            stacked, jax.tree_util.tree_map(lambda _: self._sharding, stacked))

    def merged_group_values(self):
        """Gather all shards' live groups to host: {key_tuple: (lanes...)}.

        Test/debug surface — production egress goes through flush chunks."""
        st = jax.device_get(self.state)
        out = {}
        for s in range(self.n):
            occ = st.table.occupied[s]
            live = st.lanes[0][s] > 0
            for slot in np.nonzero(occ & live)[0]:
                key = tuple(
                    st.table.key_data[c][s][slot].item()
                    if st.table.key_mask[c][s][slot] else None
                    for c in range(len(st.table.key_data))
                )
                out[key] = tuple(
                    st.lanes[j][s][slot].item() for j in range(len(st.lanes))
                )
        return out


def build_sharded_q5_step(n_devices: int) -> None:
    """Driver dry-run: full sharded NEXmark q5-core step over an n-device
    mesh — window projection, vnode all-to-all shuffle, grouped count — one
    real step executed on tiny shapes."""
    from ..common.types import INT64, TIMESTAMP
    from ..connector import NexmarkConfig, NexmarkGenerator
    from ..expr import Literal, call, col

    mesh = make_mesh(n_devices)
    gen = NexmarkGenerator(NexmarkConfig(chunk_capacity=64))
    window = Literal(10_000_000, INT64)
    w_expr = call("tumble_start", col(5, TIMESTAMP), window)
    a_expr = col(0, INT64)

    agg = ShardedHashAgg(
        mesh, [INT64, INT64], [0, 1], [count_star()],
        table_capacity=1 << 10, out_capacity=64,
    )
    raw = [gen.next_bid_chunk() for _ in range(n_devices)]
    projected = [c.with_columns((w_expr.eval(c), a_expr.eval(c))) for c in raw]
    batch = agg.batch_chunks(projected)
    rows = agg.step(batch)
    jax.block_until_ready(rows)
    assert int(rows) == n_devices * 64, int(rows)

    # cross-check against host groupby
    from ..common.chunk import chunk_to_rows
    from ..common.types import Schema, Field
    sch = Schema.of(("w", INT64), ("a", INT64))
    expected: dict = {}
    for c in projected:
        for r in chunk_to_rows(c.project([0, 1]), sch):
            expected[r] = expected.get(r, 0) + 1
    got = {k: v[0] for k, v in agg.merged_group_values().items()}
    assert got == expected, f"sharded counts mismatch: {len(got)} vs {len(expected)}"
    print(f"dryrun_multichip({n_devices}): q5-core sharded step OK, "
          f"{len(got)} groups")
