"""The checkpoint delta's device half: select the dirty rows of a
device-resident state ON THE DEVICE and gather only those.

Every state that checkpoints incrementally keeps a ``ckpt_dirty`` mask
beside its columns (``AggState``: ``[capacity]``; ``JoinSideState``:
``[capacity, W]``). ``delta_window`` hands the host one window of the
dirty rows in ascending flat-index order; ``stream/state_delta.py`` walks
the windows and stages what they hold. What crosses to the host follows
the delta, never the capacity.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def dirty_slots(rank: jax.Array, lo: jax.Array, G: int):
    """``(slot[G], valid[G])`` of the dirty rows with rank in [lo, lo+G),
    by binary search over ``rank``, the inclusive prefix count of a dirty
    mask: log2(capacity) gather passes over ``G`` indices, no scatter.
    Invalid rows (past the last dirty one) read slot 0."""
    ks = lo.astype(jnp.int32) + jnp.arange(G, dtype=jnp.int32)
    pos = jnp.searchsorted(rank, ks + 1, side="left").astype(jnp.int32)
    valid = ks < rank[-1]
    return jnp.where(valid, pos, 0), valid


def delta_window(dirty: jax.Array, columns, lo: jax.Array, G: int):
    """``(n_dirty, valid[G], columns gathered to G rows)`` for the dirty
    ranks [lo, lo+G) of ``dirty``, read as ONE flat axis in row-major
    order. ``columns`` is any pytree of arrays whose leading axes are
    ``dirty``'s (they flatten the same way). Gathers only — nothing is
    scattered into a capacity-sized array, TPU scatters serialize per
    update — so a window costs its ``G`` rows, dirty or not."""
    flat = dirty.reshape(-1)
    rank = jnp.cumsum(flat.astype(jnp.int32))
    slot, valid = dirty_slots(rank, lo, G)
    gathered = jax.tree_util.tree_map(
        lambda c: c.reshape(flat.shape + c.shape[dirty.ndim:])[slot],
        columns)
    return rank[-1], valid, gathered
