"""Explicit collective primitives over the process grid.

Reference: the distributed communication backend of SURVEY §2.2 —
Tile::send/isend/recv/irecv (include/slate/Tile.hh:131-135), the
radix-2/4 hypercube broadcast overlay (cubeBcastPattern,
src/internal/internal_comm.cc:72-117), listReduce hypercube sums
(BaseMatrix.hh:2221-2245), pivot MAXLOC allreduce
(src/internal/Tile_getrf.hh:268-270), and per-tile MPI tags.

TPU-native mapping (the BASELINE.json north star): these become XLA
collectives over the ICI mesh, expressed with shard_map when a driver
wants an explicit schedule instead of GSPMD's inferred one:

| reference                         | here                               |
|-----------------------------------|------------------------------------|
| tileBcast to rank set (hypercube) | bcast_from (masked psum — XLA      |
|                                   | routes optimally on the torus)     |
| listReduce (hypercube sum)        | reduce_sum (lax.psum)              |
| MPI_Allreduce(MAXLOC) pivot       | maxloc (pmax + index arithmetic)   |
| ring/tree neighbor exchange       | ring_shift (lax.ppermute)          |
| sub-communicator per panel        | mesh axis name subset              |

Each function is meant to be called INSIDE shard_map over the matching
mesh axes. No GPU-aware-MPI notion survives: data never leaves HBM, and
XLA schedules the DMAs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def axis_index(axis: str):
    return lax.axis_index(axis)


def bcast_from(x, root, axis: str):
    """Value of the shard at ``root`` along ``axis``, on every member.

    The tileBcast analog. Implemented as a masked psum — one all-reduce
    that XLA lowers to an optimal ICI pattern (the reference hand-builds
    a radix-2/4 hypercube of point-to-point sends for the same effect,
    internal_comm.cc:72-117)."""
    me = lax.axis_index(axis)
    masked = jnp.where(me == root, x, jnp.zeros_like(x))
    return lax.psum(masked, axis)


def reduce_sum(x, axis: str):
    """listReduce analog (hypercube sum → psum)."""
    return lax.psum(x, axis)


def reduce_max(x, axis: str):
    return lax.pmax(x, axis)


def maxloc(values, axis: str):
    """Global (max, argmax-shard, argmax-local) along a mesh axis.

    The pivot-search allreduce (MPI_Allreduce MAXLOC,
    Tile_getrf.hh:268-270): values is each shard's local candidate
    vector; returns the winning value, the owning shard index, and the
    index within that shard — everything the row-swap needs."""
    local_idx = jnp.argmax(values)
    local_max = values[local_idx]
    me = lax.axis_index(axis)
    gmax = lax.pmax(local_max, axis)
    # break ties toward the lowest shard index, like MPI_MAXLOC
    cand = jnp.where(local_max == gmax, me,
                     jnp.iinfo(jnp.int32).max).astype(jnp.int32)
    owner = lax.pmin(cand, axis)
    widx = jnp.where(me == owner, local_idx, 0)
    win_idx = lax.psum(widx, axis)
    return gmax, owner, win_idx


def ring_shift(x, axis: str, shift: int = 1):
    """Neighbor exchange around the ring (lax.ppermute) — the building
    block for ring pipelines (the reference's step-doubling tileSend/
    tileRecv exchanges, internal_ttqrt.cc:91-127, are log₂ rounds of
    this with strides 1,2,4,…)."""
    n = lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


def tree_reduce_pairwise(x, combine, axis: str):
    """Binary-tree reduction with an arbitrary combiner.

    The generalization the QR tree needs (internal_ttqrt's pairwise
    tpqrt combines): log₂(n) rounds; in round r, members exchange with
    partner = me XOR 2^r and combine(lo, hi). All members end with the
    root's result (butterfly/allreduce shape, like the reference's
    reduce-then-bcast)."""
    n = lax.axis_size(axis)
    me = lax.axis_index(axis)
    r = 1
    while r < n:
        partner_perm = [(i, i ^ r) for i in range(n) if (i ^ r) < n]
        # full butterfly: everyone exchanges with partner
        other = lax.ppermute(x, axis, [(i, i ^ r) for i in range(n)])
        lo_first = (me & r) == 0
        x = combine(
            jax.tree_util.tree_map(lambda a, b: jnp.where(lo_first, a, b),
                                   x, other),
            jax.tree_util.tree_map(lambda a, b: jnp.where(lo_first, b, a),
                                   x, other))
        r <<= 1
    return x
