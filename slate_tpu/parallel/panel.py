"""Explicitly-scheduled distributed panel factorization (shard_map).

Reference: src/internal/internal_getrf.cc:64-119 +
src/internal/Tile_getrf.hh:209-270 — the multi-threaded panel whose
per-column pivot search is an MPI_Allreduce(MAXLOC) across the panel's
ranks, followed by a pivot-row broadcast and a local rank-1 update.

Here the same schedule is written by hand with shard_map over the
grid's row axis: per column one ``maxloc`` collective (pmax + pmin +
psum), two masked-psum row broadcasts (the cross-shard row swap), and a
purely local rank-1 update. This is the explicit counterpart of the
GSPMD-inferred panel (ops/blocked.panel_getrf); `getrf` routes here
when ``Options.lu_dist_panel`` is set and a multi-device grid is
active. Measured comparison against the GSPMD panel: PERF_HISTORY.md.

Round-6 dispatch note: the default getrf now runs the PIVOT-FUSED
iterative outer loop (linalg/lu.py::_getrf_iter — permutation folded
into the trailing-update reads, deferred left swaps). The dist-panel
route keeps the 2×2 width recursion as its driver: the explicit
shard_map panel is a per-PANEL replacement and composes with either
outer loop; the recursion pairing is the one the tests pin. The
fused loop's deferred left swaps would subsume the reference's
cross-rank pivot-row exchange the same way (the suffix gathers become
collective-permutes on a mesh).

Round-7 notes. (1) LOOKAHEAD: the default outer loops now pipeline —
panel k+1 is factored between the next-panel slab and the remainder
of trailing update k (Options.lookahead; linalg/lu.py). On a mesh
this is exactly the schedule this module's explicit panel wants to
overlap with: the panel's collectives (or, on the default GSPMD
route, the replicated-panel all-gather) carry no data edge to the
remainder's sharded gemms. (2) BATCHED TOURNAMENT PANELS are the
multi-chip panel story for LU at scale: CALU's per-round chunk
factorizations run as ONE batched panel LU
(ops/blocked.panel_getrf_batched) — on a mesh, sharding the chunk
batch axis gives each device its own chunk rounds with only the
pairing exchanges between rounds, the reference's rank-tournament
(src/getrf_tntpiv.cc) without per-column collectives; the explicit
per-column maxloc schedule below remains the measured-against
reference arm. (3) The GSPMD default panel is now fed a REPLICATED
operand (blocked.replicate_on_grid — the tileBcast analog): bisected
this round, the pre-0.6 partitioner mis-lowers both the perm-compose
concatenate (blocked.lift_tail_perm) and the permutation gathers of a
row-sharded panel — the root causes of the round-6 "mesh getrf at
nb=64" open item, both now fixed + regression-pinned
(tests/test_lookahead.py).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.grid import ROW_AXIS
from ..obs import costs as obs_costs
from .collectives import bcast_from, maxloc


def dist_panel_getrf(a: jax.Array, grid) -> Tuple[jax.Array, jax.Array,
                                                  jax.Array]:
    """Partial-pivot LU of a row-sharded (m × w) panel with the explicit
    per-column maxloc/broadcast schedule described above.

    Returns (lu, perm, info) with gather semantics a[perm] = L·U; m must
    be divisible by the grid's row count (callers pad)."""
    m, w = a.shape
    p = grid.p
    if m % p:
        raise ValueError(f"dist_panel_getrf: m={m} not divisible by p={p}")
    mloc = m // p
    mesh = grid.mesh

    def body(al):
        me = lax.axis_index(ROW_AXIS)
        grow = me * mloc + jnp.arange(mloc)
        cols = jnp.arange(w)

        def col_step(j, carry):
            al, perm, info = carry
            colv = lax.dynamic_slice(al, (0, j), (mloc, 1))[:, 0]
            # local candidates: rows at global index >= j only
            score = jnp.where(grow >= j, jnp.abs(colv), -1.0)
            _, owner, widx = maxloc(score, ROW_AXIS)
            gpiv = owner * mloc + widx
            # the reference's pivot-row exchange (Tile_getrf.hh getrf_swap)
            # as two masked-psum broadcasts: row j and the pivot row
            oj = (j // mloc).astype(jnp.int32)
            jl = jnp.clip(j - me * mloc, 0, mloc - 1).astype(jnp.int32)
            zero = jnp.zeros((), jnp.int32)
            row_j = bcast_from(
                lax.dynamic_slice(al, (jl, zero), (1, w))[0], oj, ROW_AXIS)
            row_p = bcast_from(
                lax.dynamic_slice(al, (widx.astype(jnp.int32), zero),
                                  (1, w))[0], owner, ROW_AXIS)
            # swap: row j <- pivot row, pivot slot <- old row j
            upd = lax.dynamic_update_slice(al, row_p[None, :], (jl, zero))
            al = jnp.where(me == oj, upd, al)
            upd = lax.dynamic_update_slice(al, row_j[None, :],
                                           (widx.astype(jnp.int32), zero))
            al = jnp.where((me == owner) & (gpiv != j), upd, al)
            pj = perm[j]
            pp = perm[gpiv]
            perm = perm.at[j].set(pp).at[gpiv].set(pj)
            # local elimination below row j
            d = row_p[j]
            bad = jnp.isnan(jnp.abs(d)) | (jnp.abs(d) == 0)
            info = jnp.where((info == 0) & bad,
                             (j + 1).astype(jnp.int32), info)
            dsafe = jnp.where(bad, jnp.ones((), al.dtype), d)
            colv2 = lax.dynamic_slice(al, (0, j), (mloc, 1))[:, 0]
            lcol = jnp.where(grow > j, colv2 / dsafe, colv2)
            al = lax.dynamic_update_slice(al, lcol[:, None], (0, j))
            urow = jnp.where(cols > j, row_p, 0)
            lmask = jnp.where(grow > j, lcol, 0)
            al = al - jnp.outer(lmask, urow)
            return (al, perm, info)

        perm0 = jnp.arange(m, dtype=jnp.int32)
        al, perm, info = lax.fori_loop(
            0, w, col_step, (al, perm0, jnp.zeros((), jnp.int32)))
        return al, perm, info

    fn = shard_map(body, mesh=mesh,
                   in_specs=P(ROW_AXIS, None),
                   out_specs=(P(ROW_AXIS, None), P(), P()),
                   check_vma=False)
    a = lax.with_sharding_constraint(
        a, NamedSharding(mesh, P(ROW_AXIS, None)))
    # cost telemetry (round 9): per-shape AOT analysis of the compiled
    # panel (the per-column maxloc pmax/pmin/psum + two masked-psum row
    # broadcasts show up in the collective census; note the fori_loop
    # body is counted once per INSTRUCTION, so the census is a per-
    # column lower bound — PERF_HISTORY.md Round 9), credited to the process
    # bytes ledger on every eager call (obs/costs.py).
    return obs_costs.call_analyzed(
        fn, (a,), label=f"parallel.panel_getrf[p{p}]")
