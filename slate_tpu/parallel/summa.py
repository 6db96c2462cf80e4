"""Explicitly-scheduled distributed GEMM (SUMMA) via shard_map.

Reference: src/gemmC.cc — the stationary-C driver that per k-panel
broadcasts A's block column and B's block row to the ranks that need
them, with lookahead-deep pipelining (SURVEY §3.5).

This module is the hand-scheduled alternative to the GSPMD path in
linalg/blas3.gemm (which lets XLA infer the same collectives). It exists
for two reasons: (1) parity — it demonstrates the reference's explicit
communication schedule in XLA-collective form, per-panel broadcast and
all; (2) control — on real pods an explicit per-panel loop bounds the
replication workspace to one panel (the GSPMD all-gather materializes
the whole gathered operand), the same memory argument the reference's
lookahead makes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map

from ..core.grid import COL_AXIS, ROW_AXIS, ProcessGrid
from ..core.tiled_matrix import TiledMatrix, from_dense
from ..obs import costs as obs_costs
from .collectives import bcast_from


def gemm_summa(alpha, A: TiledMatrix, B: TiledMatrix, beta,
               C: TiledMatrix) -> TiledMatrix:
    """C ← α·A·B + β·C with an explicit SUMMA schedule over C's grid.

    All of A, B, C are 2D-block distributed over the (p, q) mesh. Each of
    the ``steps = p·q``-normalized panel rounds broadcasts one A block
    column along 'q' (the A-side listBcast of gemmC) and one B block row
    along 'p', then accumulates a local matmul."""
    grid = C.grid or A.grid or B.grid
    if grid is None or grid.size == 1:
        from ..linalg import blas3
        return blas3.gemm(alpha, A, B, beta, C)
    p, q = grid.p, grid.q
    mesh = grid.mesh

    a = A.dense_canonical()
    b = B.dense_canonical()
    c = C.dense_canonical()
    # pad shared/contraction dims to grid multiples so shard_map blocks
    # are even
    K = a.shape[1]
    Kpad = -(-K // (p * q)) * (p * q)
    m_pad = -(-a.shape[0] // p) * p
    n_pad = -(-b.shape[1] // q) * q
    a = jnp.pad(a, ((0, m_pad - a.shape[0]), (0, Kpad - K)))
    b = jnp.pad(b, ((0, Kpad - K), (0, n_pad - b.shape[1])))
    c = jnp.pad(c, ((0, m_pad - c.shape[0]), (0, n_pad - c.shape[1])))

    steps = p * q  # panel width = Kpad / (p·q): owner alternates evenly
    kb = Kpad // steps

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(ROW_AXIS, COL_AXIS), P(ROW_AXIS, COL_AXIS),
                  P(ROW_AXIS, COL_AXIS)),
        out_specs=P(ROW_AXIS, COL_AXIS))
    def summa(a_blk, b_blk, c_blk):
        # a_blk: (m/p, K/q); b_blk: (K/p, n/q); c_blk: (m/p, n/q)
        my_q = lax.axis_index(COL_AXIS)
        my_p = lax.axis_index(ROW_AXIS)
        Kq = a_blk.shape[1]
        Kp = b_blk.shape[0]

        def body(t, acc):
            k0 = t * kb  # global offset of this panel
            # which mesh column owns A's panel, and where inside its blk
            a_owner = k0 // Kq
            a_off = k0 - a_owner * Kq
            a_local = lax.dynamic_slice(
                a_blk, (0, jnp.where(my_q == a_owner, a_off, 0)),
                (a_blk.shape[0], kb))
            a_pan = bcast_from(a_local, a_owner, COL_AXIS)
            # which mesh row owns B's panel
            b_owner = k0 // Kp
            b_off = k0 - b_owner * Kp
            b_local = lax.dynamic_slice(
                b_blk, (jnp.where(my_p == b_owner, b_off, 0), 0),
                (kb, b_blk.shape[1]))
            b_pan = bcast_from(b_local, b_owner, ROW_AXIS)
            return acc + a_pan @ b_pan

        acc0 = jnp.zeros_like(c_blk)
        prod = lax.fori_loop(0, steps, body, acc0)
        return alpha * prod + beta * c_blk

    # cost telemetry (round 9): the first call per (grid, shape) AOT-
    # analyzes the compiled SUMMA program (XLA bytes-accessed + the
    # per-collective census — the two psum broadcasts per panel round),
    # and EVERY call credits the process bytes ledger under this label;
    # inside an outer jit it degrades to a plain call (the outer
    # program's compiler owns the analysis). See obs/costs.py.
    out = obs_costs.call_analyzed(
        summa, (a, b, c), label=f"parallel.summa[{p}x{q}]")
    out = out[: C.mt * C.nb, : C.nt * C.nb]
    return from_dense(out, C.nb, grid=grid, kind=C.kind, uplo=C.uplo,
                      logical_shape=C.shape)
