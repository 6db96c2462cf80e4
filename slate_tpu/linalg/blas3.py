"""Parallel BLAS-3 drivers.

Reference: the L4 driver files src/gemm.cc, src/gemmA.cc, src/gemmC.cc,
src/hemm*.cc, src/symm.cc, src/herk.cc, src/her2k.cc, src/syrk.cc,
src/syr2k.cc, src/trmm.cc, src/trsm*.cc, src/gbmm.cc, src/hbmm.cc,
src/tbsm.cc and their L3 internals (src/internal/internal_gemm.cc etc.).

TPU-native design: each driver is one jit-able pure function over padded
dense storage. The reference's hand-scheduled communication
(tileBcast/listBcast of A-column/B-row panels, gemmC src/gemmC.cc;
listReduce hypercube sums for the stationary-A variant,
src/internal/internal_gemmA.cc) is replaced by GSPMD sharding constraints:

- MethodGemm.C (stationary-C, SUMMA): C is constrained to the 2D grid
  spec; XLA all-gathers A's column panels along 'q' and B's row panels
  along 'p' over ICI — precisely the reference's bcast sets.
- MethodGemm.A (stationary-A): A keeps the 2D spec, B is gathered along
  'p', and the contraction leaves partial products on the 'q' axis that
  XLA combines with reduce-scatter/all-reduce into C's owners — precisely
  the reference's listReduce.

Method::Auto picks A iff C is narrow (reference select_algo,
src/gemm.cc:12-23).

The per-rank batched tile BLAS of the reference (device_regions_build +
blas::batch::gemm, src/internal/internal_gemm.cc:354-511) has no explicit
analog: each device's local shard participates in ONE large MXU matmul,
which is strictly better than a batch of nb×nb calls.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.exceptions import SlateError
from ..core.grid import COL_AXIS, ROW_AXIS
from ..core.tiled_matrix import (TiledMatrix, from_dense,
                                 unit_pad_diag)
from ..core.types import (Diag, MatrixKind, MethodGemm, Op, Options, Side,
                          Uplo, DEFAULT_OPTIONS)
from ..ops import blocked, tile_ops


def _wrap_like(c: TiledMatrix, data: jax.Array) -> TiledMatrix:
    """Repackage a canonical padded result as a matrix like c."""
    out = from_dense(data, c.nb, grid=c.grid, kind=c.kind, uplo=c.uplo,
                     diag=c.diag, kl=c.kl, ku=c.ku,
                     logical_shape=c.shape)
    return out


def _check_dims(am, an, bm, bn, cm, cn):
    if an != bm or am != cm or bn != cn:
        raise SlateError(f"gemm dimension mismatch: ({am}x{an})·({bm}x{bn})"
                         f" -> ({cm}x{cn})")


def _grid_of(*mats):
    for m in mats:
        if m.grid is not None and m.grid.size > 1:
            return m.grid
    return None


def _constrain_product(left, right, grid):
    """Stationary-C constraint recipe for one product left·right: the
    contraction panels are gathered (the reference's listBcast sets,
    src/gemmC.cc) while the result stays 2D-sharded."""
    mesh = grid.mesh
    left = jax.lax.with_sharding_constraint(
        left, NamedSharding(mesh, P(ROW_AXIS, None)))
    right = jax.lax.with_sharding_constraint(
        right, NamedSharding(mesh, P(None, COL_AXIS)))
    return left, right


def _constrain_out(out, grid):
    return jax.lax.with_sharding_constraint(
        out, NamedSharding(grid.mesh, grid.spec_2d()))


def gemm(alpha, A: TiledMatrix, B: TiledMatrix, beta, C: TiledMatrix,
         opts: Options = DEFAULT_OPTIONS) -> TiledMatrix:
    """C ← α·op(A)·op(B) + β·C  (slate::gemm, src/gemm.cc)."""
    am, an = A.shape
    bm, bn = B.shape
    cm, cn = C.shape
    _check_dims(am, an, bm, bn, cm, cn)

    method = opts.method_gemm
    if method is MethodGemm.Auto:
        # reference: gemmA iff C is narrow (B.nt() < 2), src/gemm.cc:12-23
        method = MethodGemm.A if B.nt < 2 else MethodGemm.C
    if method is MethodGemm.SUMMA:
        # explicit collective schedule (shard_map ring broadcasts) —
        # the hand-written analog of the reference's gemmC bcast loop
        from ..parallel.summa import gemm_summa
        out = gemm_summa(alpha, A, B, beta, C)
        return out

    a = A.dense_canonical()
    b = B.dense_canonical()
    c = C.dense_canonical()

    grid = _grid_of(C, A, B)
    if grid is not None:
        mesh = grid.mesh
        if method is MethodGemm.C:
            # stationary-C SUMMA: gather k-panels, keep C 2D-sharded
            a, b = _constrain_product(a, b, grid)
        else:
            # stationary-A: A keeps 2D shards; contraction dim sharded on
            # 'q' => XLA reduces partial products into C (listReduce analog)
            a = jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, P(ROW_AXIS, COL_AXIS)))
            b = jax.lax.with_sharding_constraint(
                b, NamedSharding(mesh, P(COL_AXIS, None)))
    out = tile_ops.gemm(alpha, a, b, beta, c)
    if grid is not None:
        out = _constrain_out(out, grid)
    return _wrap_like(C, out)


def symm(side: Side, alpha, A: TiledMatrix, B: TiledMatrix, beta,
         C: TiledMatrix, opts: Options = DEFAULT_OPTIONS) -> TiledMatrix:
    """C ← α·A·B + β·C with A symmetric (slate::symm, src/symm.cc).

    The reference's hemmA/hemmC method split (bcast vs reduce) maps to the
    same sharding-constraint recipes as gemm."""
    if A.kind not in (MatrixKind.Symmetric, MatrixKind.Hermitian):
        raise SlateError("symm: A must be symmetric")
    a = A.full_dense_canonical()
    b = B.dense_canonical()
    c = C.dense_canonical()
    grid = _grid_of(C, A, B)
    if side is Side.Left:
        if grid is not None:
            a, b = _constrain_product(a, b, grid)
        out = alpha * (a @ b) + beta * c
    else:
        if grid is not None:
            b, a = _constrain_product(b, a, grid)
        out = alpha * (b @ a) + beta * c
    if grid is not None:
        out = _constrain_out(out, grid)
    return _wrap_like(C, out)


def hemm(side: Side, alpha, A: TiledMatrix, B: TiledMatrix, beta,
         C: TiledMatrix, opts: Options = DEFAULT_OPTIONS) -> TiledMatrix:
    """slate::hemm (src/hemm.cc); A Hermitian.

    MethodHemm dispatch (the reference's hemmA/hemmC split,
    src/hemmA.cc vs src/hemmC.cc): C = stationary-C (gather the
    contraction panels, the listBcast recipe); A = stationary-A (A keeps
    its 2D shards, partial products reduce into C — the listReduce
    recipe). Auto = A iff C is a single block column (reference
    select_algo logic)."""
    from ..core.types import MethodHemm
    if A.kind is not MatrixKind.Hermitian:
        raise SlateError("hemm: A must be Hermitian")
    a = A.full_dense_canonical()
    b = B.dense_canonical()
    c = C.dense_canonical()
    method = opts.method_hemm
    if method is MethodHemm.Auto:
        method = MethodHemm.A if C.nt < 2 else MethodHemm.C
    grid = _grid_of(C, A, B)
    if grid is not None:
        mesh = grid.mesh
        if method is MethodHemm.A:
            # stationary-A: shard A both ways; the contraction dim of
            # the other operand rides the matching axis so XLA reduces
            # partial products into C's owners
            a = jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, P(ROW_AXIS, COL_AXIS)))
            if side is Side.Left:
                b = jax.lax.with_sharding_constraint(
                    b, NamedSharding(mesh, P(COL_AXIS, None)))
            else:
                b = jax.lax.with_sharding_constraint(
                    b, NamedSharding(mesh, P(None, ROW_AXIS)))
        else:
            if side is Side.Left:
                a, b = _constrain_product(a, b, grid)
            else:
                b, a = _constrain_product(b, a, grid)
    out = alpha * (a @ b) + beta * c if side is Side.Left \
        else alpha * (b @ a) + beta * c
    if grid is not None:
        out = _constrain_out(out, grid)
    return _wrap_like(C, out)


def _constrain_rank_k(a, grid):
    """Stationary-C constraint pair for a rank-k factor appearing on both
    sides of the product A·op(A): the left occurrence keeps its rows on
    the grid's row axis, the right occurrence (transposed in the product)
    keeps its rows on the column axis, so XLA gathers exactly the
    reference's herk bcast sets (src/internal/internal_herk.cc) while C
    stays 2D-sharded."""
    mesh = grid.mesh
    left = jax.lax.with_sharding_constraint(
        a, NamedSharding(mesh, P(ROW_AXIS, None)))
    right = jax.lax.with_sharding_constraint(
        a, NamedSharding(mesh, P(COL_AXIS, None)))
    return left, right


def syrk(alpha, A: TiledMatrix, beta, C: TiledMatrix,
         opts: Options = DEFAULT_OPTIONS) -> TiledMatrix:
    """C ← α·op(A)·op(A)ᵀ + β·C, C symmetric (slate::syrk, src/syrk.cc)."""
    if C.kind is not MatrixKind.Symmetric:
        raise SlateError("syrk: C must be symmetric")
    a = A.dense_canonical()
    c = C.dense_canonical()
    grid = _grid_of(C, A)
    if grid is None:
        out = tile_ops.syrk(alpha, a, beta, c, uplo=C.uplo)
    else:
        al, ar = _constrain_rank_k(a, grid)
        out = tile_ops._keep_triangle(alpha * (al @ ar.T) + beta * c, c,
                                      C.uplo)
        out = _constrain_out(out, grid)
    return _wrap_like(C, out)


def herk(alpha, A: TiledMatrix, beta, C: TiledMatrix,
         opts: Options = DEFAULT_OPTIONS) -> TiledMatrix:
    """C ← α·op(A)·op(A)ᴴ + β·C, C Hermitian (slate::herk, src/herk.cc)."""
    if C.kind is not MatrixKind.Hermitian:
        raise SlateError("herk: C must be Hermitian")
    a = A.dense_canonical()
    c = C.dense_canonical()
    grid = _grid_of(C, A)
    if grid is None:
        out = tile_ops.herk(alpha, a, beta, c, uplo=C.uplo)
    else:
        al, ar = _constrain_rank_k(a, grid)
        out = tile_ops._keep_triangle(
            alpha * (al @ jnp.conj(ar).T) + beta * c, c, C.uplo)
        out = _constrain_out(out, grid)
    return _wrap_like(C, out)


def syr2k(alpha, A: TiledMatrix, B: TiledMatrix, beta, C: TiledMatrix,
          opts: Options = DEFAULT_OPTIONS) -> TiledMatrix:
    if C.kind is not MatrixKind.Symmetric:
        raise SlateError("syr2k: C must be symmetric")
    a = A.dense_canonical()
    b = B.dense_canonical()
    c = C.dense_canonical()
    grid = _grid_of(C, A, B)
    if grid is None:
        out = tile_ops.syr2k(alpha, a, b, beta, c, uplo=C.uplo)
    else:
        al, ar = _constrain_rank_k(a, grid)
        bl, br = _constrain_rank_k(b, grid)
        out = tile_ops._keep_triangle(
            alpha * (al @ br.T) + alpha * (bl @ ar.T) + beta * c, c, C.uplo)
        out = _constrain_out(out, grid)
    return _wrap_like(C, out)


def her2k(alpha, A: TiledMatrix, B: TiledMatrix, beta, C: TiledMatrix,
          opts: Options = DEFAULT_OPTIONS) -> TiledMatrix:
    if C.kind is not MatrixKind.Hermitian:
        raise SlateError("her2k: C must be Hermitian")
    a = A.dense_canonical()
    b = B.dense_canonical()
    c = C.dense_canonical()
    grid = _grid_of(C, A, B)
    if grid is None:
        out = tile_ops.her2k(alpha, a, b, beta, c, uplo=C.uplo)
    else:
        al, ar = _constrain_rank_k(a, grid)
        bl, br = _constrain_rank_k(b, grid)
        out = tile_ops._keep_triangle(
            alpha * (al @ jnp.conj(br).T)
            + jnp.conj(alpha) * (bl @ jnp.conj(ar).T) + beta * c, c, C.uplo)
        out = _constrain_out(out, grid)
    return _wrap_like(C, out)


def trmm(side: Side, alpha, A: TiledMatrix, B: TiledMatrix,
         opts: Options = DEFAULT_OPTIONS) -> TiledMatrix:
    """B ← α·op(A)·B or α·B·op(A), A triangular (slate::trmm, src/trmm.cc)."""
    if A.kind not in (MatrixKind.Triangular, MatrixKind.TriangularBand):
        raise SlateError("trmm: A must be triangular")
    a = A.full_dense_canonical()
    b = B.dense_canonical()
    grid = _grid_of(B, A)
    if grid is not None:
        if side is Side.Left:
            a, b = _constrain_product(a, b, grid)
        else:
            b, a = _constrain_product(b, a, grid)
    out = alpha * (a @ b) if side is Side.Left else alpha * (b @ a)
    if grid is not None:
        out = _constrain_out(out, grid)
    return _wrap_like(B, out)


def trsm(side: Side, alpha, A: TiledMatrix, B: TiledMatrix,
         opts: Options = DEFAULT_OPTIONS) -> TiledMatrix:
    """Solve op(A)·X = α·B (Left) or X·op(A) = α·B for X, A triangular.

    Reference: slate::trsm (src/trsm.cc, work::trsm src/work/work_trsm.cc:
    96-140 — block-column loop with panel bcasts and lookahead). Here a
    gemm-based block recursion (ops/blocked.trsm_rec — XLA's own
    triangular_solve is latency-bound and ~5× below the gemm rate on TPU;
    the inverted-diagonal-block scheme matches what cuBLAS does for the
    reference). The padded diagonal is set to 1 so padding solves to
    zero.

    A Triangular A is read as stored: both solvers read only its
    referenced triangle (and not a unit diagonal), so no masked copy is
    made, and a transposed view is solved through trans_a/conj_a on the
    stored array. A TriangularBand A is masked first (full_dense): the
    band mask defines that operand."""
    from ..core.types import MethodTrsm
    if A.kind not in (MatrixKind.Triangular, MatrixKind.TriangularBand):
        raise SlateError("trsm: A must be triangular")
    if A.uplo is Uplo.General:
        raise SlateError("trsm: A must have uplo Lower/Upper")
    trans, conj = False, False
    if A.kind is MatrixKind.TriangularBand:
        S, a = A, A.full_dense_canonical()
    else:
        trans, conj = A.op is not Op.NoTrans, A.op is Op.ConjTrans
        # the stored matrix: flipping the view back is metadata only
        S = A.H if conj else A.T if trans else A
        a = S.dense_canonical()
    # unit-pad the diagonal so the padded system is nonsingular
    a = unit_pad_diag(a, S.shape[0], S.shape[1])
    lower = S.uplo is Uplo.Lower
    b = B.dense_canonical()
    method = opts.method_trsm
    if method is MethodTrsm.B:
        # substitution-based solve (XLA's native triangular_solve) —
        # the stationary-B style schedule. Auto/A use the gemm-based
        # inverted-diagonal-block recursion, which is the fast path on
        # TPU (see ops/blocked.py module docstring for measurements);
        # B is kept for narrow rhs where substitution's lower flop
        # count can win over the inversion recursion.
        x = jax.lax.linalg.triangular_solve(
            a, alpha * b, left_side=(side is Side.Left), lower=lower,
            transpose_a=trans, conjugate_a=conj,
            unit_diagonal=(A.diag is Diag.Unit))
    else:
        x = blocked.trsm_rec(
            a, alpha * b,
            left=(side is Side.Left), lower=lower,
            unit=(A.diag is Diag.Unit), trans_a=trans, conj_a=conj,
            prec=opts.update_precision,
            base=min(A.nb, a.shape[0]))
    grid = _grid_of(B, A)
    if grid is not None:
        x = _constrain_out(x, grid)
    return _wrap_like(B, x)


# -- band BLAS-3 (reference src/gbmm.cc, src/hbmm.cc, src/tbsm.cc) ---------
# Round 1: band structure realized by masking dense storage (full_dense
# applies the (kl, ku) mask); the flop/byte savings of true packed-band
# storage are a later optimization.

def gbmm(alpha, A: TiledMatrix, B: TiledMatrix, beta, C: TiledMatrix,
         opts: Options = DEFAULT_OPTIONS) -> TiledMatrix:
    if A.kind is not MatrixKind.Band:
        raise SlateError("gbmm: A must be band")
    a = A.full_dense_canonical()
    out = alpha * (a @ B.dense_canonical()) + beta * C.dense_canonical()
    return _wrap_like(C, out)


def hbmm(side: Side, alpha, A: TiledMatrix, B: TiledMatrix, beta,
         C: TiledMatrix, opts: Options = DEFAULT_OPTIONS) -> TiledMatrix:
    if A.kind is not MatrixKind.HermitianBand:
        raise SlateError("hbmm: A must be Hermitian band")
    a = A.full_dense_canonical()
    b = B.dense_canonical()
    c = C.dense_canonical()
    out = alpha * (a @ b) + beta * c if side is Side.Left \
        else alpha * (b @ a) + beta * c
    return _wrap_like(C, out)


def tbsm(side: Side, alpha, A: TiledMatrix, B: TiledMatrix,
         opts: Options = DEFAULT_OPTIONS) -> TiledMatrix:
    """Triangular-band solve (slate::tbsm, src/tbsm.cc)."""
    if A.kind is not MatrixKind.TriangularBand:
        raise SlateError("tbsm: A must be triangular band")
    # full_dense already applied op + the band mask; present the result
    # as a plain NoTrans triangular matrix for the dense solve
    tri = TiledMatrix(A.full_dense_canonical(), A.shape[0], A.shape[1], A.nb,
                      kind=MatrixKind.Triangular, uplo=A.uplo, diag=A.diag,
                      grid=A.grid)
    return trsm(side, alpha, tri, B, opts)
