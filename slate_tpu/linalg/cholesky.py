"""Cholesky family: potrf, potrs, posv, trtri, trtrm, potri, posv_mixed.

Reference: src/potrf.cc (driver + task DAG, SURVEY §3.1), src/potrs.cc,
src/posv.cc, src/trtri.cc, src/trtrm.cc, src/potri.cc,
src/posv_mixed.cc, with internals internal_potrf/internal_trsm/
internal_herk and the per-tile lapack::potrf on device
(src/internal/internal_potrf.cc:58-75).

TPU-native design (SURVEY §7.4): the reference's OpenMP task DAG with
panel/lookahead/trailing tasks and hypercube tile broadcasts
(src/potrf.cc:84-195) becomes a statically-unrolled blocked right-looking
loop inside one jit:

    for k in 0..nt-1:
        L[k,k]   = chol(A[k,k])                  (internal::potrf analog)
        L[k+1:,k]= A[k+1:,k] · L[k,k]^-H         (internal::trsm, batched)
        A[k+1:,k+1:] -= L[k+1:,k] · L[k+1:,k]ᴴ   (internal::herk trailing)

Each step's trailing update is ONE large MXU matmul; under GSPMD the
panel is all-gathered along the mesh axes (the analog of
tileBcast/listBcastMT at src/potrf.cc:109-132) and the update runs on
all devices. Lookahead (Option::Lookahead, P3) has, since round 7, a
DIRECT analog: ``Options.lookahead`` ≥ 1 (the default) restructures the
iterative outer loop into a lookahead-1 pipeline — at step k the
trailing update is split at the next-panel slab, panel k+1's diagonal
tile is factored immediately after that slab, and the remainder slabs
follow with no data edge to the factor (see _potrf_iter). The round-4
finding stands that a single TPU core executes one kernel at a time;
what the pipeline buys is SCHEDULE freedom — the compiler may interleave
the serial panel chain with the remainder gemms (latency-hiding
scheduler on TPU, overlap of the panel's broadcast with remainder
compute on a mesh), and lookahead=0 restores the strictly sequential
round-6 schedule bit-identically.

Unlike LAPACK's in-place convention the factor is returned as a new
lower-TriangularMatrix (functional semantics); ``info`` follows the
reference's reduce_info convention (src/potrf.cc:208): 0 = success,
k > 0 = leading minor k not positive definite.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..core.exceptions import SlateError
from ..core.tiled_matrix import TiledMatrix, from_dense, unit_pad_diag
from ..core.types import (Diag, MatrixKind, Norm, Options, Side, Uplo,
                          DEFAULT_OPTIONS, normalize_lookahead)
from ..core.precision import accurate_matmuls
from ..ops import blocked, tile_ops
from . import blas3
from . import elementwise as ew
from .elementwise import copy as copy_matrix
from .norms import norm


def _chol_info_scan(a: jax.Array) -> jax.Array:
    """Exact LAPACK-style failing index for one non-SPD tile.

    lax.linalg.cholesky NaN-poisons the entire tile on failure, so the
    1-based index of the first non-positive leading minor (LAPACK potrf
    info) is recovered by an unblocked fori_loop recurrence. Only invoked
    (under lax.cond) when a tile actually failed — the fast path never
    pays for it."""
    nbb = a.shape[0]
    rdtype = jnp.real(a).dtype

    def body(i, carry):
        mat, info = carry
        d = jnp.real(mat[i, i])
        bad = jnp.isnan(d) | (d <= 0)
        info = jnp.where((info == 0) & bad, i + 1, info)
        dsafe = jnp.where(bad, jnp.ones((), rdtype), d)
        col = mat[:, i] / jnp.sqrt(dsafe).astype(mat.dtype)
        idx = jnp.arange(nbb)
        live = (idx[:, None] > i) & (idx[None, :] > i)
        mat = mat - jnp.where(live, jnp.outer(col, jnp.conj(col)), 0)
        return (mat, info)

    _, info = jax.lax.fori_loop(0, nbb, body, (a, jnp.zeros((), jnp.int32)))
    return info


@jax.jit
def _tile_chol(akk: jax.Array):
    """Factor one diagonal tile + its LAPACK info (jit-cached: one
    compilation per tile shape, many call sites). Uses the ib-blocked
    tile Cholesky (blocked.chol_tile_blocked) — ~5× less sequential
    latency than lax.linalg.cholesky's column recurrence."""
    lkk = blocked.chol_tile_blocked(akk)
    tile_failed = jnp.any(jnp.isnan(jnp.diagonal(lkk)))
    tile_info = jax.lax.cond(
        tile_failed, lambda: _chol_info_scan(akk),
        lambda: jnp.zeros((), jnp.int32))
    return lkk, tile_info


def _potrf_rec(a: jax.Array, nb: int, prec, lookahead: int = 1):
    """Recursive blocked Cholesky on padded dense (lower).

    TPU redesign of the reference's panel/trailing task DAG
    (src/potrf.cc:84-195): a 2×2 static-shape recursion whose flops live
    in large MXU matmuls — gemm-based trsm (blocked.trsm_rec: XLA's
    triangular_solve is 5× slower, see ops/blocked.py) and a
    triangle-aware rank-k update (blocked.herk_lower_rec — the analog of
    internal::herk's halved flops, src/internal/internal_herk.cc:351).
    Trailing gemms run at ``prec``; panel/tile math at the caller's
    HIGHEST context. Returns (factor with garbage above diag, info);
    unlike LAPACK there is no early exit (not jit-able) — NaNs propagate
    and info reports the first failing 1-based index (reduce_info
    semantics, src/potrf.cc:208)."""
    s = a.shape[0]
    if s <= nb:
        return _tile_chol(a)
    if s <= _POTRF_ITER_BASE and s % nb == 0 and s // nb <= _ITER_MAX_NT:
        # crossover measured on-chip (see _potrf_blocked docstring);
        # the nt bound keeps the Python-unrolled loop's HLO bounded
        # for small-nb configs (nt=128 unrolls cost minutes to compile;
        # on a 1-core host — the crossover was measured at nb=1024)
        return _potrf_iter(a, nb, prec, lookahead)
    h = blocked._half(s, nb)
    l11, i1 = _potrf_rec(a[:h, :h], nb, prec, lookahead)
    l21 = blocked.rebalance(
        blocked.trsm_rec(l11, a[h:, :h], left=False, lower=True,
                         conj_a=True, trans_a=True, prec=prec, base=nb))
    a22 = blocked.rebalance(
        blocked.herk_lower_rec(a[h:, h:], l21, prec=prec))
    l22, i2 = _potrf_rec(a22, nb, prec, lookahead)
    out = jnp.concatenate([
        jnp.concatenate([l11, a[:h, h:]], axis=1),
        jnp.concatenate([l21, l22], axis=1)], axis=0)
    info = jnp.where(i1 > 0, i1,
                     jnp.where(i2 > 0, i2 + h, 0)).astype(jnp.int32)
    return out, info


# On-chip crossover between the iterative right-looking loop and the
# 2×2 recursion (round-5 A/B, tools/potrf_ab.py): below this size the
# loop's single batched-leaf inverse per panel wins on latency; above
# it the round-5 loop's trailing-block re-traffic (herk_lower_rec's
# per-level concatenation copies) lost to the recursion's O(n² log nt)
# touch pattern (PERF_HISTORY.md round 5). Round 6: the crossover only
# gates the RECURSION's base case (the legacy dispatch,
# Options.factor_iter_large=False) — the default dispatch runs the
# iterative loop at ALL sizes with nt ≤ _ITER_MAX_NT, because its
# trailing update is now written in place slab-by-slab
# (blocked.herk_trailing_inplace: no concatenation copies, the lower
# trapezoid touched once per step) with the Pallas chol_tile kernel as
# the diagonal base at every step.
_POTRF_ITER_BASE = 2048
# HLO-size guard for the unrolled loop (the crossover was measured at
# nb=1024 → nt=2; small nb would otherwise unroll 128+ panel steps;
# single source of truth in ops/blocked.py, shared with lu.py)
_ITER_MAX_NT = blocked.ITER_MAX_NT


def _iter_eligible(s: int, nb: int) -> bool:
    """Static-shape predicate: can the in-place iterative loop own an
    s×s factorization? (Shared with the tests' dispatch-policy probe —
    n=16384 @ nb=1024 must answer yes without compiling anything.)"""
    return s > nb and s % nb == 0 and s // nb <= _ITER_MAX_NT


def _potrf_iter(a: jax.Array, nb: int, prec, lookahead: int = 1):
    """Iterative right-looking blocked Cholesky (round 4; round-6
    default at every nt ≤ _ITER_MAX_NT size — see _potrf_blocked),
    restructured in round 7 as a LOOKAHEAD-1 PIPELINE.

    Each panel step pays exactly ONE tile Cholesky (the Pallas
    chol_tile kernel where eligible — at EVERY step, not just below
    the old crossover) + ONE batched-leaf inverse
    (blocked.trtri_lower_batched), the panel update is a single gemm
    against the cached inverse (the inverted-diagonal-block trsm
    scheme), and the trailing update is written IN PLACE one column
    slab at a time (blocked.herk_trailing_inplace — triangular-herk
    flops, no per-level concatenation copies). The reference's task
    DAG shape (panel → trsm → herk per step, src/potrf.cc:84-195,
    with the right-looking in-place trailing discipline of
    src/potrf.cc:136-176) is recovered exactly.

    ``lookahead`` ≥ 1 (the default; the reference's Option::Lookahead,
    src/potrf.cc:84-103 — lookahead tasks factor panel k+1 while the
    rest of trailing update k runs): the trailing update is SPLIT at
    the next-panel slab — slab k+1 is written first, the diagonal tile
    of step k+1 is factored IMMEDIATELY from it, and only then are the
    remainder slabs written. The step-(k+1) tile factor (the serial
    ~n·sqrt/divide chain that is potrf's single-chip latency floor,
    PERF_HISTORY.md) therefore has NO data edge to the remainder slabs of step
    k — the scheduler is free to interleave the panel's VPU/scalar
    chain with the remainder's MXU gemms (asserted structurally in
    tests/test_lookahead.py, and on the scheduled HLO where the
    backend schedules it so). Every slab gemm is IDENTICAL to the
    lookahead=0 schedule (same shapes, same operands — only the op
    order between independent ops changes), so lookahead=1 is
    bit-identical to lookahead=0, which reproduces the round-6
    program exactly."""
    s = a.shape[0]
    nt = s // nb
    dus = blocked.dus_i32

    info = jnp.zeros((), jnp.int32)
    ahead = None  # panel k's tile factor, produced at step k−1
    for k in range(nt):
        k0, k1 = k * nb, (k + 1) * nb
        if ahead is None:
            with jax.named_scope(f"potrf_l{k}_tile"):
                lkk, tinfo = _tile_chol(a[k0:k1, k0:k1])
        else:
            lkk, tinfo = ahead
            ahead = None
        info = jnp.where((info == 0) & (tinfo > 0), k0 + tinfo,
                         info).astype(jnp.int32)
        a = dus(a, lkk, k0, k0)
        if k1 >= s:
            continue
        with jax.named_scope(f"potrf_l{k}_panel"):
            inv = blocked.trtri_lower_batched(lkk)
            pan = blocked.mm(a[k1:, k0:k1], jnp.conj(inv).T, prec)
            pan = blocked.rebalance(pan)
        a = dus(a, pan, k1, k0)
        if lookahead >= 1 and k1 + nb <= s:
            # (a) the next-panel slab alone …
            with jax.named_scope(f"potrf_l{k}_trail_next"):
                a = blocked.herk_trailing_inplace(a, pan, k1, nb,
                                                  prec=prec,
                                                  j_stop=k1 + nb)
            # … (b) factor panel k+1 NOW (reads only slab k+1's
            # diagonal block; the remainder slabs below never touch
            # rows/cols < k1+nb, so the value is final) …
            with jax.named_scope(f"potrf_l{k + 1}_tile_lookahead"):
                ahead = _tile_chol(a[k1:k1 + nb, k1:k1 + nb])
            # … (c) the remainder slabs, independent of (b)
            with jax.named_scope(f"potrf_l{k}_trail_rest"):
                a = blocked.herk_trailing_inplace(a, pan, k1, nb,
                                                  prec=prec,
                                                  j_start=k1 + nb)
        else:
            with jax.named_scope(f"potrf_l{k}_trail"):
                a = blocked.herk_trailing_inplace(a, pan, k1, nb,
                                                  prec=prec)
    return a, info


def _potrf_blocked(a: jax.Array, nb: int, nt: int, prec: str = "high",
                   iter_large: bool = True, lookahead: int = 1):
    """Blocked Cholesky on padded dense (lower) → (tril factor, info).

    Dispatch (round 6): the in-place iterative loop owns EVERY size
    with nt ≤ _ITER_MAX_NT. The round-5 crossover (_POTRF_ITER_BASE,
    on-chip A/B tools/potrf_ab.py) was set by the loop's trailing
    re-traffic — herk_lower_rec's per-level concatenation copies, 131
    ms of a 200 ms n=16384 call — which the slab-wise in-place update
    (blocked.herk_trailing_inplace) removes; what remains is
    right-looking's inherent once-per-step trailing write, an
    O(n³/(3nb)) HBM term (~11 GB ≈ one-digit ms at n=16384 nb=1024 on
    v5e). The 2×2 recursion remains for nt > _ITER_MAX_NT (HLO-size
    guard) and as the legacy dispatch (Options.factor_iter_large=False
    — the round-5 policy, iterative only below the crossover), which
    is also the reassociation-tolerance reference arm for tests.

    ``lookahead`` (round 7, Options.lookahead): ≥ 1 runs the iterative
    loop as the lookahead pipeline (panel k+1 factored between the
    next-panel slab and the remainder slabs of trailing update k —
    bit-identical, schedule-decoupled); 0 restores the strictly
    sequential round-6 schedule (the tolerance/HLO reference arm)."""
    s = a.shape[0]
    if iter_large and _iter_eligible(s, nb):
        out, info = _potrf_iter(a, nb, prec=prec, lookahead=lookahead)
    else:
        out, info = _potrf_rec(a, nb, prec=prec, lookahead=lookahead)
    return jnp.tril(out), info


@accurate_matmuls
def potrf(A: TiledMatrix, opts: Options = DEFAULT_OPTIONS
          ) -> Tuple[TiledMatrix, jax.Array]:
    """Cholesky factorization A = L·Lᴴ (Lower) or UᴴU (Upper).

    Returns (L_or_U as TriangularMatrix, info)."""
    if A.kind not in (MatrixKind.Hermitian, MatrixKind.Symmetric):
        raise SlateError("potrf: A must be Hermitian/Symmetric (use "
                         "slate_tpu.hermitian/symmetric)")
    if A.shape[0] != A.shape[1]:
        raise SlateError("potrf: A must be square")
    n = A.shape[0]
    nb = A.nb
    # the factorization reads ONLY the lower triangle (upper content
    # passes through untouched and is tril-masked at the end), so skip
    # full_dense_canonical's Hermitian mirror — 2-3 full HBM passes at
    # bench sizes (round-5 driver-overhead profiling). Upper storage
    # reaches the lower triangle by conjugate-transposing the raw
    # storage instead of mirroring.
    if A.uplo is Uplo.Upper:
        a = jnp.conj(A.dense_canonical()).T
    else:
        a = A.dense_canonical()
    # zpotrf contract (full_dense used to realify; the raw storage
    # path must do it explicitly)
    a = tile_ops.realify_diag(a)
    a = unit_pad_diag(a, n, n)
    nt = A.mt
    with blocked.distribute_on(A.grid):
        lower, info = _potrf_blocked(a, nb, nt, prec=opts.update_precision,
                                     iter_large=opts.factor_iter_large,
                                     lookahead=normalize_lookahead(
                                         opts.lookahead))
    if A.uplo is Uplo.Upper:
        out = from_dense(jnp.conj(lower).T, nb, grid=A.grid,
                         kind=MatrixKind.Triangular, uplo=Uplo.Upper,
                         logical_shape=(n, n))
    else:
        out = from_dense(lower, nb, grid=A.grid, kind=MatrixKind.Triangular,
                         uplo=Uplo.Lower, logical_shape=(n, n))
    return out, info


def potrs(L: TiledMatrix, B: TiledMatrix,
          opts: Options = DEFAULT_OPTIONS) -> TiledMatrix:
    """Solve A·X = B given the Cholesky factor (slate::potrs,
    src/potrs.cc: two work::trsm sweeps)."""
    if L.kind is not MatrixKind.Triangular:
        raise SlateError("potrs: L must be the factor from potrf")
    if L.uplo is Uplo.Lower:
        y = blas3.trsm(Side.Left, 1.0, L, B, opts)
        x = blas3.trsm(Side.Left, 1.0, L.H, y, opts)
    else:
        y = blas3.trsm(Side.Left, 1.0, L.H, B, opts)
        x = blas3.trsm(Side.Left, 1.0, L, y, opts)
    return x


def posv(A: TiledMatrix, B: TiledMatrix,
         opts: Options = DEFAULT_OPTIONS) -> Tuple[TiledMatrix, jax.Array]:
    """Solve A·X = B for Hermitian positive definite A (slate::posv)."""
    L, info = potrf(A, opts)
    X = potrs(L, B, opts)
    return X, info


@accurate_matmuls
def trtri(A: TiledMatrix, opts: Options = DEFAULT_OPTIONS) -> TiledMatrix:
    """Triangular inverse (slate::trtri, src/trtri.cc). One XLA
    triangular_solve against I — blocked internally."""
    if A.kind not in (MatrixKind.Triangular, MatrixKind.TriangularBand):
        raise SlateError("trtri: A must be triangular")
    a = A.full_dense_canonical()
    n = A.shape[0]
    a = unit_pad_diag(a, n, n)
    eye = jnp.eye(a.shape[0], dtype=a.dtype)
    inv = jax.lax.linalg.triangular_solve(
        a, eye, left_side=True, lower=(A.uplo is Uplo.Lower),
        unit_diagonal=(A.diag is Diag.Unit))
    return from_dense(inv, A.nb, grid=A.grid, kind=MatrixKind.Triangular,
                      uplo=A.uplo, diag=A.diag, logical_shape=A.shape)


@accurate_matmuls
def trtrm(L: TiledMatrix, opts: Options = DEFAULT_OPTIONS) -> TiledMatrix:
    """Lᴴ·L (or U·Uᴴ) triangular-triangular multiply (slate::trtrm,
    src/trtrm.cc — the second half of potri)."""
    a = L.full_dense_canonical()
    if L.uplo is Uplo.Lower:
        out = jnp.conj(a).T @ a
    else:
        out = a @ jnp.conj(a).T
    return from_dense(out, L.nb, grid=L.grid, kind=MatrixKind.Hermitian,
                      uplo=L.uplo, logical_shape=L.shape)


def potri(A_factor: TiledMatrix, opts: Options = DEFAULT_OPTIONS
          ) -> TiledMatrix:
    """A⁻¹ from the Cholesky factor: inv = L⁻ᴴ·L⁻¹ (slate::potri,
    src/potri.cc = trtri + trtrm)."""
    linv = trtri(A_factor, opts)
    return trtrm(linv, opts)


def posv_mixed(A: TiledMatrix, B: TiledMatrix,
               opts: Options = DEFAULT_OPTIONS,
               factor_dtype=jnp.float32
               ) -> Tuple[TiledMatrix, jax.Array, int]:
    """Mixed-precision posv with iterative refinement.

    Reference: src/posv_mixed.cc:23-77 — factor in single, iterate the
    residual in double, fall back to full precision if IR stagnates. On
    TPU this is the *natural* mode: factor in f32 (or bf16), refine in the
    working precision. Returns (X, info, iters); iters < 0 means the
    fallback full-precision solve was used (reference convention)."""
    work_dtype = A.dtype
    if A.dtype == factor_dtype:
        X, info = posv(A, B, opts)
        return X, info, 0

    A_lo = copy_matrix(A, dtype=factor_dtype)
    L_lo, info = potrf(A_lo, opts)

    anorm = norm(A, Norm.Inf)
    eps = jnp.finfo(work_dtype).eps
    n = A.shape[0]
    cte = anorm * eps * jnp.sqrt(jnp.asarray(float(n), anorm.dtype))

    X = copy_matrix(potrs(L_lo, copy_matrix(B, dtype=factor_dtype), opts),
                    dtype=work_dtype)
    converged = False
    iters = 0
    for it in range(opts.max_iterations):
        iters = it + 1
        # R = B - A·X in working precision
        R = blas3.hemm(Side.Left, -1.0, A, X, 1.0, B, opts) \
            if A.kind is MatrixKind.Hermitian else \
            blas3.symm(Side.Left, -1.0, A, X, 1.0, B, opts)
        rnorm = norm(R, Norm.Inf)
        xnorm = norm(X, Norm.Inf)
        if bool(rnorm <= xnorm * cte):
            converged = True
            break
        D = copy_matrix(potrs(L_lo, copy_matrix(R, dtype=factor_dtype), opts),
                        dtype=work_dtype)
        X = ew.add(1.0, D, 1.0, X, opts)
    if not converged and opts.use_fallback_solver:
        X, info = posv(A, B, opts)
        return X, info, -iters
    return X, info, iters
