"""LU family: gesv, getrf (partial-pivot / no-pivot / tournament / RBT),
getrs, getri, mixed-precision iterative refinement.

Reference: src/gesv.cc, src/getrf.cc (driver DAG, SURVEY §3.2),
src/getrf_nopiv.cc, src/getrf_tntpiv.cc (CALU), src/gesv_rbt.cc +
src/gerbt.cc (random butterfly), src/gesv_mixed.cc, src/getrs.cc,
src/getri.cc, with internals internal_getrf.cc (multi-threaded panel +
MPI_Allreduce MAXLOC pivot search, internal_getrf.cc:64-119,
Tile_getrf.hh:209-270) and internal_swap.cc (batched device row swaps +
MPI_Sendrecv remote rows).

TPU-native design (SURVEY §7.5): the reference's latency-bound panel
factorization with cross-rank MAXLOC pivot search becomes
``lax.linalg.lu`` on the whole (m−k)×nb panel — XLA keeps the pivot
search on-device; the fine-grained row swaps (the hard part on
distributed memory, internal_swap.cc:503-560 batches them on GPUs)
become gathers of whole rows of the trailing block, which each step
carries as an array of its own (no column range of the matrix is
copied to gather from; stored L columns are reordered once at the end
by the composed suffix permutations), which GSPMD turns into the
collective-permute traffic the reference hand-codes with
MPI_Sendrecv. Pivots are carried as a
full row-permutation vector (the analog of the reference's Pivots
list): ``a_factored = A[perm] = L·U``.

Padding note: padded rows/cols carry an identity diagonal
(pad_diag_identity), so the padded system is block-diagonal
[[A,0],[0,I]]; pivoting can never select a padded row for a logical
column (padded rows are zero there), and solves with zero-padded rhs
stay exact.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..core.exceptions import SlateError
from ..core.tiled_matrix import TiledMatrix, from_dense, unit_pad_diag
from ..core.types import (Diag, MatrixKind, MethodLU, Norm, Options, Side,
                          Uplo, DEFAULT_OPTIONS, normalize_lookahead)
from ..core.precision import accurate_matmuls
from ..ops import blocked
from . import blas3
from . import elementwise as ew
from .norms import norm

Array = jax.Array


def _canonical(A: TiledMatrix) -> Array:
    return A.dense_canonical()


# single shared implementation in core (review: was quadruplicated)
_pad_identity_diag = unit_pad_diag


# ---------------------------------------------------------------------------
# partial-pivot LU
# ---------------------------------------------------------------------------

# width at which the recursion (nt > _ITER_MAX_NT only) hands a block
# to the flat iterative loop — the crossover measured on-chip for potrf
# (cholesky._POTRF_ITER_BASE) and shared by LU, whose loop has the same
# trailing-traffic structure
_GETRF_ITER_BASE = 2048
# HLO-size guard for the unrolled loop (single source of truth in
# ops/blocked.py, shared with cholesky._ITER_MAX_NT)
_ITER_MAX_NT = blocked.ITER_MAX_NT


def _iter_eligible(w: int, nb: int) -> bool:
    """Can the iterative loop own an (·, w) factorization? Static-shape
    predicate for the default dispatch (and the tests' policy probe —
    n=16384 @ nb=1024 must say yes without compiling anything). Unlike
    cholesky's, w == nb is allowed: a single pivoted panel is exactly
    what the loop's one step does."""
    return w % nb == 0 and w // nb <= _ITER_MAX_NT


def _getrf_rec(a: Array, nb: int, prec, threshold: float = 1.0):
    """Recursive blocked partial-pivot LU on an (M × W) column block,
    W ≤ M, recursing on width down to nb-wide panels.

    TPU redesign of the reference's panel + lookahead + trailing task DAG
    (src/getrf.cc:81-160): the multi-threaded panel with MPI MAXLOC pivot
    search (internal_getrf.cc:64-119) becomes blocked.panel_getrf — a
    width-recursion whose base is an ib-column fori_loop, heights
    bucketed to powers of two so only O(log nt) panel shapes compile
    (lax.linalg.lu is both latency-bound and fails VMEM on tall v5e
    panels, see ops/blocked.py). The fine-grained row swaps
    (internal_swap.cc:503-560 batches them on GPUs) become one
    streaming full-row gather per level (blocked.permute_rows_limited
    — measured faster on TPU than touching only the displaced rows).

    Returns (lu, perm, info) with gather semantics a[perm] = L·U;
    perm length M, info 1-based first zero pivot."""
    m, w = a.shape
    if w <= nb:
        if threshold < 1.0 and m > w:
            # Option::PivotThreshold analog: tournament panel
            # (compaction perm — permute_rows_limited's full gather
            # applies it correctly; the displacement bound is void)
            lu_p, p_p, info = _tournament_panel(a, w, nb, m)
            return lu_p, p_p, info
        hb = blocked.bucket_pow2(m, nb)
        ap = jnp.pad(a, ((0, hb - m), (0, 0))) if hb > m else a
        # replicate the thin panel operand on an active grid (the panel
        # broadcast; pre-0.6 partitioner soundness — see
        # blocked.replicate_on_grid)
        lu, perm, info = blocked.panel_getrf_jit(
            blocked.replicate_on_grid(ap))
        return lu[:m], perm[:m], info
    if w <= _GETRF_ITER_BASE and w % nb == 0 and w // nb <= _ITER_MAX_NT:
        # crossover measured on-chip for potrf and shared by LU (same
        # right-looking trailing-traffic structure; _getrf_blocked);
        # nt bound keeps the unrolled loop's HLO bounded for small nb
        return _getrf_iter(a, nb, prec, threshold)
    h = blocked._half(w, nb)
    lu1, p1, i1 = _getrf_rec(a[:, :h], nb, prec, threshold)
    right = blocked.permute_rows_limited(a[:, h:], p1, 2 * h)
    # U12 = L11⁻¹ · A12 (unit-lower block solve, gemm-based)
    u_top = blocked.trsm_rec(lu1[:h, :h], right[:h], left=True, lower=True,
                             unit=True, prec=prec, base=min(nb, h))
    schur = blocked.rebalance(
        right[h:] - blocked.mm(lu1[h:, :h], u_top, prec))
    lu2, p2, i2 = _getrf_rec(schur, nb, prec, threshold)
    low_left = blocked.permute_rows_limited(lu1[h:, :h], p2,
                                            2 * (w - h))
    lu = jnp.concatenate([
        jnp.concatenate([lu1[:h], u_top], axis=1),
        jnp.concatenate([low_left, lu2], axis=1)], axis=0)
    perm = blocked._compose_tail(p1, p2, h)
    info = jnp.where(i1 > 0, i1,
                     jnp.where(i2 > 0, i2 + h, 0)).astype(jnp.int32)
    return lu, perm, info


def _suffix_perms(pps, m: int, nb: int):
    """σⱼ = q_{j+1}∘…∘q_{nt−1} for every step j, as gather perms.

    ``pps[k]`` is step k's local permutation on rows [k·nb, m); lifting
    it to the full index space gives q_k (identity above k·nb). The
    deferred-left-swap fix-up needs, for each stored L column block j,
    the composition of every LATER step's permutation — computed by one
    backward pass: σ_{nt−1} = ι, σⱼ = q_{j+1}[σ_{j+1}] (gather-compose:
    (x[q1])[q2] = x[q1[q2]]). Returns sigmas[j] for j = 0..nt−2.

    The lift uses blocked.lift_tail_perm (iota/where/clamped-gather,
    NOT a concatenate): the pre-0.6 SPMD partitioner mis-lowers a
    concatenate whose second operand is a sharded int vector — the
    root cause of the round-6 "mesh getrf at nb=64 returns a corrupted
    perm" open item (see lift_tail_perm's docstring)."""
    nt = len(pps)
    sigmas = [None] * nt
    sig = jnp.arange(m, dtype=jnp.int32)
    for j in range(nt - 2, -1, -1):
        k0n = (j + 1) * nb
        q = blocked.lift_tail_perm(pps[j + 1], k0n, m, jnp.int32)
        sig = q[sig]
        sigmas[j] = sig
    return sigmas


def _apply_deferred_left_swaps(a: Array, pps, nb: int) -> Array:
    """The deferred-left-swap fix-up shared by _getrf_iter and
    getrf_tntpiv: reorder each stored L column block ONCE by its
    composed suffix permutation (≈ HALF a full-matrix permute in total,
    vs one full-width permute per level before). σⱼ is the identity
    above row (j+1)·nb, so only the strictly-below-diagonal L rows it
    actually moves are gathered. The ragged final column block (if any)
    has no later permutations and is skipped (σ = None)."""
    m = a.shape[0]
    with jax.named_scope("row_swap"):
        for j, sig in enumerate(_suffix_perms(pps, m, nb)):
            if sig is None:
                continue
            j0, j1 = j * nb, (j + 1) * nb
            a = blocked.dus_i32(a, a[:, j0:j1][sig[j1:]], j1, j0)
    return a


def _getrf_iter(a: Array, nb: int, prec, threshold: float = 1.0,
                lookahead: int = 1):
    """Iterative right-looking blocked partial-pivot LU, run as a
    LOOKAHEAD-1 PIPELINE (``lookahead`` ≥ 1, the default —
    Options.lookahead; 0 is the strictly sequential schedule).

    Lookahead: at step k the trailing update is split at the
    next-panel column block — the thin nb-wide u12/Schur slab is
    computed and written first, panel k+1 is factored IMMEDIATELY from
    that slab (the serial pivot-search/column chain that is getrf's
    latency floor), and only then do the remainder u12/Schur gemms run.
    The panel-(k+1) chain has no data edge to the remainder gemms, so
    the scheduler may interleave them (the reference's lookahead task,
    src/getrf.cc:121-160). Splitting the u12/Schur gemms by columns
    leaves every output element's contraction unchanged, so
    lookahead=1 is bit-identical to lookahead=0 (asserted across
    dtypes and the mesh in tests/test_lookahead.py; the formal
    guarantee is tolerance-level — column tiling of a gemm is a
    backend scheduling detail — and bit-level on the backends we test).

    Same redesign as cholesky._potrf_iter: per panel ONE bucketed
    pivoted panel factorization (blocked.panel_getrf), ONE batched-leaf
    unit-lower inverse of L11 (blocked.trtri_lower_batched), then the
    U12 block and Schur complement as single gemms — no recursive
    trsm re-inverting the same diagonal blocks at every level. The
    reference's DAG shape (panel → swaps → trsm → gemm per step,
    src/getrf.cc:81-160) is recovered step for step.

    PIVOT-FUSED trailing updates on a CARRIED trailing block: the
    permutation is folded into the trailing update's row reads, which
    take their rows straight out of the trailing block T (rows k0:, the
    input at step 0, then the previous step's Schur block — an array of
    its own, never a slice of the stored matrix):

      u12   = L11⁻¹ · T[p_p[:nb]]              (nb-row gather → gemm)
      schur = T[p_p[nb:]] − L21·u12            (the next step's T: the
                                                only HBM write, which
                                                right-looking pays
                                                anyway)

    — the TPU-native analog of the reference's device-batched row
    swaps folded into the lookahead task (internal_swap.cc:503-560,
    src/getrf.cc:121-160). XLA:TPU runs a row gather natively only
    when it takes whole rows of its operand: a column offset in the
    index (``a[rows, k1:]``) lowers to a loop of one-row slices, and
    a gather out of a column range of the stored matrix copies that
    range first. T holds only the trailing columns (and at step 0 and
    after a plain step, the next panel's), so its rows are gathered
    whole. The factor goes to a fresh output by thin stores (each
    panel's LU and u12): nothing reads the input after a write.
    Already-stored L columns are NOT re-permuted per step; the composed
    suffix permutations (_suffix_perms) reorder each column block ONCE
    at the end — O(n²) one-time traffic instead of O(n³/nb).

    ``threshold`` < 1 is the Option::PivotThreshold analog
    (src/getrf.cc + Tile_getrf.hh threshold pivoting): relaxed pivot
    quality buys a shorter critical path. Here that trades the
    per-column argmax/swap chain of the panel for the batched CALU
    tournament (winner rows selected by chunked LUs + a log₂ tree,
    then a no-pivot elimination) — tournament pivoting's growth bound
    is weaker than partial pivoting's but strong in practice, exactly
    the reference's CALU trade."""
    m, w = a.shape
    nt = w // nb
    dus = blocked.dus_i32  # raw python-int starts lower to s64 under
    # x64 and trip the pre-0.6 partitioner's mixed-width compare
    with jax.named_scope("getrf_prologue"):
        perm = jnp.arange(m, dtype=jnp.int32)
        info = jnp.zeros((), jnp.int32)
        out = jnp.zeros_like(a)
    pps = []

    def factor_panel(panel: Array, prows: int):
        """One pivoted nb-wide panel factorization → (lu rows-sliced,
        perm, info): the bucketed partial-pivot base, or under
        ``threshold`` < 1 the tournament arm (argmax/swap chain leaves
        the critical path; the tournament permutation compacts ALL
        rows, and only the nb-wide panel slice is gathered for the
        elimination). The panel operand is pinned replicated on an
        active grid first (blocked.replicate_on_grid — the panel
        broadcast; also the pre-0.6 partitioner soundness fix for the
        mesh nb=64 open item)."""
        panel = blocked.replicate_on_grid(panel)
        if threshold < 1.0:
            p_p = _tournament_perm(panel, nb, nb, prows, m)
            with jax.named_scope("row_swap"):
                pan_p = panel[p_p]
            lu_p, _, i_p = _tournament_panel(
                pan_p, nb, nb, prows, perm_done=True)
            return lu_p, p_p, i_p
        hb = blocked.bucket_pow2(prows, nb)
        if hb > prows:
            panel = jnp.pad(panel, ((0, hb - prows), (0, 0)))
        lu_p, p_p, i_p = blocked.panel_getrf_jit(panel)
        return lu_p[:prows], p_p[:prows], i_p

    # rows k0: of the trailing block: trail[:, off - nb:off] holds panel
    # k's columns unless the lookahead factored it, trail[:, off:]
    # columns k1: — the input at step 0, then each step's Schur block
    trail, off = a, nb
    ahead = None  # panel k's factorization, produced at step k−1
    for k in range(nt):
        k0, k1 = k * nb, (k + 1) * nb
        rows = m - k0
        if ahead is None:
            panel_scope = f"getrf_l{k}_panel"
            with jax.named_scope(panel_scope):
                lu_p, p_p, i_p = factor_panel(trail[:, off - nb:off], rows)
        else:
            panel_scope = f"getrf_l{k}_panel_lookahead"
            lu_p, p_p, i_p = ahead
            ahead = None
        with jax.named_scope(f"getrf_l{k}_store"):
            info = jnp.where((info == 0) & (i_p > 0), k0 + i_p,
                             info).astype(jnp.int32)
            with jax.named_scope("row_swap"):
                perm = perm.at[k0:].set(perm[k0:][p_p])
            out = dus(out, lu_p, k0, k0)
        pps.append(p_p)
        if k1 >= w:
            continue
        # the panel's L11 inverse belongs to its panel, as potrf's
        # tile inverse sits in potrf_l<k>_panel
        with jax.named_scope(panel_scope):
            l11 = jnp.tril(lu_p[:nb], -1) + jnp.eye(nb, dtype=a.dtype)
            inv11 = blocked.trtri_lower_batched(l11, unit=True)
        # whole rows of trail: the only row gather XLA:TPU runs natively
        with jax.named_scope(f"getrf_l{k}_load"):
            with jax.named_scope("row_swap"):
                top = trail[p_p[:nb]]  # pivot rows, one thin gather
            top = top[:, off:]
        if lookahead >= 1 and k1 + nb < w:
            # (a) next-panel columns: the thin nb-wide trailing slab
            with jax.named_scope(f"getrf_l{k}_trail_next"):
                with jax.named_scope("row_swap"):
                    below = trail[p_p[nb:]]
                u12n = blocked.mm(inv11, top[:, :nb], prec)
                schur_n = blocked.rebalance(
                    below[:, off:off + nb] - blocked.mm(lu_p[nb:], u12n,
                                                        prec))
            with jax.named_scope(f"getrf_l{k}_store"):
                out = dus(out, u12n, k0, k1)
            # (b) factor panel k+1 from the fresh slab — the serial
            # pivot/column chain, no data edge to the remainder gemms
            with jax.named_scope(f"getrf_l{k + 1}_panel_lookahead"):
                ahead = factor_panel(schur_n, m - k1)
            # (c) the remainder slab, independent of (b)
            with jax.named_scope(f"getrf_l{k}_trail_rest"):
                u12r = blocked.mm(inv11, top[:, nb:], prec)
                trail = blocked.rebalance(
                    below[:, off + nb:] - blocked.mm(lu_p[nb:], u12r, prec))
            with jax.named_scope(f"getrf_l{k}_store"):
                out = dus(out, u12r, k0, k1 + nb)
            off = 0
        else:
            with jax.named_scope(f"getrf_l{k}_trail"):
                with jax.named_scope("row_swap"):
                    below = trail[p_p[nb:]]
                u12 = blocked.mm(inv11, top, prec)
                out = dus(out, u12, k0, k1)
                trail = blocked.rebalance(
                    below[:, off:] - blocked.mm(lu_p[nb:], u12, prec))
            off = nb
    with jax.named_scope("getrf_epilogue"):
        out = _apply_deferred_left_swaps(out, pps, nb)
    return out, perm, info


def _getrf_blocked(a: Array, nb: int, nt: int, prec: str = "high",
                   threshold: float = 1.0, lookahead: int = 1):
    """Blocked partial-pivot LU on padded dense (possibly rectangular).

    Dispatch, from the shape alone: the pivot-fused iterative loop
    (_getrf_iter) owns EVERY width with nt ≤ _ITER_MAX_NT (the Schur
    write it pays is right-looking's inherent O(n³/nb) term, ~11 GB at
    n=16384 nb=1024 ≈ a one-digit-ms HBM budget per the round-5
    roofline numbers); the 2×2 width recursion (_getrf_rec) takes
    nt > _ITER_MAX_NT, the HLO-size guard of the unrolled loop. For
    wide matrices the remaining U columns get one block solve + no
    further pivoting."""
    m, n = a.shape
    k = min(m, n)
    if _iter_eligible(k, nb):
        lu, perm, info = _getrf_iter(a[:, :k], nb, prec, threshold,
                                     lookahead=lookahead)
    else:
        lu, perm, info = _getrf_rec(a[:, :k], nb, prec, threshold)
    if n > k:
        rest = blocked.permute_rows_limited(a[:, k:], perm, 2 * k)
        u_rest = blocked.trsm_rec(lu[:, :k], rest, left=True, lower=True,
                                  unit=True, prec=prec, base=nb)
        lu = jnp.concatenate([lu, u_rest], axis=1)
    return lu, perm, info


@accurate_matmuls
def getrf(A: TiledMatrix, opts: Options = DEFAULT_OPTIONS
          ) -> Tuple[TiledMatrix, Array, Array]:
    """Partial-pivot LU: A[perm] = L·U (slate::getrf, src/getrf.cc).

    Returns (LU packed in one matrix, perm, info)."""
    method = opts.method_lu
    if method is MethodLU.NoPiv:
        LU, info = getrf_nopiv(A, opts)
        nrows = LU.mt * LU.nb  # canonical rows, not grid-padded storage
        return LU, jnp.arange(nrows, dtype=jnp.int32), info
    if method is MethodLU.CALU:
        return getrf_tntpiv(A, opts)
    m, n = A.shape
    with jax.named_scope("getrf_prologue"):
        a = _canonical(A)
        a = _pad_identity_diag(a, m, n)
    with blocked.distribute_on(A.grid):
        lu, perm, info = _getrf_blocked(
            a, A.nb, min(A.mt, A.nt),
            prec=opts.update_precision,
            threshold=opts.pivot_threshold,
            lookahead=normalize_lookahead(opts.lookahead))
    with jax.named_scope("getrf_epilogue"):
        out = from_dense(lu, A.nb, grid=A.grid, logical_shape=(m, n))
    return out, perm, info


@accurate_matmuls
def getrf_nopiv(A: TiledMatrix, opts: Options = DEFAULT_OPTIONS
                ) -> Tuple[TiledMatrix, Array]:
    """LU without pivoting (slate::getrf_nopiv, src/getrf_nopiv.cc) —
    for diagonally-dominant or RBT-preconditioned systems."""
    m, n = A.shape
    a = _canonical(A)
    a = _pad_identity_diag(a, m, n)
    lu, info = _lu_nopiv_recursive(a)
    out = from_dense(lu, A.nb, grid=A.grid, logical_shape=(m, n))
    return out, info


def _lu_nopiv_recursive(a: Array, base: int = 64):
    """Recursive blocked no-pivot LU; base case is an unblocked
    fori_loop recurrence (maps the reference's Tile_getrf_nopiv.hh panel
    kernel to a compiler-friendly static recursion)."""
    n = min(a.shape)
    if n <= base:
        return _lu_nopiv_unblocked(a)
    half = (n // 2 + 7) & ~7 if n > 16 else n // 2  # 8-aligned split
    half = max(8, min(half, n - 1))
    a11, info1 = _lu_nopiv_recursive(a[:half, :half], base)
    l11 = a11
    a12 = jax.lax.linalg.triangular_solve(
        l11, a[:half, half:], left_side=True, lower=True, unit_diagonal=True)
    a21 = jax.lax.linalg.triangular_solve(
        l11, a[half:, :half], left_side=False, lower=False,
        unit_diagonal=False)
    a22 = a[half:, half:] - a21 @ a12
    a22, info2 = _lu_nopiv_recursive(a22, base)
    out = jnp.block([[a11, a12], [a21, a22]])
    info = jnp.where(info1 > 0, info1,
                     jnp.where(info2 > 0, info2 + half, 0)).astype(jnp.int32)
    return out, info


def _lu_nopiv_unblocked(a: Array):
    n = min(a.shape)
    rows = jnp.arange(a.shape[0])
    cols = jnp.arange(a.shape[1])

    def body(i, carry):
        mat, info = carry
        d = mat[i, i]
        bad = jnp.isnan(jnp.abs(d)) | (jnp.abs(d) == 0)
        info = jnp.where((info == 0) & bad, i + 1, info)
        dsafe = jnp.where(bad, jnp.ones((), mat.dtype), d)
        col = jnp.where(rows > i, mat[:, i] / dsafe, 0)
        mat = mat.at[:, i].set(jnp.where(rows > i, col, mat[:, i]))
        urow = jnp.where(cols > i, mat[i, :], 0)
        mat = mat - jnp.outer(col, urow)
        # the outer product zeroed nothing at/above row i (col is 0 there)
        return (mat, info)

    mat, info = jax.lax.fori_loop(0, n, body, (a, jnp.zeros((), jnp.int32)))
    return mat, info


def _tournament_perm(panel: Array, w: int, nb: int, prows: int,
                     mpad: int) -> Array:
    """CALU tournament over a (prows × w) panel: returns the length-
    ``prows`` permutation putting the w winner rows on top (reference
    src/getrf_tntpiv.cc:110-175 — local LU per nb-row chunk selects
    candidates, then a log₂ tree of pairwise stacked LUs picks the
    winners; all on device).

    Each round's chunk factorizations run as ONE batched panel LU
    (blocked.panel_getrf_batched — a single fori_loop whose body does
    the pivot search / swap / rank-1 update for every chunk at once),
    not vmap(lax.linalg.lu), whose custom-call backends execute the
    batch as a sequential per-block loop. A round's sequential depth
    is then w column steps regardless of the chunk count.

    Padding sentinels (zero-padded chunk rows, selectable only when a
    panel column is entirely zero) are replaced by distinct unused
    rows so the permutation stays valid and singularity surfaces only
    via info."""
    nchunks = -(-prows // nb)
    # bucket the chunk count to a power of two with zero chunks (their
    # candidate rows carry the mpad sentinel): round shapes become
    # SIZE-INDEPENDENT — (2^i, nb, w) and (2^i, 2w, w) only — so the
    # batched-round programs compile once per (nb, w) and amortize
    # across every panel step and problem size, and every pairing is
    # even
    nck = 1
    while nck < nchunks:
        nck *= 2
    pad_rows = nck * nb - prows
    stacked = jnp.pad(panel, ((0, pad_rows), (0, 0)))
    chunks = stacked.reshape(nck, nb, w)
    cand_idx = (jnp.arange(nck * nb, dtype=jnp.int32)
                .reshape(nck, nb))
    if nck != nchunks:
        # rows past the real panel are sentinels, not candidates
        cand_idx = jnp.where(cand_idx < prows, cand_idx, mpad)

    def round_perms(chs: Array) -> Array:
        _, perms_c, _ = blocked.panel_getrf_batched(chs)
        return perms_c

    rnd = 0
    while chunks.shape[0] > 1:
        with jax.named_scope(f"calu_round{rnd}"):
            perms_c = round_perms(chunks)
        rnd += 1
        top = jax.vmap(lambda c, p: c[p][:w])(chunks, perms_c)
        topi = jax.vmap(lambda ci, p: ci[p][:w])(cand_idx, perms_c)
        nc = top.shape[0]
        chunks = top.reshape(nc // 2, 2 * w, w)
        cand_idx = topi.reshape(nc // 2, 2 * w)
    with jax.named_scope(f"calu_round{rnd}_final"):
        pfin = round_perms(chunks[:1])[0]
    winners = cand_idx[0][pfin][:w]  # panel-relative row indices
    valid = winners < prows
    used = (jnp.zeros(prows + 1, bool)
            .at[jnp.where(valid, winners, prows)].set(True))[:prows]
    unused = jnp.nonzero(~used, size=prows,
                         fill_value=prows - 1)[0].astype(jnp.int32)
    slot = jnp.cumsum(~valid) - (~valid)  # per-slot sentinel ordinal
    winners = jnp.where(valid, winners, unused[slot])
    others_mask = jnp.ones(prows, bool).at[winners].set(False)
    rest = jnp.nonzero(others_mask, size=prows - w, fill_value=0)[0]
    return jnp.concatenate([winners, rest.astype(jnp.int32)])


def _tournament_panel(panel: Array, w: int, nb: int, prows: int,
                      perm_done: bool = False
                      ) -> Tuple[Array, Array, Array]:
    """Tournament-pivoted panel factorization: select winners
    (_tournament_perm), then eliminate without further pivoting —
    (lu packed, compaction perm, info). ``perm_done``: the caller
    already applied the permutation to ``panel`` (it then passes the
    permuted slice and ignores the returned iota)."""
    if perm_done:
        p_p = jnp.arange(prows, dtype=jnp.int32)
        pan_w = panel
    else:
        p_p = _tournament_perm(panel, w, nb, prows, prows)
        pan_w = panel[p_p]
    lu_top, info = _lu_nopiv_recursive(pan_w[:w])
    below = jax.lax.linalg.triangular_solve(
        lu_top, pan_w[w:], left_side=False, lower=False,
        unit_diagonal=False)
    return (jnp.concatenate([lu_top, below], axis=0), p_p,
            info.astype(jnp.int32))


@accurate_matmuls
def getrf_tntpiv(A: TiledMatrix, opts: Options = DEFAULT_OPTIONS
                 ) -> Tuple[TiledMatrix, Array, Array]:
    """Tournament (CALU) pivoting LU (slate::getrf_tntpiv,
    src/getrf_tntpiv.cc:110-175).

    The reference factors each rank's local tile stack, then plays a
    binary tournament over ranks exchanging candidate row blocks via
    tileSend/Recv. Here: one batched LU over nb-row chunks selects each
    chunk's candidate rows, then a log₂ tree of pairwise stacked LUs
    picks the panel's winners — all on device, no host round-trips.

    The tournament permutation is pivot-fused like the partial-pivot
    loop: the winner compaction is folded into the panel/trailing READS
    and the stored L columns are reordered once at the end
    (_suffix_perms), instead of a full-width permuted copy per step.
    The tournament rounds run batched (see _tournament_perm)."""
    m, n = A.shape
    nb = A.nb
    a = _canonical(A)
    a = _pad_identity_diag(a, m, n)
    mpad = a.shape[0]
    perm = jnp.arange(mpad, dtype=jnp.int32)
    info = jnp.zeros((), jnp.int32)
    nt = min(A.mt, A.nt)
    pps = []
    for k in range(nt):
        k0, k1 = k * nb, min((k + 1) * nb, a.shape[1])
        w = k1 - k0
        prows = mpad - k0
        with blocked.distribute_on(A.grid):
            panel = blocked.replicate_on_grid(a[k0:, k0:k1])
        p_perm = _tournament_perm(panel, w, nb, prows, mpad)
        perm = perm.at[k0:].set(perm[k0:][p_perm])
        pps.append(p_perm)
        pan_g = panel[p_perm]  # w-wide gather, no full-width copy
        # eliminate panel without further pivoting
        lu_pan, pinfo = _lu_nopiv_recursive(pan_g[:w])
        a = a.at[k0:k1, k0:k1].set(lu_pan)
        info = jnp.where((info == 0) & (pinfo > 0), k0 + pinfo, info)
        lkk = lu_pan
        below = jax.lax.linalg.triangular_solve(
            lkk, pan_g[w:], left_side=False, lower=False,
            unit_diagonal=False)
        a = a.at[k1:, k0:k1].set(below)
        if k1 < a.shape[1]:
            right = a[k0:, k1:]
            urow = jax.lax.linalg.triangular_solve(
                lkk, right[p_perm[:w]], left_side=True, lower=True,
                unit_diagonal=True)
            a = a.at[k0:k1, k1:].set(urow)
            a = a.at[k1:, k1:].set(right[p_perm[w:]] - below @ urow)
    a = _apply_deferred_left_swaps(a, pps, nb)
    out = from_dense(a, nb, grid=A.grid, logical_shape=(m, n))
    return out, perm, info


@accurate_matmuls
def getrs(LU: TiledMatrix, perm: Array, B: TiledMatrix,
          opts: Options = DEFAULT_OPTIONS, trans: bool = False
          ) -> TiledMatrix:
    """Solve A·X = B (or Aᵀ·X = B) from getrf factors (slate::getrs,
    src/getrs.cc: permuteRows → trsm(L) → trsm(U))."""
    prec = opts.update_precision
    with jax.named_scope("getrs_fwd"):
        lu = LU.dense_canonical()
        # storage beyond the logical shape is zero by invariant; restore
        # the unit diagonal there so the padded triangular solves stay
        # exact
        lu = _pad_identity_diag(lu, *LU.shape)
        b = B.dense_canonical()
        if b.shape[0] != lu.shape[0]:
            pad = lu.shape[0] - b.shape[0]
            if pad < 0:
                raise SlateError("getrs: rhs taller than factor")
            b = jnp.pad(b, ((0, pad), (0, 0)))
        if not trans:
            # same fusion contract as the factorization's trailing
            # reads: b[perm] is ONE gather feeding the first trsm's
            # operand (XLA fuses it into the solve's reads) — never a
            # per-level copy
            with jax.named_scope("row_swap"):
                pb = b[perm]
            y = blocked.trsm_rec(lu, pb, left=True, lower=True, unit=True,
                                 prec=prec, base=LU.nb)
        else:
            y = blocked.trsm_rec(lu, b, left=True, lower=False,
                                 unit=False, trans_a=True, prec=prec,
                                 base=LU.nb)
    with jax.named_scope("getrs_bwd"):
        if not trans:
            x = blocked.trsm_rec(lu, y, left=True, lower=False,
                                 unit=False, prec=prec, base=LU.nb)
        else:
            w = blocked.trsm_rec(lu, y, left=True, lower=True, unit=True,
                                 trans_a=True, prec=prec, base=LU.nb)
            with jax.named_scope("row_swap"):
                x = jnp.zeros_like(w).at[perm].set(w)
        x = x[: B.dense_canonical().shape[0]]
        return from_dense(x, B.nb, grid=B.grid, logical_shape=B.shape)


def gesv(A: TiledMatrix, B: TiledMatrix, opts: Options = DEFAULT_OPTIONS
         ) -> Tuple[TiledMatrix, Array]:
    """Solve A·X = B (slate::gesv = getrf + getrs; MethodLU dispatch at
    src/getrf.cc:324-353)."""
    if opts.method_lu is MethodLU.RBT:
        return gesv_rbt(A, B, opts)
    LU, perm, info = getrf(A, opts)
    X = getrs(LU, perm, B, opts)
    return X, info


def gesv_nopiv(A: TiledMatrix, B: TiledMatrix,
               opts: Options = DEFAULT_OPTIONS) -> Tuple[TiledMatrix, Array]:
    LU, info = getrf_nopiv(A, opts)
    X = getrs(LU, jnp.arange(LU.mt * LU.nb, dtype=jnp.int32), B, opts)
    return X, info


def getri(LU: TiledMatrix, perm: Array, opts: Options = DEFAULT_OPTIONS
          ) -> TiledMatrix:
    """Matrix inverse from getrf factors (slate::getri, src/getri.cc)."""
    n = LU.shape[0]
    eye = jnp.eye(LU.dense_canonical().shape[0], dtype=LU.dtype)
    I = from_dense(eye, LU.nb, grid=LU.grid,
                   logical_shape=(n, n))
    return getrs(LU, perm, I, opts)


def getri_oop(LU: TiledMatrix, perm: Array,
              opts: Options = DEFAULT_OPTIONS) -> TiledMatrix:
    """Out-of-place inverse from getrf factors (slate::getriOOP,
    src/getriOOP.cc). The reference distinguishes in-place (overwrite
    the factor) from out-of-place (result in B, factors preserved);
    functional semantics make every solve out-of-place here, so this is
    the same computation under the reference's other name — kept so
    callers porting from the reference find it."""
    return getri(LU, perm, opts)


# ---------------------------------------------------------------------------
# Random Butterfly Transform (RBT)
# ---------------------------------------------------------------------------

def _butterfly_vectors(n2: int, depth: int, seed: int, dtype) -> Array:
    """Random diagonal entries for the butterflies: exp(r/10)/sqrt(2) with
    r ~ U[-1,1] (the classic Parker/PRBT scaling used by the reference's
    internal_rbt_generate.cc)."""
    key = jax.random.key(seed)
    r = jax.random.uniform(key, (2 * depth, n2), jnp.float32,
                           minval=-1.0, maxval=1.0)
    return (jnp.exp(r / 10.0) / jnp.sqrt(2.0)).astype(dtype)


def _apply_butterfly(x: Array, d: Array, transpose: bool) -> Array:
    """y = Bᵀ·x (transpose=True) or B·x, where B = [[D1, D2],[D1, -D2]]
    acting on the leading axis (one recursion level)."""
    h = x.shape[0] // 2
    x1, x2 = x[:h], x[h:]
    d1 = d[:h, None]
    d2 = d[h: 2 * h, None]
    if transpose:
        return jnp.concatenate([d1 * (x1 + x2), d2 * (x1 - x2)])
    return jnp.concatenate([d1 * x1 + d2 * x2, d1 * x1 - d2 * x2])


def _rbt_rows(x: Array, diags: Array, depth: int, transpose: bool) -> Array:
    """Apply the depth-d recursive butterfly W (or Wᵀ) to the rows of x."""
    n = x.shape[0]
    levels = range(depth - 1, -1, -1) if not transpose else range(depth)
    for lev in levels:
        nblk = 2 ** lev
        blk = n // nblk
        xr = x.reshape(nblk, blk, -1)
        d = diags[lev][: nblk * blk].reshape(nblk, blk)
        xr = jax.vmap(lambda xb, db: _apply_butterfly(xb, db, transpose)
                      )(xr, d)
        x = xr.reshape(n, -1)
    return x


def gerbt(A: TiledMatrix, opts: Options = DEFAULT_OPTIONS, seed: int = 0):
    """Two-sided random butterfly transform Ã = Uᵀ·A·V (slate::gerbt,
    src/gerbt.cc). Returns (Ã, u_diags, v_diags)."""
    depth = opts.depth
    a = A.dense_canonical()
    a = _pad_identity_diag(a, *A.shape)
    n = a.shape[0]
    # butterfly needs n divisible by 2^depth; padded nb grids usually are
    while n % (2 ** depth):
        depth -= 1
    u = _butterfly_vectors(n, depth, seed * 2 + 1, a.dtype).reshape(-1, n)
    v = _butterfly_vectors(n, depth, seed * 2 + 2, a.dtype).reshape(-1, n)
    at = _rbt_rows(a, u, depth, transpose=True)           # Uᵀ·A
    at = _rbt_rows(at.T, v, depth, transpose=True).T      # (Vᵀ·(UᵀA)ᵀ)ᵀ = UᵀAV
    At = from_dense(at, A.nb, grid=A.grid, logical_shape=A.shape)
    return At, (u, depth), (v, depth)


def gesv_rbt(A: TiledMatrix, B: TiledMatrix,
             opts: Options = DEFAULT_OPTIONS) -> Tuple[TiledMatrix, Array]:
    """Solve via RBT + no-pivot LU + iterative refinement
    (slate::gesv_rbt, src/gesv_rbt.cc: butterfly transform, no-pivot
    factor, then refinement with fallback): A = U·Ã·Vᵀ ⇒
    X = V·Ã⁻¹·Uᵀ·B."""
    At, (u, du), (v, dv) = gerbt(A, opts)
    LU, info = getrf_nopiv(At, opts)
    npad = LU.dense_canonical().shape[0]
    iota = jnp.arange(npad, dtype=jnp.int32)

    def rbt_solve(rhs_mat: TiledMatrix) -> TiledMatrix:
        rb = rhs_mat.dense_canonical()
        if rb.shape[0] < npad:
            rb = jnp.pad(rb, ((0, npad - rb.shape[0]), (0, 0)))
        tb = _rbt_rows(rb, u, du, transpose=True)
        Tb = from_dense(tb, B.nb, logical_shape=(npad, rhs_mat.shape[1]))
        Y = getrs(LU, iota, Tb, opts)
        x = _rbt_rows(Y.dense_canonical()[:npad], v, dv, transpose=False)
        return from_dense(x[: B.shape[0]], B.nb, grid=B.grid,
                          logical_shape=B.shape)

    X = rbt_solve(B)
    # iterative refinement in working precision guards the RBT/no-pivot
    # stability loss (reference refines and falls back the same way)
    anorm = norm(A, Norm.Inf)
    eps = jnp.finfo(jnp.real(A.data).dtype).eps
    cte = anorm * eps * jnp.sqrt(jnp.asarray(float(A.shape[0]), anorm.dtype))
    converged = False
    for _ in range(opts.max_iterations + 1):
        R = blas3.gemm(-1.0, A, X, 1.0, B, opts)
        if bool(norm(R, Norm.Inf) <= norm(X, Norm.Inf) * cte):
            converged = True
            break
        X = ew.add(1.0, rbt_solve(R), 1.0, X, opts)
    if not converged and opts.use_fallback_solver:
        # partial-pivot rescue (MethodLU.PartialPiv), reference fallback
        LU2, perm2, info2 = getrf(A, opts.replace(method_lu=MethodLU.PartialPiv))
        return getrs(LU2, perm2, B, opts), info2
    return X, info


# ---------------------------------------------------------------------------
# mixed precision
# ---------------------------------------------------------------------------

def gesv_mixed(A: TiledMatrix, B: TiledMatrix,
               opts: Options = DEFAULT_OPTIONS, factor_dtype=jnp.float32
               ) -> Tuple[TiledMatrix, Array, int]:
    """Factor in low precision, refine in working precision
    (slate::gesv_mixed, src/gesv_mixed.cc:23-77). Returns (X, info,
    iters); iters < 0 ⇒ fell back to full-precision solve."""
    if A.dtype == factor_dtype:
        X, info = gesv(A, B, opts)
        return X, info, 0
    work_dtype = A.dtype
    A_lo = ew.copy(A, dtype=factor_dtype)
    LU, perm, info = getrf(A_lo, opts)

    anorm = norm(A, Norm.Inf)
    eps = jnp.finfo(work_dtype).eps
    n = A.shape[0]
    cte = anorm * eps * jnp.sqrt(jnp.asarray(float(n), anorm.dtype))

    X = ew.copy(getrs(LU, perm, ew.copy(B, dtype=factor_dtype), opts),
                dtype=work_dtype)
    converged = False
    iters = 0
    for it in range(opts.max_iterations):
        iters = it + 1
        R = blas3.gemm(-1.0, A, X, 1.0, B, opts)
        if bool(norm(R, Norm.Inf) <= norm(X, Norm.Inf) * cte):
            converged = True
            break
        D = ew.copy(getrs(LU, perm, ew.copy(R, dtype=factor_dtype), opts),
                    dtype=work_dtype)
        X = ew.add(1.0, D, 1.0, X, opts)
    if not converged and opts.use_fallback_solver:
        X, info = gesv(A, B, opts)
        return X, info, -iters
    return X, info, iters
