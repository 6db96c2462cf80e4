"""Gemm-based blocked building blocks for the factorization drivers.

Why this module exists (measured on one TPU v5e chip, n=8192 f32):

- XLA's ``triangular_solve`` runs at ~12 TFLOP/s for big solves and takes
  ~10 ms *per call* for thin (panel-width) solves — it is a latency-bound
  custom expansion, ~5× slower than the 60 TFLOP/s "high"-precision gemm
  rate and ~13× below the 160 TFLOP/s default gemm rate.
- XLA's QR / LU panel kernels are column-recurrence loops: a 16384×512
  QR panel costs ~25 ms, and ``lax.linalg.lu`` on the same panel fails to
  compile on v5e (VMEM overflow in LuDecompositionBlock).

So every hot path here is restructured into *static-shape recursions whose
flops live in large MXU matmuls* — the TPU-native analog of the
reference's strategy of pushing panel work onto the GPU via contiguous
gathers (src/internal/internal_geqrf.cc:235-254) and batched BLAS for
trailing updates (src/internal/internal_herk.cc:351):

- ``trtri_rec`` — triangular inverse by 2×2 block recursion; base case is
  a fori_loop substitution on a ≤64 block.
- ``trsm_rec`` — triangular solve by block-column recursion; base case
  multiplies by the inverse of an nb-sized diagonal block (the same
  inverted-diagonal-block scheme cuBLAS/MAGMA use for GPU trsm).
- ``herk_lower_rec`` — rank-k update computing only the lower triangle
  (recursive split; off-diagonal blocks are plain gemms), halving the
  trailing-update flops of potrf exactly like the reference's herk.
- ``panel_getrf`` / ``panel_geqrf`` — blocked panel factorizations with a
  narrow (ib-column) fori_loop base and gemm aggregation above it.
  Panel heights are bucketed to powers of two (zero-padding below is
  harmless for both: QR of [B;0] embeds QR of B, and LU pivoting never
  selects an exactly-zero padded row unless the column is entirely zero,
  in which case the diagonal fallback keeps the permutation valid) so a
  full factorization compiles ≤ log2(nt) distinct panel shapes instead
  of nt.

Precision policy: panel/base math runs under the caller's (HIGHEST)
context; the caller passes ``prec`` ("high" = bf16x3, ≈ f32-accurate at
2× the HIGHEST rate) for the large trailing-update matmuls. See
core/precision.py.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array

# Distribution context for the factorization recursions: when a driver
# runs on a multi-device grid it installs the grid here, and rebalance()
# pins intermediates (trailing submatrices, panels) to the full 2D mesh.
# This is the TPU-native replacement for the reference's static 2D
# block-cyclic layout (include/slate/func.hh:179): instead of fixing a
# cyclic tile→rank map up front (an MPI-world necessity — redistribution
# is expensive there), every recursion level re-shards its shrinking
# trailing submatrix evenly over ALL devices, so no device goes idle as
# the factorization proceeds. XLA turns each constraint into
# collective-permute/all-gather traffic over ICI.
_GRID_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "slate_tpu_factor_grid", default=None)


@contextlib.contextmanager
def distribute_on(grid):
    """Install ``grid`` as the factorization distribution context (used
    by drivers; None or a single-device grid disables rebalancing)."""
    use = grid if (grid is not None and grid.size > 1) else None
    tok = _GRID_CTX.set(use)
    try:
        yield
    finally:
        _GRID_CTX.reset(tok)


def _single_device() -> bool:
    """No multi-device grid is installed: only then may a driver call a
    Pallas kernel. GSPMD partitions a sharded program and cannot
    partition a Mosaic call ("Mosaic kernels cannot be automatically
    partitioned"), so the sharded drivers take the XLA bases."""
    return _GRID_CTX.get() is None


def current_grid():
    """The grid installed by distribute_on (None outside a context) —
    the public accessor; callers must not read _GRID_CTX directly."""
    return _GRID_CTX.get()


def rebalance(x: Array) -> Array:
    """Constrain a 2-D intermediate to the active grid's (p, q) spec —
    the per-level load-balancing resharding (see _GRID_CTX). No-op
    without an active multi-device grid."""
    g = _GRID_CTX.get()
    if g is None or x.ndim != 2:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..core.grid import COL_AXIS, ROW_AXIS
    return lax.with_sharding_constraint(
        x, NamedSharding(g.mesh, P(ROW_AXIS, COL_AXIS)))


def replicate_on_grid(x: Array) -> Array:
    """Pin ``x`` FULLY REPLICATED over the active grid (no-op without
    one) — the GSPMD analog of the reference's panel broadcast
    (tileBcast/listBcastMT, src/potrf.cc:109-132): the thin pivoted
    panel is factored identically on every device while the O(n³)
    trailing updates stay sharded.

    This is also the round-7 soundness fix for the second half of the
    "mesh getrf at nb=64" open item: with a ROW-SHARDED panel operand,
    the pre-0.6 SPMD partitioner mis-lowers the permutation gathers
    inside panel_getrf's width recursion (wrong VALUES, valid perm —
    distinct from the lift_tail_perm concatenate bug, bisected the
    same way). A replicated operand partitions trivially, so every
    lowering is sound; the cost is one all-gather of an (m, nb) strip
    per level — traffic the reference pays for the same panel by
    design."""
    g = _GRID_CTX.get()
    if g is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    return lax.with_sharding_constraint(
        x, NamedSharding(g.mesh, P(*([None] * x.ndim))))

# base sizes, chosen for TPU: ib such that the fori-loop bases touch
# O(m·nb·ib) bytes total; bases for recursion chosen so leaf ops stay
# MXU-sized without blowing up HLO op count.
TRTRI_BASE = 64
TRSM_BASE = 512
HERK_BASE = 1024
PANEL_IB = 32
# HLO-size guard for the unrolled iterative outer loops of the
# factorization drivers — single source of truth for linalg/lu.py and
# linalg/cholesky.py (their _ITER_MAX_NT aliases)
ITER_MAX_NT = 64


def mm(a: Array, b: Array, prec: Optional[str] = None) -> Array:
    """Matmul with an explicit precision override (None = context)."""
    return jnp.matmul(a, b, precision=prec)


def _round_to(x: int, q: int) -> int:
    return -(-x // q) * q


def _half(n: int, q: int) -> int:
    """Split point for 2×2 recursion: ~n/2 rounded up to a multiple of q
    (so recursion leaves stay q-aligned and shape-uniform), clamped to
    keep both halves non-empty."""
    h = _round_to(n // 2, q)
    if h >= n:
        h = _round_to(n // 2, 8)
    if h >= n or h == 0:
        h = max(1, n // 2)
    return h


def bucket_pow2(h: int, q: int) -> int:
    """Smallest q·2^i ≥ h — the panel-height bucketing quantum."""
    b = q
    while b < h:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# triangular inverse
# ---------------------------------------------------------------------------

def _trtri_lower_base(l: Array, unit: bool) -> Array:
    """Unblocked inv of a lower-triangular block via row substitution."""
    n = l.shape[0]
    cols = jnp.arange(n)

    def body(i, x):
        lrow = jnp.where(cols < i, l[i, :], 0)
        contrib = lrow @ x
        e_i = (cols == i).astype(l.dtype)
        if unit:
            row = e_i - contrib
        else:
            row = (e_i - contrib) / l[i, i]
        return x.at[i, :].set(row)

    return lax.fori_loop(0, n, body, jnp.zeros_like(l))


def trtri_lower_rec(l: Array, unit: bool = False,
                    base: int = TRTRI_BASE) -> Array:
    """inv(L) for lower-triangular L.

    2×2 block recursion: inv([[A,0],[B,C]]) = [[iA,0],[−iC·B·iA, iC]].
    All flops above the base live in gemms. Only the lower triangle of
    the input is read."""
    n = l.shape[0]
    if n <= base:
        return _trtri_lower_base(l, unit)
    h = _half(n, 8)
    ia = trtri_lower_rec(l[:h, :h], unit, base)
    ic = trtri_lower_rec(l[h:, h:], unit, base)
    b = l[h:, :h]
    off = -mm(ic, mm(b, ia))
    top = jnp.concatenate([ia, jnp.zeros((h, n - h), l.dtype)], axis=1)
    bot = jnp.concatenate([off, ic], axis=1)
    return jnp.concatenate([top, bot], axis=0)


def trtri_rec(a: Array, lower: bool = True, unit: bool = False,
              base: int = TRTRI_BASE) -> Array:
    """Triangular inverse (lower or upper) — inv(U) = inv(Uᵀ)ᵀ."""
    if lower:
        return trtri_lower_rec(a, unit, base)
    return trtri_lower_rec(a.T, unit, base).T


@functools.partial(jax.jit, static_argnames=("unit",))
def _trtri_block(l: Array, unit: bool) -> Array:
    """jit-cached lower-triangular block inverse: trsm bases hit the same
    (TRSM_BASE, TRSM_BASE) shape hundreds of times per factorization —
    one compilation, many call sites."""
    return trtri_lower_rec(l, unit)


def trtri_lower_batched(l: Array, unit: bool = False,
                        leaf: int = 64) -> Array:
    """inv(L) with ALL diagonal leaf blocks inverted in one vmapped
    straight-line kernel, then combined by the 2×2 gemm recursion.

    The plain recursion executes its fori_loop leaf inversions
    sequentially — at (1024, leaf 64) that is 16 × ~0.3 ms of serial
    latency per inverse; batching the leaves collapses it to one fused
    kernel + log2(n/leaf) combine levels of MXU gemms. This is the
    panel-inverse kernel of the iterative potrf/getrf paths (the
    inverted-diagonal-block scheme cuBLAS/MAGMA use for GPU trsm, done
    once per panel instead of once per trsm call)."""
    n = l.shape[0]
    nleaf = n // leaf if n % leaf == 0 else 0
    if n <= leaf or nleaf == 0 or (nleaf & (nleaf - 1)) != 0:
        return trtri_lower_rec(l, unit)  # needs a power-of-two leaf grid
    idx = jnp.arange(nleaf) * leaf
    diags = jax.vmap(
        lambda i: lax.dynamic_slice(l, (i, i), (leaf, leaf)))(idx)
    inv_leaves = jax.vmap(lambda d: _trtri_unrolled_u(d, leaf, unit))(diags)

    # bottom-up assembly: at each level, pair up the current inverses —
    # inv([[A,0],[B,C]]) = [[iA, 0], [−iC·B·iA, iC]]
    inv = inv_leaves  # (nblk, s, s)
    s = leaf
    while s < n:
        nblk = inv.shape[0]
        ia = inv[0::2]  # (nblk/2, s, s)
        ic = inv[1::2]
        starts = jnp.arange(nblk // 2) * (2 * s)
        b = jax.vmap(
            lambda i: lax.dynamic_slice(l, (i + s, i), (s, s)))(starts)
        off = -jnp.einsum("bij,bjk,bkl->bil", ic, b, ia,
                          precision=lax.Precision.HIGHEST)
        top = jnp.concatenate(
            [ia, jnp.zeros((nblk // 2, s, s), l.dtype)], axis=2)
        bot = jnp.concatenate([off, ic], axis=2)
        inv = jnp.concatenate([top, bot], axis=1)
        s *= 2
    return inv[0]


def _trtri_unrolled_u(l: Array, ib: int, unit: bool) -> Array:
    """Straight-line inverse of a lower-triangular block, unit-aware."""
    cols = jnp.arange(ib)
    x = jnp.zeros_like(l)
    for i in range(ib):
        lrow = jnp.where(cols < i, l[i, :], 0)
        e_i = (cols == i).astype(l.dtype)
        row = e_i - lrow @ x
        if not unit:
            row = row / l[i, i]
        x = x.at[i, :].set(row)
    return x


# ---------------------------------------------------------------------------
# triangular solve
# ---------------------------------------------------------------------------

def _trsm_left_lower(m: Array, b: Array, unit: bool, prec, base) -> Array:
    """X with M·X = B, M lower triangular (only lower triangle read)."""
    n = m.shape[0]
    if n <= base:
        inv = _trtri_block(m, unit) if n == base \
            else trtri_lower_rec(m, unit)
        return mm(inv, b, prec)
    h = _half(n, base)
    x1 = _trsm_left_lower(m[:h, :h], b[:h], unit, prec, base)
    rhs2 = b[h:] - mm(m[h:, :h], x1, prec)
    x2 = _trsm_left_lower(m[h:, h:], rhs2, unit, prec, base)
    return jnp.concatenate([x1, x2], axis=0)


def _trsm_left_upper(m: Array, b: Array, unit: bool, prec, base) -> Array:
    n = m.shape[0]
    if n <= base:
        # inv(U) = inv(Uᵀ)ᵀ so the jit-cached lower kernel serves both
        inv = _trtri_block(m.T, unit).T if n == base \
            else trtri_rec(m, lower=False, unit=unit)
        return mm(inv, b, prec)
    h = _half(n, base)
    x2 = _trsm_left_upper(m[h:, h:], b[h:], unit, prec, base)
    rhs1 = b[:h] - mm(m[:h, h:], x2, prec)
    x1 = _trsm_left_upper(m[:h, :h], rhs1, unit, prec, base)
    return jnp.concatenate([x1, x2], axis=0)


def trsm_rec(a: Array, b: Array, *, left: bool = True, lower: bool = True,
             unit: bool = False, trans_a: bool = False,
             conj_a: bool = False, prec: Optional[str] = None,
             base: int = TRSM_BASE) -> Array:
    """Solve op(A)·X = B (left) or X·op(A) = B (right), A triangular.

    Gemm-based replacement for lax.linalg.triangular_solve (see module
    docstring for why). op(A) is materialized first (XLA fuses the
    transpose/conj into the consumers)."""
    m = a
    if conj_a:
        m = jnp.conj(m)
    eff_lower = lower
    if trans_a:
        m = m.T
        eff_lower = not lower
    if left:
        if eff_lower:
            return _trsm_left_lower(m, b, unit, prec, base)
        return _trsm_left_upper(m, b, unit, prec, base)
    # right: X·M = B  ⇔  Mᵀ·Xᵀ = Bᵀ
    mt = m.T
    if eff_lower:
        xt = _trsm_left_upper(mt, b.T, unit, prec, base)
    else:
        xt = _trsm_left_lower(mt, b.T, unit, prec, base)
    return xt.T


# ---------------------------------------------------------------------------
# triangle-aware rank-k update
# ---------------------------------------------------------------------------

def herk_lower_rec(c: Array, a: Array, b: Optional[Array] = None,
                   prec: Optional[str] = None,
                   base: int = HERK_BASE) -> Array:
    """C ← C − A·Bᴴ restricted to the lower triangle (B defaults to A —
    the herk case). ONLY the lower triangle of the result is meaningful;
    the strict upper triangle holds unmodified entries of ``c``.

    Recursive split: diagonal blocks recurse, the off-diagonal block is
    one big gemm — so the flops approach the true herk count (half of a
    full gemm), which is where the reference's internal::herk wins too
    (src/internal/internal_herk.cc).

    The Pallas tile-triangle kernel (ops/pallas_ops.herk_lower_update)
    is an OPT-IN alternative for the pure-herk case
    (SLATE_TPU_PALLAS_HERK=1, single device, divisible shapes): round-3
    A/B measurement showed it HBM-bound on tile re-reads and no faster
    than this recursion end-to-end (PERF_HISTORY.md), so the jnp path is the
    default. Multi-device grids always use the recursion (GSPMD cannot
    partition a pallas_call, and rebalance() constraints live here)."""
    if b is None:
        from . import pallas_ops
        blk = pallas_ops.default_block(a.shape[1])
        if _single_device() and pallas_ops.herk_eligible(
                c.shape[0], a.shape[1], c.dtype, blk):
            # kernel runs HIGHEST regardless of prec (see pallas_ops —
            # it is HBM-bound, so the pass count doesn't matter)
            return pallas_ops.herk_lower_update(c, a, blk)
        b = a
    s = c.shape[0]
    if s <= base:
        return c - mm(a, jnp.conj(b).T, prec)
    h = _half(s, 8)
    c11 = herk_lower_rec(c[:h, :h], a[:h], b[:h], prec, base)
    c21 = c[h:, :h] - mm(a[h:], jnp.conj(b[:h]).T, prec)
    c22 = herk_lower_rec(c[h:, h:], a[h:], b[h:], prec, base)
    top = jnp.concatenate([c11, c[:h, h:]], axis=1)
    bot = jnp.concatenate([c21, c22], axis=1)
    return jnp.concatenate([top, bot], axis=0)


def dus_i32(x: Array, val: Array, i: int, j: int) -> Array:
    """dynamic_update_slice with int32 starts: with x64 on, python ints
    lower to s64 constants and the pre-0.6 SPMD partitioner emits a
    mixed s64/s32 compare the HLO verifier rejects (shared by the
    iterative potrf/getrf/geqrf outer loops)."""
    return lax.dynamic_update_slice(x, val, (jnp.int32(i), jnp.int32(j)))


def herk_trailing_inplace(a: Array, pan: Array, k1: int, nb: int,
                          prec: Optional[str] = None,
                          j_start: Optional[int] = None,
                          j_stop: Optional[int] = None) -> Array:
    """A[k1:, k1:] ← A[k1:, k1:] − pan·panᴴ written IN PLACE, one
    nb-wide column slab at a time (round 6).

    ``j_start``/``j_stop`` (round 7) bound the slab range [j_start,
    j_stop) so the lookahead pipeline can write the NEXT-panel slab
    (j_stop = k1 + nb) separately from the remainder (j_start =
    k1 + nb): each slab's gemm is unchanged (rows/cols sliced from the
    same ``pan`` at the same offsets), so splitting the call is
    bit-identical to one call over the full range — only the op ORDER
    between the two calls changes, which is exactly the point (the
    panel-(k+1) factor slots between them with no data edge to the
    remainder).

    The iterative right-looking loops previously routed this update
    through herk_lower_rec, whose 2×2 recursion concatenates full
    copies of the trailing block per level — the measured
    O(n²·log nt)-per-step re-traffic that set the round-5 n=2048
    crossover (PERF_HISTORY.md round 5). Here each trailing column slab
    j gets ONE gemm  pan[j0−k1:]·pan[j0−k1:j1−k1]ᴴ  and ONE
    dynamic_update_slice write of the (s−j0)×nb slab — the lower
    trapezoid is touched exactly once per step and the flop count is
    the triangular herk count (plus the slab-internal strict-upper
    corner, garbage by the factor contract). This is the reference's
    right-looking in-place trailing discipline (src/potrf.cc:136-176:
    per-block-column herk + gemm into resident tiles) in XLA form.

    Only the lower trapezoid of the result is meaningful; entries above
    the diagonal inside a diagonal slab receive the (harmless)
    symmetric update. Each slab is rebalance()d so multi-device grids
    keep the per-level resharding constraints."""
    s = a.shape[0]
    lo = k1 if j_start is None else j_start
    hi = s if j_stop is None else min(j_stop, s)
    for j0 in range(lo, hi, nb):
        jw = min(nb, s - j0)
        rows = pan[j0 - k1:]
        cols = pan[j0 - k1:j0 - k1 + jw]
        slab = a[j0:, j0:j0 + jw] - mm(rows, jnp.conj(cols).T, prec)
        a = dus_i32(a, rebalance(slab), j0, j0)
    return a


# ---------------------------------------------------------------------------
# Cholesky of one diagonal block
# ---------------------------------------------------------------------------

def chol_lower_rec(a: Array, base: int = 128) -> Array:
    """Lower Cholesky factor of one (nb × nb) diagonal block by 2×2
    recursion (trailing entries above the diagonal are garbage, matching
    lax.linalg.cholesky's tril-only contract is applied by callers).
    NaN-poisons like lax.linalg.cholesky on non-SPD input."""
    n = a.shape[0]
    if n <= base:
        # symmetrize_input=False: storage may be lower-only (the
        # driver no longer mirrors); read the lower triangle like
        # LAPACK dpotrf instead of averaging in a zero upper
        return lax.linalg.cholesky(a, symmetrize_input=False)
    h = _half(n, 8)
    l11 = chol_lower_rec(a[:h, :h], base)
    l21 = trsm_rec(l11, a[h:, :h], left=False, lower=True, conj_a=True,
                   trans_a=True, base=base)
    a22 = a[h:, h:] - mm(l21, jnp.conj(l21).T)
    l22 = chol_lower_rec(a22, base)
    top = jnp.concatenate([l11, jnp.zeros((h, n - h), a.dtype)], axis=1)
    bot = jnp.concatenate([l21, l22], axis=1)
    return jnp.concatenate([top, bot], axis=0)


def _chol_unrolled(d: Array, ib: int) -> Array:
    """Straight-line (unrolled) Cholesky of an (ib × ib) block — no loop
    construct, so XLA fuses the whole recurrence into one kernel instead
    of paying ~3 µs per column of while-loop latency (measured: the
    column chain is what makes lax.linalg.cholesky(512) cost 1.5 ms)."""
    rows = jnp.arange(ib)
    for j in range(ib):
        dj = jnp.sqrt(jnp.real(d[j, j])).astype(d.dtype)
        col = d[:, j] / dj
        col = jnp.where(rows > j, col, 0).at[j].set(dj)
        d = d.at[:, j].set(col)
        d = d - jnp.where((rows[:, None] > j) & (rows[None, :] > j),
                          jnp.outer(col, jnp.conj(col)), 0)
    return jnp.tril(d)


def _trtri_unrolled(l: Array, ib: int) -> Array:
    """Straight-line inverse of a lower-triangular (ib × ib) block."""
    cols = jnp.arange(ib)
    x = jnp.zeros_like(l)
    for i in range(ib):
        lrow = jnp.where(cols < i, l[i, :], 0)
        e_i = (cols == i).astype(l.dtype)
        x = x.at[i, :].set((e_i - lrow @ x) / l[i, i])
    return x


def chol_tile_blocked(a: Array, ib: int = 64) -> Array:
    """Cholesky of one diagonal tile as a fori_loop over ib-wide steps.

    Per step: unrolled ib×ib factor + inverse (straight-line, fused),
    one (b × ib) MXU matmul for the sub-panel, one rank-ib MXU update.
    Sequential latency is b/ib loop steps instead of b column steps.
    ib=64 measured best at n=8192 on one v5e chip (sweep: ib 8/32/64 →
    3041/3267/3333 GFLOP/s at nb=512; nb=1024+ib=64 → 4187). NaN-poisons
    on non-SPD like lax.linalg.cholesky (sqrt of negative)."""
    b = a.shape[0]
    from . import pallas_ops
    if _single_device() and pallas_ops.chol_eligible(b, a.dtype):
        # round 5: the whole tile factor as ONE Mosaic kernel — the
        # fori_loop path below pays ~230 µs per ib-step in per-op
        # dispatch latency (64 sequential trtri matvecs, each its own
        # XLA op); in-kernel the same chain is pipeline-latency only
        # (measured: PERF_HISTORY.md round 5, tools/potrf_ab.py)
        return pallas_ops.chol_tile(a)
    if b % ib or b <= ib:
        if a.dtype in (jnp.bfloat16, jnp.float16):
            # the lax.linalg.cholesky base lowers to a LAPACK custom
            # call with no bf16/f16 kernel (CPU raises, round 11);
            # factor the ONE diagonal tile in f32 and round back — the
            # standard low-precision-factorization recipe (tile math
            # in higher precision, the O(n³) trailing gemms stay low),
            # and what the mixed-precision drivers (gesv_mixed/
            # posv_mixed factor_dtype=bf16) need to run at all
            hi = lax.linalg.cholesky(a.astype(jnp.float32),
                                     symmetrize_input=False)
            return jnp.tril(hi).astype(a.dtype)
        return jnp.tril(lax.linalg.cholesky(a, symmetrize_input=False))
    rows = jnp.arange(b)

    def body(s, a):
        j0 = s * ib
        d = lax.dynamic_slice(a, (j0, j0), (ib, ib))
        l8 = _chol_unrolled(d, ib)
        inv8 = _trtri_unrolled(l8, ib)
        panel = lax.dynamic_slice(a, (0, j0), (b, ib))
        below = jnp.where((rows >= j0 + ib)[:, None], panel, 0)
        col = mm(below, jnp.conj(inv8).T)  # (b, ib) tail of the L column
        a = a - mm(col, jnp.conj(col).T)  # nonzero only in [j1:, j1:]
        # write back the column block: l8 on the diagonal, solved tail
        # below (rows < j0 become 0 — they are strictly-upper, dropped by
        # the final tril anyway)
        colw = lax.dynamic_update_slice(col, l8, (j0, 0))
        a = lax.dynamic_update_slice(a, colw, (0, j0))
        return a

    a = lax.fori_loop(0, b // ib, body, a)
    return jnp.tril(a)


# ---------------------------------------------------------------------------
# blocked panel LU (partial pivot)
# ---------------------------------------------------------------------------

def _panel_getrf_base(a: Array) -> Tuple[Array, Array, Array]:
    """Right-looking fori_loop LU on an (H × ib) panel.

    Returns (lu, perm, info): perm is gather-semantics (out = in[perm]).
    A column whose remaining entries are all zero keeps the diagonal
    pivot (permutation stays valid) and flags info."""
    hh, w = a.shape
    rows = jnp.arange(hh)
    cols = jnp.arange(w)

    def body(j, carry):
        a, perm, info = carry
        col = lax.dynamic_slice(a, (0, j), (hh, 1))[:, 0]
        score = jnp.where(rows >= j, jnp.abs(col), -1.0)
        p = jnp.argmax(score).astype(jnp.int32)
        # swap rows j <-> p (reads before writes; p == j is a no-op)
        row_j = a[j, :]
        row_p = a[p, :]
        a = a.at[j, :].set(row_p).at[p, :].set(row_j)
        pj, pp = perm[j], perm[p]
        perm = perm.at[j].set(pp).at[p].set(pj)
        d = a[j, j]
        bad = jnp.isnan(jnp.abs(d)) | (jnp.abs(d) == 0)
        info = jnp.where((info == 0) & bad, j + 1, info)
        dsafe = jnp.where(bad, jnp.ones((), a.dtype), d)
        col2 = lax.dynamic_slice(a, (0, j), (hh, 1))[:, 0]
        lcol = jnp.where(rows > j, col2 / dsafe, col2)
        a = a.at[:, j].set(lcol)
        urow = jnp.where(cols > j, a[j, :], 0)
        lmask = jnp.where(rows > j, lcol, 0)
        a = a - jnp.outer(lmask, urow)
        return (a, perm, info)

    perm0 = jnp.arange(hh, dtype=jnp.int32)
    a, perm, info = lax.fori_loop(
        0, w, body, (a, perm0, jnp.zeros((), jnp.int32)))
    return a, perm, info


def permute_rows_limited(x: Array, perm: Array, max_moved: int) -> Array:
    """out = x[perm] where perm moves at most ``max_moved`` rows (the case
    for partial-pivot panel permutations: w pivots displace ≤ 2w rows).

    Round-5 on-chip finding: the "touch only the moved rows" scheme
    (nonzero + row gather + row SCATTER) measures SLOWER than the
    plain full gather on TPU — 10.4 vs 6.4 ms at (16384², 2048 moved)
    — because XLA:TPU lowers the dynamic row scatter far below HBM
    bandwidth while the full-row gather streams. ``max_moved`` is kept
    in the signature as documentation of the displacement bound (and
    for any future backend where bounded scatter wins).

    Round 6: the DEFAULT getrf/getrf_tntpiv paths no longer call this
    per level at all — the permutation is folded into the trailing
    update's row reads (pivot fusion, linalg/lu.py) and the stored L
    columns are reordered once at the end. This materialized permute
    remains in the recursion (_getrf_rec), the legacy arm
    (Options.lu_pivot_fusion=False), and the wide-matrix rest solve."""
    del max_moved
    return x[perm]


def lift_tail_perm(p_tail: Array, h: int, m: int, dtype=None) -> Array:
    """The length-``m`` gather perm [0..h) ++ (h + p_tail) WITHOUT a
    concatenate.

    Root cause of the long-open "mesh getrf at nb=64 returns a corrupted
    perm" item (CHANGES.md round 6, reproduced + bisected this round):
    on jax 0.4.37's old SPMD partitioner, lowering
    ``concatenate([iota(h), h + p_tail])`` with a SHARDED ``p_tail``
    (GSPMD propagates the panel's row sharding into the perm carry of
    the fori base) produces OUT-OF-RANGE indices — the partitioned
    concatenate mis-applies shard offsets to the second operand. The
    iota/where/clamped-gather formulation below lowers correctly under
    the same shardings (verified against the minimal repro, now a
    regression test: tests/test_lookahead.py::test_compose_tail_sharded
    and the nb=64 mesh getrf it unblocks). nb=32 never hit it because a
    32-wide panel is one fori base — no composition."""
    if dtype is None:
        dtype = p_tail.dtype
    iota = jnp.arange(m, dtype=dtype)
    tail = p_tail[jnp.maximum(iota - h, 0)]
    return jnp.where(iota < h, iota, h + tail.astype(dtype))


def _compose_tail(p1: Array, p2: Array, h: int) -> Array:
    """Total gather perm for 'apply p1, then p2 on rows h:'."""
    return p1[lift_tail_perm(p2, h, p1.shape[0], p1.dtype)]


def panel_getrf(a: Array, ib: int = PANEL_IB,
                prec: Optional[str] = None
                ) -> Tuple[Array, Array, Array]:
    """Blocked partial-pivot LU of a tall (H × w) panel, recursing on
    width down to an ib-column fori_loop base. Replaces lax.linalg.lu,
    whose LuDecompositionBlock custom-call both runs out of VMEM on tall
    v5e panels and is latency-bound (module docstring).

    Returns (lu, perm, info) with gather semantics a[perm] = L·U."""
    hh, w = a.shape
    if w <= ib or _round_to(w // 2, ib) >= w:
        # round 5: the base runs as ONE Mosaic kernel where eligible —
        # the in-kernel column loop replaces ~30 XLA-op dispatches per
        # column (pallas_ops._lu_panel_kernel; a straight-line unrolled
        # XLA base was tried in round 3 and OOM-killed the compiler at
        # n=16384 panel heights, the fori base is the fallback).
        from . import pallas_ops
        if _single_device() and pallas_ops.lu_panel_eligible(hh, w,
                                                             a.dtype):
            return pallas_ops.lu_panel_base(a)
        return _panel_getrf_base(a)
    from . import pallas_ops
    if _single_device() and pallas_ops.lu_panel_eligible(hh, w,
                                                         a.dtype):
        # round 7 (deeper-unrolled bases): a WIDE base (w ≤ 128) runs
        # as ONE kernel invocation instead of recursing into 32-wide
        # bases with XLA trsm/gemm aggregation between them — the
        # kernel's column loop is arithmetic-identical to the fori
        # base at any width, so this only removes dispatch/fusion
        # boundaries. Gated by the same scoped-VMEM cells budget, so
        # it activates on the SHORT panels of a factorization's tail —
        # exactly the latency-dominated steps.
        return pallas_ops.lu_panel_base(a)
    h = _round_to(w // 2, ib)
    lu1, p1, i1 = panel_getrf(a[:, :h], ib, prec)
    right = permute_rows_limited(a[:, h:], p1, 2 * h)
    u_top = trsm_rec(lu1[:h, :h], right[:h], left=True, lower=True,
                     unit=True, prec=prec, base=max(ib, 64))
    schur = right[h:] - mm(lu1[h:, :h], u_top, prec)
    lu2, p2, i2 = panel_getrf(schur, ib, prec)
    low_left = permute_rows_limited(lu1[h:, :h], p2, 2 * (w - h))
    top = jnp.concatenate([lu1[:h], u_top], axis=1)
    bot = jnp.concatenate([low_left, lu2], axis=1)
    lu = jnp.concatenate([top, bot], axis=0)
    perm = _compose_tail(p1, p2, h)
    info = jnp.where(i1 > 0, i1,
                     jnp.where(i2 > 0, i2 + h, 0)).astype(jnp.int32)
    return lu, perm, info


@functools.partial(jax.jit, static_argnames=("ib",))
def panel_getrf_jit(a: Array, ib: int = PANEL_IB):
    """jit entry so bucketed panel shapes compile once per bucket."""
    return panel_getrf(a, ib)


def panel_getrf_batched(stack: Array) -> Tuple[Array, Array, Array]:
    """One BATCHED pivoted panel factorization over a (B, H, w) chunk
    stack — the per-round kernel of the CALU tournament (round 7).

    The tournament previously ran each round through
    ``vmap(lax.linalg.lu)``: a batched custom-call whose backends
    execute the batch as a SEQUENTIAL loop of per-block column
    recurrences (XLA:CPU loops lapack getrf over the batch dim;
    XLA:TPU's LuDecompositionBlock expansion is likewise serial per
    block — the "per-block sequential tree" of ISSUE 3). Here the whole
    round is ONE fori_loop of w column steps whose body does the pivot
    search / swap / rank-1 update for EVERY chunk at once: batch
    parallelism lives INSIDE each op (batched argmax, batched outer
    product — VPU/MXU-wide), and the sequential depth of a round is w
    column steps regardless of the chunk count. The body is written
    HAND-BATCHED — row swaps as take_along_axis gathers of a swapped
    index map rather than vmap of the fori base's dynamic scatters
    (vmapped batched-index scatters compile ~40 s and run ~6× slower
    per round on XLA:CPU; the gather form is also the natural TPU
    lowering). Arithmetic is op-for-op the fori base's, so per-chunk
    results match _panel_getrf_base exactly. Reference analog: the
    reference plays its tournament across ranks in parallel
    (src/getrf_tntpiv.cc:110-175, tileSend/Recv pairs); a single XLA
    program gets the same concurrency from batching, not message
    passing.

    Returns (lu, perm, info) stacks with the _panel_getrf_base
    contract per chunk."""
    return _panel_getrf_batched_jit(stack)


@jax.jit
def _panel_getrf_batched_jit(stack: Array):
    return _panel_getrf_batched_impl(stack)


def _panel_getrf_batched_impl(stack: Array):
    """Traceable body of panel_getrf_batched — shared by the CALU
    tournament's jitted entry above and the batched blocked getrf
    outer loop (getrf_batched), which composes it per panel inside
    ONE larger program."""
    bsz, hh, w = stack.shape
    iot = jnp.arange(hh)[None, :]                     # (1, H)
    rdtype = jnp.real(stack).dtype

    def body(j, carry):
        a, perm, info = carry
        col = lax.dynamic_slice_in_dim(a, j, 1, axis=2)[:, :, 0]  # (B, H)
        score = jnp.where(iot >= j, jnp.abs(col), -1.0).astype(rdtype)
        p = jnp.argmax(score, axis=1).astype(jnp.int32)           # (B,)
        # swap rows j <-> p_b as ONE gather of a swapped index map
        idx = jnp.where(iot == j, p[:, None], iot)
        idx = jnp.where(iot == p[:, None], j, idx)    # p == j stays j
        a = jnp.take_along_axis(a, idx[:, :, None], axis=1)
        perm = jnp.take_along_axis(perm, idx, axis=1)
        d = jnp.take_along_axis(col, p[:, None], axis=1)[:, 0]    # (B,)
        bad = jnp.isnan(jnp.abs(d)) | (jnp.abs(d) == 0)
        info = jnp.where((info == 0) & bad, j + 1, info).astype(jnp.int32)
        dsafe = jnp.where(bad, jnp.ones((), a.dtype), d)
        col2 = lax.dynamic_slice_in_dim(a, j, 1, axis=2)[:, :, 0]
        lcol = jnp.where(iot > j, col2 / dsafe[:, None], col2)    # (B, H)
        cW = jnp.arange(w)[None, None, :]
        a = jnp.where(cW == j, lcol[:, :, None], a)
        urow = lax.dynamic_slice_in_dim(a, j, 1, axis=1)[:, 0, :]  # (B, w)
        urow = jnp.where(cW[0] > j, urow, 0)
        lmask = jnp.where(iot > j, lcol, 0)
        a = a - lmask[:, :, None] * urow[:, None, :]
        return (a, perm, info)

    perm0 = jnp.broadcast_to(jnp.arange(hh, dtype=jnp.int32)[None, :],
                             (bsz, hh))
    a, perm, info = lax.fori_loop(
        0, w, body, (stack, perm0, jnp.zeros((bsz,), jnp.int32)))
    return a, perm, info


# ---------------------------------------------------------------------------
# blocked panel QR (Householder)
# ---------------------------------------------------------------------------

def _larfg(alpha: Array, tail: Array):
    """Householder reflector of [alpha; tail] (LAPACK larfg): returns
    (beta, tau, scale) with v = [1; tail·scale], H·x = [beta; 0],
    H = I − τ·v·vᴴ, τ = (β − α)/β, v_tail = x/(α − β).
    Degenerate (zero tail, real alpha) → τ = 0, H = I."""
    sig = jnp.sum(jnp.real(tail * jnp.conj(tail)))
    anorm = jnp.sqrt(jnp.real(alpha * jnp.conj(alpha)) + sig)
    beta = jnp.where(jnp.real(alpha) <= 0, anorm, -anorm).astype(alpha.dtype)
    if jnp.iscomplexobj(alpha):
        degenerate = (sig == 0) & (jnp.imag(alpha) == 0)
    else:
        degenerate = sig == 0
    one = jnp.ones((), alpha.dtype)
    zero = jnp.zeros((), alpha.dtype)
    beta_safe = jnp.where(degenerate | (beta == 0), one, beta)
    denom_safe = jnp.where(degenerate, one, alpha - beta)
    tau = jnp.where(degenerate, zero, (beta - alpha) / beta_safe)
    scale = jnp.where(degenerate, zero, 1.0 / denom_safe)
    beta_out = jnp.where(degenerate, alpha, beta)
    return beta_out, tau, scale


def _panel_geqrf_base(a: Array) -> Tuple[Array, Array]:
    """fori_loop Householder QR on an (H × ib) panel → packed V\\R + taus."""
    hh, w = a.shape
    rows = jnp.arange(hh)
    cols = jnp.arange(w)

    def body(j, carry):
        a, taus = carry
        col = lax.dynamic_slice(a, (0, j), (hh, 1))[:, 0]
        alpha = col[j]
        tail = jnp.where(rows > j, col, 0)
        beta, tau, scale = _larfg(alpha, tail)
        v = jnp.where(rows > j, col * scale, 0).at[j].set(1.0)
        # eliminate with Hᴴ = I − conj(τ)·v·vᴴ (LAPACK larfg convention:
        # Hᴴ·x = β·e₁ with H = I − τ·v·vᴴ and Q = H₀·H₁·…)
        w_row = jnp.conj(v) @ a  # (w,)
        upd = jnp.outer(jnp.conj(tau) * v, jnp.where(cols > j, w_row, 0))
        a = a - upd
        # store beta on the diagonal, v's tail below it
        newcol = jnp.where(rows > j, v, 0).at[j].set(beta)
        keep = jnp.where(rows < j, col, 0)
        a = a.at[:, j].set(newcol + keep)
        taus = taus.at[j].set(tau)
        return (a, taus)

    taus0 = jnp.zeros((w,), a.dtype)
    a, taus = lax.fori_loop(0, w, body, (a, taus0))
    return a, taus


def _larft_base(v: Array, taus: Array, prec: Optional[str] = None) -> Array:
    """LAPACK's columnwise T recurrence: T[:i,i] = −τᵢ·T[:i,:i]·(Vᴴvᵢ),
    T[i,i] = τᵢ. One Gram matmul + a width-step fori_loop — kept as the
    small-width base and the parity reference for the closed form."""
    nbb = taus.shape[0]
    w = mm(jnp.conj(v).T, v, prec)
    idx = jnp.arange(nbb)

    def body(i, t):
        wi = jnp.where(idx < i, w[:, i], 0)
        col = -taus[i] * (t @ wi)
        col = jnp.where(idx < i, col, 0)
        col = col.at[i].set(taus[i].astype(col.dtype))
        return t.at[:, i].set(col)

    t0 = jnp.zeros((nbb, nbb), v.dtype)
    return lax.fori_loop(0, nbb, body, t0)


_LARFT_BASE = 32


def larft(v: Array, taus: Array, prec: Optional[str] = None) -> Array:
    """Forward columnwise T factor of the compact-WY representation.

    LAPACK's w-step recurrence (see _larft_base) in matrix form reads
    T·(I + S·D) = D with S = striu(VᴴV), D = diag(τ) — so
        T = D·(I + S·D)⁻¹
    one Gram matmul + one log-depth unit-upper triangular inverse
    (trtri_lower_batched on the transpose) + a row scaling, replacing
    the w-step serial chain. Degenerate columns (τᵢ = 0) come out
    exactly zero: column i of (I + S·D) is then eᵢ, so column i of the
    inverse is eᵢ and row-scaling by τᵢ = 0 zeroes T[:,i]'s support.
    Reference analog: tile::larft inside the panel task
    (src/internal/internal_geqrf.cc) — serial per tile there; here the
    whole T is MXU gemms so back-transforms stay device-resident."""
    nbb = taus.shape[0]
    if nbb <= _LARFT_BASE:
        return _larft_base(v, taus, prec)
    g = mm(jnp.conj(v).T, v, prec)
    s = jnp.triu(g, 1)
    m = jnp.eye(nbb, dtype=v.dtype) + s * taus[None, :].astype(v.dtype)
    minv = trtri_lower_batched(jnp.transpose(m), unit=True)
    return taus[:, None].astype(v.dtype) * jnp.transpose(minv)


def _split_v(vr: Array, w: int) -> Array:
    """Unit-lower-trapezoidal V from a packed V\\R panel (first w cols)."""
    v = jnp.tril(vr[:, :w], -1)
    return v.at[jnp.arange(w), jnp.arange(w)].set(1.0)


def panel_geqrf(a: Array, ib: int = PANEL_IB,
                prec: Optional[str] = None) -> Tuple[Array, Array]:
    """Blocked Householder QR of a tall (H × w) panel → (V\\R packed,
    taus). Recursion on width; flops above the ib base are gemms.
    Replaces the ~25 ms/panel lax.linalg.geqrf expansion."""
    hh, w = a.shape
    if w <= ib or _round_to(w // 2, ib) >= w:
        # round 5: one Mosaic kernel per base where eligible — the
        # in-kernel column loop replaces ~12 XLA-op dispatches per
        # column (pallas_ops._qr_panel_kernel; same rationale as the
        # LU panel base above).
        from . import pallas_ops
        if _single_device() and pallas_ops.qr_panel_eligible(hh, w,
                                                             a.dtype):
            return pallas_ops.qr_panel_base(a)
        return _panel_geqrf_base(a)
    from . import pallas_ops
    if _single_device() and pallas_ops.qr_panel_wide_eligible(hh, w,
                                                              a.dtype):
        # round 7 (deeper-unrolled bases): a wide base runs as ONE
        # micro-blocked kernel — per-column Householder updates
        # restricted to 32-lane micro-blocks, compact-WY MXU updates
        # between blocks (chol_tile's three-level structure brought to
        # the QR panel; see pallas_ops._qr_panel_wide_kernel).
        return pallas_ops.qr_panel_base_wide(a)
    h = _round_to(w // 2, ib)
    vr1, taus1 = panel_geqrf(a[:, :h], ib, prec)
    v1 = _split_v(vr1, h)
    t1 = larft(v1, taus1, prec)
    # right half ← (I − V1 T1 V1ᴴ)ᴴ · right
    right = a[:, h:]
    right = right - mm(v1, mm(jnp.conj(t1).T,
                              mm(jnp.conj(v1).T, right, prec), prec), prec)
    vr2, taus2 = panel_geqrf(right[h:], ib, prec)
    top = jnp.concatenate([vr1[:h], right[:h]], axis=1)
    bot = jnp.concatenate([vr1[h:], vr2], axis=1)
    return (jnp.concatenate([top, bot], axis=0),
            jnp.concatenate([taus1, taus2]))


@jax.jit
def apply_block_reflectors_stacked(Vs: Array, Ts: Array, C: Array) -> Array:
    """C ← Q·C for Q = ∏ₖ(I − VₖTₖVₖᴴ) given stacked per-panel block
    reflectors Vs (k, n, b) / Ts (k, b, b) — the shared back-transform
    of the two-sided reductions (unmtr_he2td, unmbr ge2bd). Last panel
    applies first; all MXU gemms inside one jit."""
    n_panels = Vs.shape[0]

    def step(i, C):
        k = n_panels - 1 - i
        V = Vs[k]
        T = Ts[k]
        return C - V @ (T @ (jnp.conj(V).T @ C))

    return lax.fori_loop(0, n_panels, step, C)


def level_plan(rem: int, min_panels: int = 4):
    """Panel counts per level for the halving two-sided reductions
    (he2hb / ge2tb): halve the remaining panels until few are left,
    then finish — O(log rem) jitted programs, ~1.7× flop overhead
    versus perfectly-shrinking updates."""
    plan = []
    while rem > 0:
        kp = rem if rem <= min_panels else rem // 2
        plan.append(kp)
        rem -= kp
    return plan


@jax.jit
def apply_block_reflectors_stacked_H(Vs: Array, Ts: Array,
                                     C: Array) -> Array:
    """C ← Qᴴ·C for the same stacked Q as apply_block_reflectors_stacked
    (first panel applies first; Hᴴ = I − V·Tᴴ·Vᴴ)."""
    n_panels = Vs.shape[0]

    def step(k, C):
        V = Vs[k]
        T = Ts[k]
        return C - V @ (jnp.conj(T).T @ (jnp.conj(V).T @ C))

    return lax.fori_loop(0, n_panels, step, C)


@functools.partial(jax.jit, static_argnames=("ib",))
def panel_geqrf_with_t(a: Array, ib: int = PANEL_IB):
    """jit entry: bucketed panel QR + its T factor, compiled per bucket.

    Returns (vr_packed, taus, T) where T is (w, w)."""
    vr, taus = panel_geqrf(a, ib)
    w = a.shape[1]
    v = _split_v(vr, w)
    t = larft(v, taus)
    return vr, taus, t


# ---------------------------------------------------------------------------
# batched blocked factorizations over [B, n, n] stacks (round 10)
# ---------------------------------------------------------------------------
# The many-small-problems engine: the round-7 panel_getrf_batched recipe
# (hand-batched fori/unrolled bodies, row swaps as take_along_axis
# gathers of a swapped index map, NEVER vmap of per-item custom calls —
# backends execute a vmapped factorization custom-call as a SEQUENTIAL
# per-item loop) generalized to full blocked factorizations and the
# triangular solves they feed. Reference analog: SLATE's
# HostBatch/Devices batched-gemm target class (PAPER.md L3) and the
# batched one-sided factorizations of Haidar et al. (IJHPCA 2015) —
# batch parallelism lives INSIDE each op (batched argmax, batched
# gemm: VPU/MXU-wide), sequential depth is that of ONE problem.
#
# Discipline shared by every kernel here:
#   * outer loops are python-static and write IN PLACE (round-6 dus
#     slab discipline) — shapes depend only on (n, nb), so one program
#     serves any batch once the batch dim is bucketed (linalg/batched);
#   * per-item arithmetic is batch-independent (elementwise across B,
#     matmuls with a leading batch dim), so results are BIT-IDENTICAL
#     across batch sizes/paddings — a B=1 run is the per-request
#     reference for the batched serving path (tests/test_batched.py);
#   * failure is GUARDED, not NaN-poisoned: a singular/non-SPD item
#     flags its own info and divides by a safe 1 — its neighbors'
#     bits are untouched (per-item isolation).


def _bT(x: Array) -> Array:
    """Transpose of the last two axes (batched matrix transpose)."""
    return jnp.swapaxes(x, -1, -2)


def _trtri_unrolled_b(l: Array, ib: int, unit: bool = False) -> Array:
    """Batched straight-line inverse of [B, ib, ib] lower-triangular
    blocks (the _trtri_unrolled_u recurrence with a leading batch dim)."""
    cols = jnp.arange(ib)
    x = jnp.zeros_like(l)
    for i in range(ib):
        lrow = jnp.where(cols < i, l[:, i, :], 0)
        e_i = (cols == i).astype(l.dtype)
        row = e_i[None, :] - jnp.matmul(lrow[:, None, :], x)[:, 0, :]
        if not unit:
            row = row / l[:, i, i][:, None]
        x = x.at[:, i, :].set(row)
    return x


TRTRI_B_LEAF = 32


def trtri_lower_b(l: Array, unit: bool = False,
                  leaf: int = TRTRI_B_LEAF) -> Array:
    """Batched inv(L) over a [B, n, n] stack: 2×2 block recursion
    (python-static shapes) with batched unrolled leaves — the batched
    peer of trtri_lower_rec. Only the lower triangles are read."""
    n = l.shape[-1]
    if n <= leaf:
        return _trtri_unrolled_b(l, n, unit)
    h = _half(n, 8)
    ia = trtri_lower_b(l[:, :h, :h], unit, leaf)
    ic = trtri_lower_b(l[:, h:, h:], unit, leaf)
    off = -jnp.matmul(ic, jnp.matmul(l[:, h:, :h], ia))
    top = jnp.concatenate(
        [ia, jnp.zeros(ia.shape[:1] + (h, n - h), l.dtype)], axis=2)
    bot = jnp.concatenate([off, ic], axis=2)
    return jnp.concatenate([top, bot], axis=1)


TRSM_B_BASE = 64


def trsm_lower_b(m: Array, b: Array, unit: bool = False,
                 prec: Optional[str] = None,
                 base: int = TRSM_B_BASE) -> Array:
    """Batched X with M·X = B, M a [B, n, n] lower-triangular stack —
    block-column recursion, base case multiplies by the batched
    inverted diagonal block (the trsm_rec scheme with a batch dim)."""
    n = m.shape[-1]
    if n <= base:
        return mm(trtri_lower_b(m, unit), b, prec)
    h = _half(n, 8)
    x1 = trsm_lower_b(m[:, :h, :h], b[:, :h], unit, prec, base)
    rhs2 = b[:, h:] - mm(m[:, h:, :h], x1, prec)
    x2 = trsm_lower_b(m[:, h:, h:], rhs2, unit, prec, base)
    return jnp.concatenate([x1, x2], axis=1)


def trsm_upper_b(m: Array, b: Array, unit: bool = False,
                 prec: Optional[str] = None,
                 base: int = TRSM_B_BASE) -> Array:
    """Batched X with M·X = B, M a [B, n, n] upper-triangular stack."""
    n = m.shape[-1]
    if n <= base:
        inv = _bT(trtri_lower_b(_bT(m), unit))
        return mm(inv, b, prec)
    h = _half(n, 8)
    x2 = trsm_upper_b(m[:, h:, h:], b[:, h:], unit, prec, base)
    rhs1 = b[:, :h] - mm(m[:, :h, h:], x2, prec)
    x1 = trsm_upper_b(m[:, :h, :h], rhs1, unit, prec, base)
    return jnp.concatenate([x1, x2], axis=1)


def _chol_unrolled_b(d: Array, ib: int) -> Tuple[Array, Array]:
    """Batched straight-line Cholesky of [B, ib, ib] diagonal blocks →
    (tril L, info). Guarded pivots: the 1-based index of the first
    non-positive (or NaN) leading minor lands in info and the bad
    column divides by a safe 1 — the batched analog of
    _panel_getrf_base's info discipline (a failing item must not
    poison its batch neighbors, and the guarded arithmetic is
    batch-independent)."""
    bsz = d.shape[0]
    rows = jnp.arange(ib)
    rdtype = jnp.real(d).dtype
    info = jnp.zeros((bsz,), jnp.int32)
    for j in range(ib):
        dj = jnp.real(d[:, j, j])
        bad = jnp.isnan(dj) | (dj <= 0)
        info = jnp.where((info == 0) & bad, j + 1, info)
        dsafe = jnp.where(bad, jnp.ones((), rdtype), dj)
        root = jnp.sqrt(dsafe).astype(d.dtype)
        col = d[:, :, j] / root[:, None]
        col = jnp.where(rows[None, :] > j, col, 0)
        col = col.at[:, j].set(root)
        d = d.at[:, :, j].set(col)
        live = (rows[:, None] > j) & (rows[None, :] > j)
        d = d - jnp.where(live[None],
                          col[:, :, None] * jnp.conj(col)[:, None, :], 0)
    return jnp.tril(d), info


CHOL_B_IB = 32


def chol_tile_b(d: Array, ib: int = CHOL_B_IB) -> Tuple[Array, Array]:
    """Batched Cholesky of [B, nb, nb] diagonal tiles → (tril L, info):
    python-unrolled ib-wide steps (chol_tile_blocked's structure with a
    batch dim and NO lax.linalg/Pallas base — the batched paths must
    never lower to per-item custom calls)."""
    b = d.shape[-1]
    if b <= ib or b % ib:
        return _chol_unrolled_b(d, b)
    bsz = d.shape[0]
    info = jnp.zeros((bsz,), jnp.int32)
    for j0 in range(0, b, ib):
        j1 = j0 + ib
        blk = d[:, j0:j1, j0:j1]
        l8, binfo = _chol_unrolled_b(blk, ib)
        info = jnp.where((info == 0) & (binfo > 0), j0 + binfo, info)
        d = d.at[:, j0:j1, j0:j1].set(l8)
        if j1 >= b:
            continue
        inv8 = _trtri_unrolled_b(l8, ib)
        col = jnp.matmul(d[:, j1:, j0:j1], _bT(jnp.conj(inv8)))
        d = d.at[:, j1:, j0:j1].set(col)
        d = d.at[:, j1:, j1:].set(
            d[:, j1:, j1:] - jnp.matmul(col, _bT(jnp.conj(col))))
    return jnp.tril(d), info


def potrf_batched(a: Array, nb: int,
                  prec: Optional[str] = None) -> Tuple[Array, Array]:
    """Batched blocked Cholesky over a [B, n, n] stack (lower) →
    (tril L stack, info[B]).

    Iterative in-place outer loop — batched tile factor, batched
    inverted-diagonal-block panel trsm, trailing update written one
    nb-wide column slab at a time (the round-6 herk_trailing_inplace
    discipline with a batch dim). Reads only the lower triangles;
    entries above the diagonal inside a slab receive the harmless
    symmetric update (dropped by the final tril). One non-SPD item
    flags its own info (guarded pivots, _chol_unrolled_b) and leaves
    every neighbor's arithmetic untouched."""
    bsz, n, _ = a.shape
    info = jnp.zeros((bsz,), jnp.int32)
    for k0 in range(0, n, nb):
        w = min(nb, n - k0)
        k1 = k0 + w
        lkk, tinfo = chol_tile_b(a[:, k0:k1, k0:k1])
        info = jnp.where((info == 0) & (tinfo > 0), k0 + tinfo, info)
        a = a.at[:, k0:k1, k0:k1].set(lkk)
        if k1 >= n:
            continue
        inv = trtri_lower_b(lkk)
        pan = mm(a[:, k1:, k0:k1], _bT(jnp.conj(inv)), prec)
        a = a.at[:, k1:, k0:k1].set(pan)
        for j0 in range(k1, n, nb):
            jw = min(nb, n - j0)
            rows_ = pan[:, j0 - k1:]
            cols_ = pan[:, j0 - k1:j0 - k1 + jw]
            slab = a[:, j0:, j0:j0 + jw] - mm(rows_, _bT(jnp.conj(cols_)),
                                              prec)
            a = a.at[:, j0:, j0:j0 + jw].set(slab)
    return jnp.tril(a), info


def lift_tail_perm_b(p_tail: Array, h: int, m: int) -> Array:
    """Batched lift_tail_perm: the [B, m] gather perm
    [0..h) ++ (h + p_tail) for a [B, m−h] tail perm stack — same
    iota/where/clamped-gather form (no concatenate), batch-wise."""
    bsz = p_tail.shape[0]
    iota = jnp.arange(m, dtype=p_tail.dtype)[None, :]
    idx = jnp.broadcast_to(jnp.maximum(iota - h, 0), (bsz, m))
    tail = jnp.take_along_axis(p_tail, idx, axis=1)
    return jnp.where(iota < h, iota, h + tail)


def getrf_batched(a: Array, nb: int,
                  prec: Optional[str] = None
                  ) -> Tuple[Array, Array, Array]:
    """Batched blocked partial-pivot LU over a [B, n, n] stack →
    (LU stack, perm [B, n] gather semantics, info[B]).

    Outer loop over nb-wide panels, in place: each panel is ONE
    hand-batched pivoted factorization (_panel_getrf_batched_impl —
    the round-7 CALU round kernel, batched argmax pivot search + row
    swaps as take_along_axis gathers of a swapped index map), the
    panel permutation is lifted to a full-row gather map WITHOUT a
    concatenate (lift_tail_perm_b) and applied to the whole row block
    batch-wise, U12 comes from a batched unit-lower trsm and the Schur
    complement from one batched gemm. A structurally singular item
    keeps a valid permutation, flags its own 1-based info column, and
    never perturbs its neighbors."""
    bsz, n, _ = a.shape
    perm = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :],
                            (bsz, n))
    info = jnp.zeros((bsz,), jnp.int32)
    for k0 in range(0, n, nb):
        w = min(nb, n - k0)
        k1 = k0 + w
        plu, pperm, pinfo = _panel_getrf_batched_impl(a[:, k0:, k0:k1])
        info = jnp.where((info == 0) & (pinfo > 0), k0 + pinfo,
                         info).astype(jnp.int32)
        full = lift_tail_perm_b(pperm, k0, n)
        a = jnp.take_along_axis(a, full[:, :, None], axis=1)
        perm = jnp.take_along_axis(perm, full, axis=1)
        a = a.at[:, k0:, k0:k1].set(plu)
        if k1 >= n:
            continue
        u12 = trsm_lower_b(plu[:, :w, :w], a[:, k0:k1, k1:], unit=True,
                           prec=prec)
        a = a.at[:, k0:k1, k1:].set(u12)
        schur = a[:, k1:, k1:] - mm(plu[:, w:, :], u12, prec)
        a = a.at[:, k1:, k1:].set(schur)
    return a, perm, info


def _panel_geqrf_batched(a: Array) -> Tuple[Array, Array]:
    """Hand-batched Householder QR of a (B, H, w) panel stack →
    (packed V\\R, taus): one fori_loop of w column steps whose body
    reflects EVERY item at once (_panel_geqrf_base's arithmetic with a
    leading batch dim; dynamic column access via dynamic_slice, column
    writes as where-masks — the gather/mask discipline of
    _panel_getrf_batched_impl)."""
    bsz, hh, w = a.shape
    rows = jnp.arange(hh)[None, :]                    # (1, H)
    wcols = jnp.arange(w)
    is_cplx = jnp.iscomplexobj(a)

    def body(j, carry):
        a, taus = carry
        col = lax.dynamic_slice_in_dim(a, j, 1, axis=2)[:, :, 0]  # (B, H)
        alpha = lax.dynamic_slice_in_dim(col, j, 1, axis=1)[:, 0]  # (B,)
        tail = jnp.where(rows > j, col, 0)
        sig = jnp.sum(jnp.real(tail * jnp.conj(tail)), axis=1)
        anorm = jnp.sqrt(jnp.real(alpha * jnp.conj(alpha)) + sig)
        beta = jnp.where(jnp.real(alpha) <= 0, anorm,
                         -anorm).astype(a.dtype)
        if is_cplx:
            degenerate = (sig == 0) & (jnp.imag(alpha) == 0)
        else:
            degenerate = sig == 0
        one = jnp.ones((), a.dtype)
        zero = jnp.zeros((), a.dtype)
        beta_safe = jnp.where(degenerate | (beta == 0), one, beta)
        denom_safe = jnp.where(degenerate, one, alpha - beta)
        tau = jnp.where(degenerate, zero, (beta - alpha) / beta_safe)
        scale = jnp.where(degenerate, zero, 1.0 / denom_safe)
        v = jnp.where(rows > j, col * scale[:, None], 0)
        v = jnp.where(rows == j, one, v)
        w_row = jnp.matmul(jnp.conj(v)[:, None, :], a)[:, 0, :]  # (B, w)
        w_row = jnp.where(wcols[None, :] > j, w_row, 0)
        upd = ((jnp.conj(tau)[:, None] * v)[:, :, None]
               * w_row[:, None, :])
        a = a - upd
        newcol = jnp.where(rows > j, v, 0)
        newcol = jnp.where(rows == j, beta[:, None], newcol)
        colw = newcol + jnp.where(rows < j, col, 0)
        a = jnp.where(wcols[None, None, :] == j, colw[:, :, None], a)
        taus = jnp.where(wcols[None, :] == j,
                         tau[:, None].astype(taus.dtype), taus)
        return (a, taus)

    taus0 = jnp.zeros((bsz, w), a.dtype)
    a, taus = lax.fori_loop(0, w, body, (a, taus0))
    return a, taus


def _split_v_b(vr: Array, w: int) -> Array:
    """Batched unit-lower-trapezoidal V from packed V\\R stacks."""
    hh = vr.shape[1]
    v = jnp.tril(vr[:, :, :w], -1)
    return v + jnp.eye(hh, w, dtype=vr.dtype)[None]


def larft_b(v: Array, taus: Array, prec: Optional[str] = None) -> Array:
    """Batched forward columnwise T factor (larft's closed form with a
    batch dim): T = D·(I + striu(VᴴV)·D)⁻¹, the inverse via the batched
    unit-triangular trtri. Degenerate columns (τ = 0) come out exactly
    zero, same argument as larft."""
    nbb = taus.shape[-1]
    g = mm(_bT(jnp.conj(v)), v, prec)
    s = jnp.triu(g, 1)
    m = (jnp.eye(nbb, dtype=v.dtype)[None]
         + s * taus[:, None, :].astype(v.dtype))
    minv = trtri_lower_b(_bT(m), unit=True)
    return taus[:, :, None].astype(v.dtype) * _bT(minv)


def geqrf_batched(a: Array, nb: int,
                  prec: Optional[str] = None
                  ) -> Tuple[Array, Array, Array]:
    """Batched blocked Householder QR over a [B, m, n] stack (m ≥ n) →
    (packed V\\R stack, taus [B, n], Ts [B, ceil(n/nb), nb, nb]).

    Outer loop over nb-wide panels, in place: each panel is ONE
    hand-batched Householder factorization (_panel_geqrf_batched), its
    compact-WY T comes from the batched closed-form larft, and the
    trailing update is three batched gemms. The per-panel T factors
    are returned stacked (zero-padded to nb on the tail panel) so the
    solve path (gels_batched_using_factor) applies Qᴴ without
    recomputing them."""
    bsz, m_, n = a.shape
    taus = jnp.zeros((bsz, n), a.dtype)
    ts = []
    for k0 in range(0, n, nb):
        w = min(nb, n - k0)
        k1 = k0 + w
        vr, tau = _panel_geqrf_batched(a[:, k0:, k0:k1])
        a = a.at[:, k0:, k0:k1].set(vr)
        taus = taus.at[:, k0:k1].set(tau)
        v = _split_v_b(vr, w)
        t = larft_b(v, tau, prec)
        if w < nb:  # pad the tail T so the stack is rectangular
            t = jnp.pad(t, ((0, 0), (0, nb - w), (0, nb - w)))
        ts.append(t)
        if k1 < n:
            c = a[:, k0:, k1:]
            c = c - mm(v, mm(_bT(jnp.conj(t[:, :w, :w])),
                             mm(_bT(jnp.conj(v)), c, prec), prec), prec)
            a = a.at[:, k0:, k1:].set(c)
    return a, taus, jnp.stack(ts, axis=1)


# -- batched solves against the factor stacks -------------------------------


def getrs_batched(lu: Array, perm: Array, b: Array,
                  prec: Optional[str] = None) -> Array:
    """Batched A·X = B from getrf_batched factors: ONE batched row
    gather (b[perm], the pivot-fusion contract of linalg/lu.getrs) +
    batched unit-lower and upper trsm."""
    pb = jnp.take_along_axis(b, perm[:, :, None], axis=1)
    y = trsm_lower_b(lu, pb, unit=True, prec=prec)
    return trsm_upper_b(lu, y, unit=False, prec=prec)


def potrs_batched(l: Array, b: Array,
                  prec: Optional[str] = None) -> Array:
    """Batched A·X = B from potrf_batched factors (two batched trsm
    sweeps: L then Lᴴ)."""
    y = trsm_lower_b(l, b, unit=False, prec=prec)
    return trsm_upper_b(_bT(jnp.conj(l)), y, unit=False, prec=prec)


def gels_qr_solve_batched(vr: Array, taus: Array, ts: Array, b: Array,
                          nb: int, prec: Optional[str] = None) -> Array:
    """Batched least-squares solve from geqrf_batched factors:
    X = R⁻¹·(Qᴴ·B)[:n] — Qᴴ applied panel-forward via the stored
    compact-WY (V, T) pairs, then one batched upper trsm against R."""
    bsz, m_, n = vr.shape
    c = b
    for i, k0 in enumerate(range(0, n, nb)):
        w = min(nb, n - k0)
        v = _split_v_b(vr[:, k0:, k0:k0 + w], w)
        t = ts[:, i, :w, :w]
        ck = c[:, k0:, :]
        ck = ck - mm(v, mm(_bT(jnp.conj(t)),
                           mm(_bT(jnp.conj(v)), ck, prec), prec), prec)
        c = c.at[:, k0:, :].set(ck)
    r = jnp.triu(vr[:, :n, :n])
    return trsm_upper_b(r, c[:, :n, :], unit=False, prec=prec)
