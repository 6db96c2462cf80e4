"""Pallas TPU kernels for the factorizations' serial bases.

Reference analog: the hand-written batched device kernels of
src/cuda/*.cu and lapack::potrf/getrf/geqrf on the device inside
internal::potrf/getrf/geqrf — the diagonal tile and the panel bases
run as single device kernels rather than as chains of small ops.

Each kernel keeps a dependent column chain inside ONE Mosaic kernel,
where XLA would pay one op dispatch per step: the tile Cholesky
(chol_tile), the pivoted LU panel base (lu_panel_base) and the
Householder QR panel bases (qr_panel_base, qr_panel_base_wide). Each
has an eligibility gate (dtype, shape, backend) that ops/blocked.py
checks at its call site; ineligible shapes take the XLA fori base.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# In-VMEM blocked tile Cholesky (round 5)
# ---------------------------------------------------------------------------
#
# Round-5 on-chip profiling (PERF_HISTORY.md round 5) showed the tile
# Cholesky is the single-chip potrf floor: chol_tile_blocked's
# fori_loop pays ~230 us per ib-step, almost all of it the 64
# SEQUENTIAL (1,ib)@(ib,ib) matvecs of the unrolled trtri — each a
# separate XLA op with ~3 us dispatch latency. Inside ONE Mosaic
# kernel the same dependent chain costs only MXU/VPU pipeline latency.
# This kernel runs the whole (b,b) factor in VMEM with the classic
# LAPACK three-level blocking (b -> 128-block -> 32-micro -> column),
# all loops statically unrolled, all O(b^3) flops in MXU dots.
# Reference analog: lapack::potrf on the GPU inside internal::potrf
# (src/internal/internal_potrf.cc:58-75) — the reference also factors
# the diagonal tile with a single device kernel rather than a host
# round-trip.

_CHOL_IB = 128  # lane-aligned panel width (outer block)
_CHOL_MB = 32   # micro-block width inside a panel


def _chol_cols_unrolled(d, m):
    """Right-looking unrolled Cholesky of an (m, m) block (static m).
    NaN-poisons on non-SPD input (rsqrt of a negative), matching
    blocked.chol_tile_blocked semantics."""
    rI = jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)
    cI = jax.lax.broadcasted_iota(jnp.int32, (m, m), 1)
    one_col = rI[:, :1]
    for j in range(m):
        inv = jax.lax.rsqrt(d[j, j])
        colm = d[:, j:j + 1] * inv                       # (m, 1)
        colm = jnp.where(one_col > j, colm, 0.0)
        # (Mosaic has no scatter — element writes are mask selects)
        colm = jnp.where(one_col == j, d[j, j] * inv, colm)  # sqrt(d_jj)
        rank1 = colm * jnp.transpose(colm)               # outer product
        d = jnp.where((cI > j) & (rI > j), d - rank1, d)
        d = jnp.where(cI == j, colm, d)                  # write column j
    return d


def _trtri_cols_unrolled(l, m):
    """Unrolled inverse of the lower (m, m) triangle of ``l``."""
    cI = jax.lax.broadcasted_iota(jnp.int32, (m, m), 1)
    rI = jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)
    crow = cI[:1, :]
    x = jnp.zeros_like(l)
    for i in range(m):
        lrow = jnp.where(crow < i, l[i:i + 1, :], 0.0)   # (1, m)
        e_i = (crow == i).astype(l.dtype)
        row = (e_i - jax.lax.dot_general(
            lrow, x, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)) / l[i, i]
        x = jnp.where(rI == i, row, x)
    return x


def _chol_tile_kernel(a_ref, out_ref):
    """Kernel body. Mosaic's tpu.concatenate cannot mix pieces whose
    layouts carry different lane offsets, so the micro-step does NO
    concatenation/placement at all: the micro factor is applied to the
    whole panel by one dot with X = I + sel·(L⁻¹ − I)·selᵀ (selection-
    matrix placement — dots always produce offset-0 layouts), using
    the exact-arithmetic identity D·L⁻ᵀ = L on the diagonal micro rows
    (D = L·Lᴴ after the left-looking update)."""
    b = out_ref.shape[0]
    IB, MB = _CHOL_IB, _CHOL_MB
    f32 = jnp.float32
    hp = jax.lax.Precision.HIGHEST
    nt_dims = (((1,), (1,)), ((), ()))   # X @ Y^T

    rII = jax.lax.broadcasted_iota(jnp.int32, (IB, IB), 0)
    cII = jax.lax.broadcasted_iota(jnp.int32, (IB, IB), 1)
    eye_II = (rII == cII).astype(f32)
    rIM = jax.lax.broadcasted_iota(jnp.int32, (IB, MB), 0)
    cIM = jax.lax.broadcasted_iota(jnp.int32, (IB, MB), 1)
    rMM = jax.lax.broadcasted_iota(jnp.int32, (MB, MB), 0)
    cMM = jax.lax.broadcasted_iota(jnp.int32, (MB, MB), 1)
    eye_MM = (rMM == cMM).astype(f32)
    rbI = jax.lax.broadcasted_iota(jnp.int32, (b, IB), 0)
    cbI = jax.lax.broadcasted_iota(jnp.int32, (b, IB), 1)

    out_ref[:] = a_ref[:]
    for jb in range(b // IB):
        j0 = jb * IB
        pan = out_ref[:, j0:j0 + IB]                     # (b, IB)
        if jb:
            left = out_ref[:, :j0]                       # (b, j0)
            top = out_ref[j0:j0 + IB, :j0]               # (IB, j0)
            pan = pan - jax.lax.dot_general(
                left, top, nt_dims, precision=hp,
                preferred_element_type=f32)
        for mb in range(IB // MB):
            m0 = mb * MB
            if mb:
                # left-looking within the panel: lanes [m0, m0+MB)
                # minus pan[:, :m0] @ D[m0:m0+MB, :m0]^T, expressed as
                # one full-width masked dot (M holds those D rows,
                # zero elsewhere, so the product lands in-place)
                D = pan[j0:j0 + IB, :]                   # (IB, IB)
                M = jnp.where((rII >= m0) & (rII < m0 + MB) & (cII < m0),
                              D, 0.0)
                pan = pan - jax.lax.dot_general(
                    jnp.where(cbI < m0, pan, 0.0), M, nt_dims,
                    precision=hp, preferred_element_type=f32)
            d = pan[j0 + m0:j0 + m0 + MB, m0:m0 + MB]    # (MB, MB)
            l = _chol_cols_unrolled(d, MB)
            linv = _trtri_cols_unrolled(l, MB)
            # X = I + sel (linv − I) selᵀ ; pan ← pan · Xᵀ applies the
            # micro trsm to lanes [m0, m0+MB) of every row: diagonal
            # micro rows become l (D·L⁻ᵀ = L), rows below become the
            # solved sub-panel, rows above transform masked-off junk
            sel = ((rIM == cIM + m0)).astype(f32)        # (IB, MB)
            placed = jax.lax.dot_general(
                jax.lax.dot_general(sel, linv - eye_MM,
                                    (((1,), (0,)), ((), ())),
                                    precision=hp,
                                    preferred_element_type=f32),
                sel, nt_dims, precision=hp, preferred_element_type=f32)
            pan = jax.lax.dot_general(
                pan, eye_II + placed, nt_dims, precision=hp,
                preferred_element_type=f32)
        # tril-mask this panel at write time — a full-(b,b) mask at the
        # end would need two b² int32 iotas (8 MiB at b=1024: VMEM OOM)
        out_ref[:, j0:j0 + IB] = jnp.where(rbI >= cbI + j0, pan, 0.0)


def _panel_gate(dtype, shape_ok: bool) -> bool:
    """Shared eligibility gate for the in-VMEM factor kernels: real f32
    only, caller's shape predicate, and (last, so CPU-host tests
    exercise the rest) a real-TPU backend check. A backend that fails
    to initialise raises here: it is an error, not "no TPU"."""
    if dtype not in (jnp.float32.dtype, np.dtype("float32")):
        return False
    if not shape_ok:
        return False
    return jax.default_backend() == "tpu"


def chol_eligible(b: int, dtype) -> bool:
    """Kernel gate: TPU backend, real f32, lane-aligned size that fits
    VMEM (b=1024 is 2 x 4 MiB in+out). The kernel is the tile factor
    on TPU: it replaces dispatch latency, not XLA's gemms, so it wins
    by construction (measured on-chip before being made default).

    The iterative outer loop owns every nt ≤ 64 size
    (linalg/cholesky.py::_potrf_blocked), so this kernel is the
    diagonal base at EVERY panel step; likewise the LU and QR panel
    kernels below for getrf/geqrf."""
    return _panel_gate(
        dtype, b >= _CHOL_IB and b % _CHOL_IB == 0 and b <= 1024)


@functools.partial(jax.jit, static_argnames=("interpret",))
def chol_tile(a: jax.Array, *, interpret: bool = False) -> jax.Array:
    """Cholesky of one (b, b) tile as ONE Pallas kernel (lower factor,
    strict upper zeroed). Caller is responsible for eligibility."""
    b = a.shape[0]
    return pl.pallas_call(
        _chol_tile_kernel,
        out_shape=jax.ShapeDtypeStruct((b, b), a.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
    )(a)


# ---------------------------------------------------------------------------
# In-VMEM pivoted LU panel base (round 5)
# ---------------------------------------------------------------------------
#
# getrf's floor after the round-5 dispatch fix is the panel chain:
# each (H, 32) fori_loop base (blocked._panel_getrf_base) pays ~30
# XLA-op dispatches per column; a 16384-column factorization runs
# ~512 such bases. This kernel runs one whole base as ONE Mosaic
# program: the column loop is statically unrolled, the pivot search
# is an in-kernel argmax, and the row swaps are dynamic-sublane ref
# writes (no masked full-panel passes). Reference analog: the
# multi-threaded panel of src/internal/internal_getrf.cc:64-119 /
# Tile_getrf.hh:209-270 — one tight kernel owning the whole chain
# instead of per-column task/MPI hops.

# VMEM budget for the panel-base kernels in f32 cells. Measured
# on-chip (round 5): Mosaic's scoped-vmem accounting charges ~8× the
# (H, W) panel for the loop body's live temporaries — at H=16384 w=32
# the QR kernel needs 25.3 MiB standalone and the LU kernel 16.12 MiB
# inside the full getrf program, both over the 16 MiB scoped limit
# (the margin shrinks inside larger programs). H=8192 compiles in
# ~2.5 s and runs with headroom, so the budget is 8192·32 cells;
# taller bases fall back to the XLA fori base.
_PANEL_MAX_CELLS = 8192 * 32
# The 16 MiB is Mosaic's default scoped limit, not the chip's: v5e has
# 128 MiB of VMEM. Inside a geqrf program (gels 8192×2048, nb=512,
# AOT-compiled for v5e in PR 21) a (7968, 32) QR base asked for
# 16.29 MiB, so the panel kernels raise their limit to 64 MiB — four
# times what a budget-sized base was seen to take.
_PANEL_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 2 ** 20)


def _lu_panel_kernel(a_ref, lu_ref, perm_ref, info_ref):
    # The column loop is a lax.fori_loop, NOT Python-unrolled: each
    # call site embeds the serialized Mosaic module in the parent HLO,
    # and getrf(n=16384) has ~512 panel-base sites — unrolled bodies
    # pushed the program to 8 MB of MLIR and the remote compile helper
    # was OOM-killed (round-5 measurement). Dynamic-j lane access is
    # expressed as masked full-panel selects/reductions (Mosaic has no
    # dynamic lane slicing); the panel is VMEM-resident so the extra
    # (H, W) traffic per step is noise.
    H, W = a_ref.shape
    f32 = jnp.float32
    rH1 = jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0)
    cW1 = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)

    lu_ref[:] = a_ref[:]
    perm_ref[:] = rH1
    info_ref[0, 0] = jnp.int32(0)

    def body(j, carry):
        cur = lu_ref[:]
        col = jnp.sum(jnp.where(cW1 == j, cur, 0.0), axis=1,
                      keepdims=True)                     # (H, 1)
        score = jnp.where(rH1 >= j, jnp.abs(col), -1.0)
        # NaN-safe pivot choice: argmax ignores NaN rows unless all
        # candidates are NaN (matching the fori base's argmax)
        p = jnp.argmax(score).astype(jnp.int32)
        row_j = lu_ref[pl.ds(j, 1), :]
        row_p = lu_ref[pl.ds(p, 1), :]
        lu_ref[pl.ds(p, 1), :] = row_j
        lu_ref[pl.ds(j, 1), :] = row_p
        pj = perm_ref[pl.ds(j, 1), :]
        pp = perm_ref[pl.ds(p, 1), :]
        perm_ref[pl.ds(p, 1), :] = pj
        perm_ref[pl.ds(j, 1), :] = pp
        d = jnp.sum(jnp.where(cW1 == j, row_p, 0.0))     # new pivot
        bad = jnp.isnan(jnp.abs(d)) | (jnp.abs(d) == 0)
        info_ref[0, 0] = jnp.where(
            (info_ref[0, 0] == 0) & bad, (j + 1).astype(jnp.int32),
            info_ref[0, 0])
        dsafe = jnp.where(bad, jnp.ones((), f32), d)
        cur = lu_ref[:]                                  # after swaps
        col2 = jnp.sum(jnp.where(cW1 == j, cur, 0.0), axis=1,
                       keepdims=True)
        lcol = jnp.where(rH1 > j, col2 / dsafe, col2)
        urow = jnp.where(cW1 > j, row_p, 0.0)            # pivot row
        lmask = jnp.where(rH1 > j, lcol, 0.0)
        # one fused pass: write the scaled column and apply the rank-1
        # update (lmask is zero on rows <= j and urow on cols <= j, so
        # the pivot row/column are preserved; the where writes col j)
        cur = jnp.where(cW1 == j, lcol, cur)
        lu_ref[:] = cur - lmask * urow
        return carry

    jax.lax.fori_loop(0, W, body, 0)


def lu_panel_eligible(h: int, w: int, dtype) -> bool:
    """Kernel gate: TPU f32 panel bases; the XLA fori base
    otherwise."""
    return _panel_gate(
        dtype, 8 <= w <= 128 and h % 8 == 0 and w <= h
        and h * w <= _PANEL_MAX_CELLS)


@functools.partial(jax.jit, static_argnames=("interpret",))
def lu_panel_base(a: jax.Array, *, interpret: bool = False):
    """Pivoted LU of one (H, w) panel base as ONE Pallas kernel.
    Returns (lu, perm, info) with the _panel_getrf_base contract
    (gather-semantics perm, 1-based first-zero-pivot info)."""
    hh, w = a.shape
    lu, perm, info = pl.pallas_call(
        _lu_panel_kernel,
        out_shape=(jax.ShapeDtypeStruct((hh, w), a.dtype),
                   jax.ShapeDtypeStruct((hh, 1), jnp.int32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.SMEM)),
        compiler_params=_PANEL_PARAMS,
        interpret=interpret,
    )(a)
    return lu, perm[:, 0], info[0, 0]


# ---------------------------------------------------------------------------
# In-VMEM Householder QR panel base (round 5)
# ---------------------------------------------------------------------------
#
# Same dispatch-latency analysis as the LU panel kernel: geqrf's panel
# chain runs blocked._panel_geqrf_base once per (H, 32) base — a
# w-step fori_loop whose body is ~12 XLA ops (slice, larfg scalars,
# matvec, rank-1 update, two column writes). This kernel runs the
# whole base as ONE Mosaic program with the column loop statically
# unrolled. Reference analog: the panel task of
# src/internal/internal_geqrf.cc:180-260 (one thread team owns the
# whole panel; triangle-reduce across tiles) — here the panel is one
# kernel and the cross-tile reduction is XLA's tsqr tree.

def _qr_panel_kernel(a_ref, vr_ref, tau_ref):
    # lax.fori_loop column loop, masked-select dynamic-j lane access —
    # same compile-payload rationale as _lu_panel_kernel above.
    H, W = a_ref.shape
    f32 = jnp.float32
    hp = jax.lax.Precision.HIGHEST
    rH1 = jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0)
    cW1 = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)

    vr_ref[:] = a_ref[:]

    def body(j, carry):
        cur = vr_ref[:]                                  # (H, W)
        col = jnp.sum(jnp.where(cW1 == j, cur, 0.0), axis=1,
                      keepdims=True)                     # (H, 1)
        alpha = jnp.sum(jnp.where(rH1 == j, col, 0.0))
        tail = jnp.where(rH1 > j, col, 0.0)
        sig = jnp.sum(tail * tail)
        anorm = jnp.sqrt(alpha * alpha + sig)
        beta = jnp.where(alpha <= 0, anorm, -anorm)
        # degenerate column (zero tail): tau = 0, H = I (larfg contract)
        degen = sig == 0.0
        beta_safe = jnp.where(degen | (beta == 0), jnp.ones((), f32), beta)
        denom_safe = jnp.where(degen, jnp.ones((), f32), alpha - beta)
        tau = jnp.where(degen, jnp.zeros((), f32), (beta - alpha) / beta_safe)
        scale = 1.0 / denom_safe
        v = jnp.where(rH1 > j, col * scale, 0.0)
        v = jnp.where(rH1 == j, jnp.ones((), f32), v)
        # eliminate: A ← A − τ·v·(vᵀA) on columns > j (real f32: Hᴴ = H)
        w_row = jax.lax.dot_general(
            v, cur, (((0,), (0,)), ((), ())),
            precision=hp, preferred_element_type=f32)    # (1, W)
        upd = (tau * v) * jnp.where(cW1 > j, w_row, 0.0)
        out = cur - upd
        # column j: beta on the diagonal, v's tail below, R above
        newcol = jnp.where(rH1 > j, v, col)
        newcol = jnp.where(rH1 == j, jnp.where(degen, alpha, beta), newcol)
        vr_ref[:] = jnp.where(cW1 == j, newcol, out)
        tau_ref[pl.ds(j, 1), :] = jnp.reshape(tau, (1, 1))
        return carry

    jax.lax.fori_loop(0, W, body, 0)


def qr_panel_eligible(h: int, w: int, dtype) -> bool:
    """Kernel gate: TPU f32 panel bases; the XLA fori base
    otherwise."""
    return _panel_gate(
        dtype, 8 <= w <= 128 and h % 8 == 0 and w <= h
        and h * w <= _PANEL_MAX_CELLS)


# ---------------------------------------------------------------------------
# Deeper-unrolled WIDE QR panel kernel (round 7)
# ---------------------------------------------------------------------------
#
# ISSUE 3's "deeper-unrolled fused panel base": chol_tile already
# factors a whole nb tile per invocation with three-level blocking
# (b → 128-panel → 32-micro → column); this kernel gives the QR panel
# the same structure so a 64/128-wide base runs as ONE Mosaic program
# instead of a width recursion over 32-wide bases with XLA gemm
# aggregation between them (each base call site is a kernel dispatch +
# fusion boundary; the recursion for a 128-wide panel pays 4 bases +
# ~6 aggregation gemms). Inside: the column loop is a fori PER
# 32-micro-block (compile-payload bounded — the round-5 lesson), each
# column's Householder update masked to the micro lanes only, and the
# trailing lanes of the panel get ONE compact-WY block update per
# micro-block (T from the closed form T = D·(I + striu(VᵀV)·D)⁻¹, the
# unit-triangular inverse by its nilpotent fixed point — all MXU dots,
# the in-kernel analog of ops/blocked.larft). Unlike the w ≤ 32 base
# kernel this reassociates the trailing arithmetic (deferral), so it
# is residual-tested, not bit-parity-tested, against the fori base.

_QR_WIDE_MB = 32


def _qr_wide_micro_fori(vr_ref, tau_ref, m0, H, W):
    """fori over the MB columns of micro-block at lane offset ``m0``;
    per-column Householder elimination restricted to micro lanes."""
    f32 = jnp.float32
    rH1 = jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0)
    cW1 = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
    hp = jax.lax.Precision.HIGHEST
    hi = m0 + _QR_WIDE_MB

    def body(j, carry):
        cur = vr_ref[:]
        col = jnp.sum(jnp.where(cW1 == j, cur, 0.0), axis=1,
                      keepdims=True)
        alpha = jnp.sum(jnp.where(rH1 == j, col, 0.0))
        tail = jnp.where(rH1 > j, col, 0.0)
        sig = jnp.sum(tail * tail)
        anorm = jnp.sqrt(alpha * alpha + sig)
        beta = jnp.where(alpha <= 0, anorm, -anorm)
        degen = sig == 0.0
        beta_safe = jnp.where(degen | (beta == 0), jnp.ones((), f32), beta)
        denom_safe = jnp.where(degen, jnp.ones((), f32), alpha - beta)
        tau = jnp.where(degen, jnp.zeros((), f32),
                        (beta - alpha) / beta_safe)
        scale = 1.0 / denom_safe
        v = jnp.where(rH1 > j, col * scale, 0.0)
        v = jnp.where(rH1 == j, jnp.ones((), f32), v)
        w_row = jax.lax.dot_general(
            v, cur, (((0,), (0,)), ((), ())),
            precision=hp, preferred_element_type=f32)     # (1, W)
        # update masked to THIS micro-block's later lanes only — the
        # rest of the panel is updated once per block, by compact WY
        upd = (tau * v) * jnp.where((cW1 > j) & (cW1 < hi), w_row, 0.0)
        out = cur - upd
        newcol = jnp.where(rH1 > j, v, col)
        newcol = jnp.where(rH1 == j, jnp.where(degen, alpha, beta), newcol)
        vr_ref[:] = jnp.where(cW1 == j, newcol, out)
        tau_ref[pl.ds(j, 1), :] = jnp.reshape(tau, (1, 1))
        return carry

    jax.lax.fori_loop(m0, hi, body, 0)


def _qr_panel_wide_kernel(a_ref, vr_ref, tau_ref):
    H, W = a_ref.shape
    MB = _QR_WIDE_MB
    f32 = jnp.float32
    hp = jax.lax.Precision.HIGHEST
    nt_dims = (((1,), (1,)), ((), ()))   # X @ Yᵀ
    tn_dims = (((0,), (0,)), ((), ()))   # Xᵀ @ Y

    rHW = jax.lax.broadcasted_iota(jnp.int32, (H, W), 0)
    cHW = jax.lax.broadcasted_iota(jnp.int32, (H, W), 1)
    rWW = jax.lax.broadcasted_iota(jnp.int32, (W, W), 0)
    cWW = jax.lax.broadcasted_iota(jnp.int32, (W, W), 1)
    eye_WW = (rWW == cWW).astype(f32)

    vr_ref[:] = a_ref[:]
    tau_ref[:] = jnp.zeros((W, 1), f32)
    for mb in range(W // MB):
        m0 = mb * MB
        hi = m0 + MB
        _qr_wide_micro_fori(vr_ref, tau_ref, m0, H, W)
        if hi >= W:
            break
        cur = vr_ref[:]
        micro_l = (cHW >= m0) & (cHW < hi)
        # V of this micro-block as a masked (H, W) form (unit lower)
        vm = jnp.where(micro_l & (rHW > cHW), cur, 0.0)
        vm = jnp.where(micro_l & (rHW == cHW), 1.0, vm)
        # T = D·(I + striu(VᵀV)·D)⁻¹ — inverse of the unit-upper
        # factor by its nilpotent fixed point X ← I − N·X (N strictly
        # upper within the micro block ⇒ exact after MB iterations)
        g = jax.lax.dot_general(vm, vm, tn_dims, precision=hp,
                                preferred_element_type=f32)  # (W, W)
        tau_row = jnp.transpose(tau_ref[:])                  # (1, W)
        micro_ww = ((rWW >= m0) & (rWW < hi)
                    & (cWW >= m0) & (cWW < hi))
        n_mat = jnp.where(micro_ww & (rWW < cWW), g * tau_row, 0.0)
        x = eye_WW
        for _ in range(MB):
            x = eye_WW - jax.lax.dot_general(
                n_mat, x, (((1,), (0,)), ((), ())), precision=hp,
                preferred_element_type=f32)
        # T = D·X: row-scale the inverse by tau (micro rows live only)
        t_mat = jnp.where(micro_ww, tau_ref[:] * x, 0.0)
        # one compact-WY update of the REMAINING lanes:
        # C ← C − V·(Tᵀ·(Vᵀ·C)) on lanes ≥ hi
        cmask = jnp.where(cHW >= hi, cur, 0.0)
        y = jax.lax.dot_general(vm, cmask, tn_dims, precision=hp,
                                preferred_element_type=f32)  # (W, W)
        z = jax.lax.dot_general(t_mat, y, tn_dims, precision=hp,
                                preferred_element_type=f32)
        upd = jax.lax.dot_general(vm, z, (((1,), (0,)), ((), ())),
                                  precision=hp,
                                  preferred_element_type=f32)
        vr_ref[:] = jnp.where(cHW >= hi, cur - upd, cur)


def qr_panel_wide_eligible(h: int, w: int, dtype) -> bool:
    """Gate for the wide (micro-blocked) QR panel kernel: widths past
    the w ≤ 32 base up to 128, MB-divisible, within the measured
    scoped-VMEM cells budget."""
    return _panel_gate(
        dtype, _QR_WIDE_MB < w <= 128 and w % _QR_WIDE_MB == 0
        and h % 8 == 0 and w <= h and h * w <= _PANEL_MAX_CELLS)


@functools.partial(jax.jit, static_argnames=("interpret",))
def qr_panel_base_wide(a: jax.Array, *, interpret: bool = False):
    """Householder QR of one WIDE (H, w) panel (32 < w ≤ 128) as ONE
    micro-blocked Mosaic kernel — same output contract as
    qr_panel_base. Trailing-lane updates are compact-WY per micro
    block (reassociated ⇒ tolerance-level, not bit-level, parity with
    the fori base)."""
    hh, w = a.shape
    vr, taus = pl.pallas_call(
        _qr_panel_wide_kernel,
        out_shape=(jax.ShapeDtypeStruct((hh, w), a.dtype),
                   jax.ShapeDtypeStruct((w, 1), a.dtype)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.VMEM)),
        compiler_params=_PANEL_PARAMS,
        interpret=interpret,
    )(a)
    return vr, taus[:, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def qr_panel_base(a: jax.Array, *, interpret: bool = False):
    """Householder QR of one (H, w) panel base as ONE Pallas kernel.
    Returns (vr_packed, taus) with the _panel_geqrf_base contract
    (beta on the diagonal, v tails below, R above, LAPACK taus)."""
    hh, w = a.shape
    vr, taus = pl.pallas_call(
        _qr_panel_kernel,
        out_shape=(jax.ShapeDtypeStruct((hh, w), a.dtype),
                   jax.ShapeDtypeStruct((w, 1), a.dtype)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.VMEM)),
        compiler_params=_PANEL_PARAMS,
        interpret=interpret,
    )(a)
    return vr, taus[:, 0]
