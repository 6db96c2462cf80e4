"""Parameter-sweep tester / benchmark driver.

Reference: the `tester` binary built from test/ on TestSweeper
(test/test.cc:116-260 registers ~90 routines; each test_xxx.cc declares
sweep params, runs the call bracketed by barrier'd wall time, and
reports time + model GFLOP/s + a residual self-check — SURVEY §4). The
self-checks need no ScaLAPACK reference: probabilistic residual bounds
(test/test_gemm.cc:135-279) — the property that lets our tester run
anywhere a chip is.

Error convention (matches the reference's 3·ε-scaled bounds,
test/test_gemm.cc:135-279): every routine reports a SCALED error —
residual / (ε · dimension · norms) — and passes when it is < tol.

Large-n note (round 5): rows timed at n ≥ 8192 must pass operands as
jit ARGUMENTS (see _t_gemm/_t_potrf/_t_getrf/_t_geqrf) — a
jax.jit(lambda: ...) closing over device operands embeds them as n²
constants in the compiled program (8192² of them at n=8192). The
4096-and-below rows keep the closure form
(3 by default; a handful of algorithms with genuinely looser bounds,
e.g. randomized butterfly or mixed-precision paths, declare their own
tol, visible in the table).

Usage:
    python -m slate_tpu.tester --routine gemm,posv --n 512,1024 \
        --nb 128 --p 1 --q 1 --dtype f32 [--uplo lower] [--trans n] \
        [--iters 2] [--trace out.svg]
    python -m slate_tpu.tester --list           # all registered routines
    python -m slate_tpu.tester --routine all    # run everything

Prints one table row per (routine, size) combination:
routine, dims, nb, grid, seconds, GFLOP/s, scaled error, status.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from typing import Callable, Dict, Optional

import numpy as np

DEFAULT_TOL = 3.0

# model-GFLOP formulas come from the central FLOP ledger (obs/flops.py
# — shared with runtime/session.py); `_fl(name)` is the
# tester's (m, n) signature over that table, `_fl2` adapts ad-hoc rows
from .obs.flops import tester_model as _fl

_REGISTRY: Dict[str, Callable] = {}
_TOLS: Dict[str, float] = {}


def register(name, flops=None, tol=DEFAULT_TOL):
    def deco(fn):
        _REGISTRY[name] = fn
        _TOLS[name] = tol
        fn._flops = flops or (lambda m, n: 0.0)
        return fn
    return deco


@dataclasses.dataclass
class Ctx:
    m: int
    n: int
    nb: int
    grid: object
    dtype: object
    seed: int
    iters: int
    uplo: str = "lower"
    trans: str = "n"
    # where user data starts (reference Origin::{Host,Devices,ScaLAPACK},
    # test/test.hh:24-46): "device" = jax array, "host" = numpy array,
    # "scalapack" = routed through the 2D block-cyclic local buffers
    # (interop.scalapack round-trip — the fromScaLAPACK analog)
    origin: str = "device"

    @property
    def eps(self):
        import jax.numpy as jnp
        return float(jnp.finfo(self.dtype).eps)

    @staticmethod
    def _sync(out):
        from slate_tpu.utils.timing import sync_tree
        sync_tree(out)

    def timed(self, fn):
        """Time ``fn`` warm. --iters 1 (default): one warm-timed call
        (the historical correctness-sweep behavior — fine for residual
        rows, compile+transfer-dominated as a GFLOP/s source).

        --iters K > 1: WARM-ITERATION SLOPE TIMING (round 6, VERDICT
        r5 weak #3): after the warmup call, batches of K and 2K
        back-to-back calls are each timed with ONE result fetch at the
        batch end, best of two reps each; the per-call time is the
        slope (t₂ₖ − tₖ)/K, so the one-time dispatch/sync round-trip —
        ~1 s per fetch on the remote-TPU setup of rounds 2–5, the term
        that made that setup's sweep rows ~100× below steady state —
        cancels and the GFLOP/s column is steady-state. The
        implementation is utils/timing.eager_slope_seconds."""
        from slate_tpu.utils.timing import eager_slope_seconds

        if self.iters <= 1:
            out = fn()
            self._sync(out)
            t0 = time.perf_counter()
            out = fn()
            self._sync(out)
            return out, time.perf_counter() - t0
        return eager_slope_seconds(fn, self.iters, 2 * self.iters, reps=2)

    # -- matrix builders -------------------------------------------------
    def gen(self, kind, m, n, ds=0, **kw):
        from slate_tpu.matgen import generate_matrix
        return generate_matrix(kind, m, n, self.dtype, self.seed + ds, **kw)

    def spd(self, n, ds=0):
        from slate_tpu.matgen import random_spd
        return random_spd(n, dtype=self.dtype, seed=self.seed + ds)

    def herm(self, a):
        import jax.numpy as jnp
        import slate_tpu as st
        from slate_tpu.core.types import Uplo
        a = jnp.asarray(self.origin_array(a))
        u = Uplo.Lower if self.uplo == "lower" else Uplo.Upper
        tri = jnp.tril(a) if self.uplo == "lower" else jnp.triu(a)
        return st.hermitian(tri, nb=self.nb, uplo=u, grid=self.grid)

    def origin_array(self, a):
        """Route operand VALUES per --origin: host → numpy; scalapack →
        a round-trip through TRUE 2D block-cyclic local buffers (the
        fromScaLAPACK analog, interop/scalapack.py + native packers).
        Applied by every operand builder (dense/herm/tri), so hermitian
        and triangular inputs exercise the path too."""
        if self.origin == "host":
            return np.asarray(a)
        if self.origin == "scalapack":
            import jax.numpy as jnp
            import slate_tpu as st
            from slate_tpu.interop import scalapack as sca
            # s/d/c/z all round-trip through the native packers (round 5:
            # element-size-templated layout kernels)
            an = np.asarray(a, self.dtype)
            p, q = ((self.grid.p, self.grid.q) if self.grid is not None
                    else (2, 2))
            A0 = st.from_dense(an, nb=self.nb)
            locals_ = sca.to_scalapack(A0, p, q)
            rt = sca.from_scalapack(locals_, an.shape[0], an.shape[1],
                                    self.nb, p, q)
            return jnp.asarray(rt.to_numpy(), self.dtype)
        return a

    def dense(self, a):
        import slate_tpu as st
        return st.from_dense(self.origin_array(a), nb=self.nb,
                             grid=self.grid)

    def tri(self, a, diag_boost=True):
        import jax.numpy as jnp
        import slate_tpu as st
        from slate_tpu.core.types import Uplo
        a = jnp.asarray(self.origin_array(a))
        u = Uplo.Lower if self.uplo == "lower" else Uplo.Upper
        t = jnp.tril(a) if self.uplo == "lower" else jnp.triu(a)
        if diag_boost:
            # solve-oriented operand: scale the strict triangle by 1/n
            # so rows are diagonally dominant and ‖T⁻¹‖ = O(1). A raw
            # random triangle's inverse grows exponentially with n —
            # the forward solution overflows f32 around n=4096
            # (measured: on-chip trsm/trtri sweep rows went NaN); the
            # reference's testers control cond the same way
            # (test/matrix_utils.hh diag-dominant generators).
            t = t / t.shape[0]
            idx = jnp.arange(t.shape[0])
            t = t.at[idx, idx].set(2.0 + jnp.abs(t[idx, idx]))
        return st.triangular(t, nb=self.nb, uplo=u, grid=self.grid)


def _np64(v):
    """Promote to f64/c128 without discarding imaginary parts."""
    v = np.asarray(v)
    return v.astype(np.complex128 if np.iscomplexobj(v) else np.float64)


def _rel(err_norm, scale):
    return float(err_norm / max(scale, 1e-300))


def _solve_err(ctx, a, x, b):
    """LAPACK-style scaled backward error ‖b−Ax‖/(ε·n·‖A‖·‖x‖)."""
    a, x, b = (_np64(v) for v in (a, x, b))
    num = np.linalg.norm(b - a @ x, 1)
    den = ctx.eps * a.shape[1] * np.linalg.norm(a, 1) * max(
        np.linalg.norm(x, 1), 1e-300)
    return _rel(num, den)


# growth-bound machinery: promoted to obs/numerics.py (round 16 —
# the serving runtime's factor-time health signals and ROADMAP item
# 2's update-vs-refactor bound read the SAME formulas), re-imported
# here so the ~30 tester call sites keep their historical names.
from slate_tpu.obs.numerics import (  # noqa: E402
    aasen_growth as _aasen_growth, chol_growth as _chol_growth,
    lu_growth as _lu_growth, lu_growth_arr as _lu_growth_arr)


def _mixed_factor_dtype(ctx):
    """One tier below the sweep's working dtype (the refine/policy
    ladder: f32→bf16, f64→f32, c128→c64) so the mixed rows exercise a
    GENUINELY lower factor precision. None where no lower precision
    exists (c64) — the eager rows then keep the drivers' historical
    default, the batched rows pass the working dtype explicitly (the
    trivial path), and the growth scale collapses to 1."""
    from slate_tpu.refine import default_factor_dtype, jax_dtype
    lo = default_factor_dtype(ctx.dtype)
    return jax_dtype(lo) if lo is not None else None


def _prod_err(ctx, got, ref, lhs, rhs):
    """LAPACK-style product bound ‖got−ref‖/(ε·k·‖lhs‖·‖rhs‖) — the
    test_gemm.cc-family denominator. Scaling by ‖ref‖ instead (the
    pre-round-5 formula) inflates the scaled error by the cancellation
    factor ‖lhs‖‖rhs‖/‖ref‖ ≈ √k for random operands, which pushed
    on-chip f32 rows over tol=3 at n=4096 with a correct result."""
    lhs, rhs = _np64(lhs), _np64(rhs)
    den = ctx.eps * lhs.shape[1] * max(
        np.linalg.norm(lhs, 1) * np.linalg.norm(rhs, 1), 1e-300)
    return _rel(np.linalg.norm(_np64(got) - _np64(ref), 1), den)


# -- BLAS-3 -----------------------------------------------------------------

@register("gemm", flops=_fl("gemm"))
def _t_gemm(ctx):
    import slate_tpu as st
    import jax
    m, n = ctx.m, ctx.n
    a = ctx.gen("randn", m, n)
    b = ctx.gen("randn", n, m, 1)
    A, B = ctx.dense(a), ctx.dense(b)
    if ctx.trans in ("t", "c"):
        A = A.T if ctx.trans == "t" else A.H
        B = B.T if ctx.trans == "t" else B.H
        an, bn = np.asarray(a).T, np.asarray(b).T
        if ctx.trans == "c":
            an, bn = an.conj(), bn.conj()
        C0 = st.zeros(m, m, ctx.nb, ctx.dtype, grid=ctx.grid)
        fn = jax.jit(lambda B_, A_, C_: st.gemm(1.0, B_, A_, 0.0, C_))
        out, secs = ctx.timed(lambda: fn(B, A, C0))
        ref_l, ref_r = bn, an
    else:
        C0 = st.zeros(m, m, ctx.nb, ctx.dtype, grid=ctx.grid)
        fn = jax.jit(lambda A_, B_, C_: st.gemm(1.0, A_, B_, 0.0, C_))
        out, secs = ctx.timed(lambda: fn(A, B, C0))
        ref_l, ref_r = np.asarray(a), np.asarray(b)
    x = _np64(ctx.gen("rands", ref_r.shape[1], 8, 2))
    lhs = np.asarray(out.to_numpy(), np.complex128 if np.iscomplexobj(ref_l)
                     else np.float64) @ x
    rhs = ref_l @ (ref_r @ x)
    err = _rel(np.linalg.norm(lhs - rhs, 1),
               ctx.eps * ctx.n * np.linalg.norm(rhs, 1))
    return secs, err


@register("symm", flops=_fl("symm"))
def _t_symm(ctx):
    import slate_tpu as st
    import jax
    import jax.numpy as jnp
    from slate_tpu.core.types import Side, Uplo
    n = ctx.n
    a = ctx.gen("randn", n, n)
    a = 0.5 * (a + a.T)
    b = ctx.gen("randn", n, n, 1)
    u = Uplo.Lower if ctx.uplo == "lower" else Uplo.Upper
    A = st.symmetric(jnp.tril(a) if ctx.uplo == "lower" else jnp.triu(a),
                     nb=ctx.nb, uplo=u, grid=ctx.grid)
    B = ctx.dense(b)
    C = st.zeros(n, n, ctx.nb, ctx.dtype, grid=ctx.grid)
    out, secs = ctx.timed(
        jax.jit(lambda: st.symm(Side.Left, 1.0, A, B, 0.0, C)))
    ref = _np64(a) @ _np64(b)
    err = _prod_err(ctx, out.to_numpy(), ref, a, b)
    return secs, err


@register("hemm", flops=_fl("hemm"))
def _t_hemm(ctx):
    import slate_tpu as st
    import jax
    import jax.numpy as jnp
    from slate_tpu.core.types import Side
    n = ctx.n
    a = ctx.gen("randn", n, n)
    a = 0.5 * (a + jnp.conj(a).T)  # Hermitian, not merely symmetric
    A = ctx.herm(a)
    b = ctx.gen("randn", n, n, 1)
    B = ctx.dense(b)
    C = st.zeros(n, n, ctx.nb, ctx.dtype, grid=ctx.grid)
    out, secs = ctx.timed(
        jax.jit(lambda: st.hemm(Side.Left, 1.0, A, B, 0.0, C)))
    ref = _np64(a) @ _np64(b)
    err = _prod_err(ctx, out.to_numpy(), ref, a, b)
    return secs, err


def _rank_k(ctx, routine):
    import slate_tpu as st
    import jax
    import jax.numpy as jnp
    from slate_tpu.core.types import Uplo
    n = ctx.n
    a = ctx.gen("randn", n, n)
    u = Uplo.Lower if ctx.uplo == "lower" else Uplo.Upper
    kind = st.symmetric if routine.startswith("sy") else st.hermitian
    C = kind(jnp.zeros((n, n), ctx.dtype), nb=ctx.nb, uplo=u, grid=ctx.grid)
    A = ctx.dense(a)
    he = routine.startswith("he")
    tr = (lambda x: x.conj().T) if he else (lambda x: x.T)
    if routine in ("syrk", "herk"):
        fn = getattr(st, routine)
        out, secs = ctx.timed(jax.jit(lambda: fn(1.0, A, 0.0, C)))
        ref = _np64(a) @ tr(_np64(a))
    else:
        b = ctx.gen("randn", n, n, 1)
        B = ctx.dense(b)
        fn = getattr(st, routine)
        out, secs = ctx.timed(jax.jit(lambda: fn(1.0, A, B, 0.0, C)))
        an, bn = _np64(a), _np64(b)
        ref = an @ tr(bn) + bn @ tr(an)
    got = np.asarray(out.full_dense_canonical())[:n, :n]
    other = a if routine in ("syrk", "herk") else b
    err = _prod_err(ctx, got, ref, a, other)
    return secs, err


for _r in ("syrk", "herk"):
    register(_r, flops=_fl("syrk"))(
        lambda ctx, _r=_r: _rank_k(ctx, _r))
for _r in ("syr2k", "her2k"):
    register(_r, flops=_fl("syr2k"))(
        lambda ctx, _r=_r: _rank_k(ctx, _r))


@register("trmm", flops=_fl("trmm"))
def _t_trmm(ctx):
    import slate_tpu as st
    import jax
    from slate_tpu.core.types import Side
    n = ctx.n
    L = ctx.tri(ctx.gen("randn", n, n), diag_boost=False)
    b = ctx.gen("randn", n, n, 1)
    B = ctx.dense(b)
    out, secs = ctx.timed(jax.jit(lambda: st.trmm(Side.Left, 1.0, L, B)))
    lref = _np64(L.full_dense_canonical())[:n, :n]
    ref = lref @ _np64(b)
    err = _prod_err(ctx, out.to_numpy(), ref, lref, b)
    return secs, err


@register("trsm", flops=_fl("trsm"))
def _t_trsm(ctx):
    import slate_tpu as st
    import jax
    from slate_tpu.core.types import Side
    n = ctx.n
    L = ctx.tri(ctx.gen("randn", n, n))
    b = ctx.gen("randn", n, n, 1)
    B = ctx.dense(b)
    out, secs = ctx.timed(jax.jit(lambda: st.trsm(Side.Left, 1.0, L, B)))
    lref = _np64(L.full_dense_canonical())[:n, :n]
    err = _solve_err(ctx, lref, out.to_numpy(), np.asarray(b))
    return secs, err


@register("trtri", flops=_fl("trtri"))
def _t_trtri(ctx):
    import slate_tpu as st
    import jax
    n = ctx.n
    L = ctx.tri(ctx.gen("randn", n, n))
    out, secs = ctx.timed(jax.jit(lambda: st.trtri(L)))
    lref = _np64(L.full_dense_canonical())[:n, :n]
    got = _np64(out.full_dense_canonical())[:n, :n]
    err = _rel(np.linalg.norm(lref @ got - np.eye(n), 1), ctx.eps * n *
               np.linalg.norm(lref, 1) * np.linalg.norm(got, 1))
    return secs, err


# -- norms ------------------------------------------------------------------

def _norm_case(ctx, kind_name):
    import slate_tpu as st
    import jax
    from slate_tpu.core.types import Norm
    n = ctx.n
    a = ctx.gen("randn", ctx.m, n)
    if kind_name == "henorm":
        a = 0.5 * (a + a.T)
        A = ctx.herm(a)
        an = np.asarray(A.full_dense_canonical())[:n, :n]
    elif kind_name == "trnorm":
        A = ctx.tri(a, diag_boost=False)
        an = np.asarray(A.full_dense_canonical())[:ctx.m, :n]
    else:
        A = ctx.dense(a)
        an = np.asarray(a)
    errs = []
    secs = 0.0
    for norm_kind, ref in ((Norm.One, lambda x: np.linalg.norm(x, 1)),
                           (Norm.Inf, lambda x: np.linalg.norm(x, np.inf)),
                           (Norm.Fro, lambda x: np.linalg.norm(x, "fro")),
                           (Norm.Max, lambda x: np.abs(x).max())):
        out, s = ctx.timed(jax.jit(lambda nk=norm_kind: st.norm(A, nk)))
        secs += s
        r = ref(_np64(an))
        errs.append(_rel(abs(float(out) - r), ctx.eps * n * max(r, 1e-300)))
    return secs, max(errs)


for _r in ("genorm", "henorm", "trnorm"):
    register(_r)(lambda ctx, _r=_r: _norm_case(ctx, _r))


# -- Cholesky family --------------------------------------------------------

@register("potrf", flops=_fl("potrf"))
def _t_potrf(ctx):
    import slate_tpu as st
    import jax
    n = ctx.n
    a = ctx.spd(n)
    A = ctx.herm(a)
    fn = jax.jit(lambda A_: st.potrf(A_)[0])
    out, secs = ctx.timed(lambda: fn(A))
    f = _np64(out.full_dense_canonical())[:n, :n]
    if ctx.uplo == "lower":
        rec = np.tril(f) @ np.tril(f).conj().T
    else:
        rec = np.triu(f).conj().T @ np.triu(f)
    an = _np64(a)
    err = _rel(np.linalg.norm(an - rec, 1),
               ctx.eps * n * np.linalg.norm(an, 1))
    return secs, err


@register("posv", flops=_fl("posv"))
def _t_posv(ctx):
    import slate_tpu as st
    import jax
    n = ctx.n
    a = ctx.spd(n)
    A = ctx.herm(a)
    b = ctx.gen("randn", n, 8, 1)
    B = ctx.dense(b)
    out, secs = ctx.timed(jax.jit(lambda: st.posv(A, B)[0]))
    return secs, _solve_err(ctx, a, out.to_numpy(), b)


@register("potri", flops=_fl("potri"))
def _t_potri(ctx):
    import slate_tpu as st
    import jax
    n = ctx.n
    a = ctx.spd(n)
    A = ctx.herm(a)
    L, _ = st.potrf(A)
    out, secs = ctx.timed(jax.jit(lambda: st.potri(L)))
    got = _np64(out.full_dense_canonical())[:n, :n]
    an = _np64(a)
    err = _rel(np.linalg.norm(an @ got - np.eye(n), 1), ctx.eps * n *
               np.linalg.norm(an, 1) * np.linalg.norm(got, 1))
    return secs, err


def _posv_mixed_case(ctx, solver, k=2):
    """Shared mixed-Cholesky row body: factor one tier below the sweep
    dtype (_mixed_factor_dtype), bound growth-scaled by the
    LOW-precision factor's ‖L‖‖Lᴴ‖/‖A‖ (round 13 — the flat tol=30
    bound kept the mixed rows blind to the factor-precision loss the
    refinement must recover; now a refinement regression cannot hide
    behind the denominator)."""
    import slate_tpu as st
    n = ctx.n
    a = ctx.spd(n)
    A = ctx.herm(a)
    b = ctx.gen("randn", n, k, 1)
    B = ctx.dense(b)
    fd = _mixed_factor_dtype(ctx)
    kw = {} if fd is None else {"factor_dtype": fd}
    (X, info, iters), secs = ctx.timed(lambda: solver(st, A, B, **kw))
    growth = 1.0
    if fd is not None:
        from slate_tpu.linalg import elementwise as _ew
        L_lo, info_lo = st.potrf(_ew.copy(A, dtype=fd))
        if int(info_lo) == 0:
            growth = _chol_growth(L_lo, a)
    return secs, _solve_err(ctx, a, X.to_numpy(), b) / growth


register("posv_mixed", flops=_fl("posv_mixed"), tol=30)(
    lambda ctx: _posv_mixed_case(
        ctx, lambda st, A, B, **kw: st.posv_mixed(A, B, **kw)))
register("posv_mixed_gmres", flops=_fl("posv_mixed_gmres"), tol=30)(
    lambda ctx: _posv_mixed_case(
        ctx, lambda st, A, B, **kw: st.posv_mixed_gmres(A, B, **kw),
        k=1))


@register("posv_mixed_batched", flops=_fl("posv_mixed_batched"), tol=30)
def _t_posv_mixed_batched(ctx):
    """Round 13: the batched mixed engine — a B=4 SPD stack through
    ONE bucket program (lo Cholesky + per-item-masked IR,
    refine/engine.batched_ir_loop); worst per-item error, each
    growth-scaled by its own low-precision factor."""
    import slate_tpu as st
    from slate_tpu.linalg import batched as lb
    n = ctx.n
    bsz = 4
    a = np.stack([np.asarray(ctx.spd(n, ds=i)) for i in range(bsz)])
    b = np.stack([np.asarray(ctx.gen("randn", n, 2, 10 + i))
                  for i in range(bsz)])
    fd = _mixed_factor_dtype(ctx)
    # no lower dtype on the ladder (c64/bf16 sweeps): pass the working
    # dtype explicitly — the batched verbs' ladder default would raise
    # by design, and lo == working is the exact trivial path
    kw = {"factor_dtype": fd if fd is not None else ctx.dtype}
    (X, info, iters), secs = ctx.timed(
        lambda: st.posv_mixed_batched(a, b, **kw))
    x = np.asarray(X)
    l_lo, _ = lb.potrf_mixed_batched(a, fd if fd is not None
                                     else ctx.dtype)
    errs = []
    for i in range(bsz):
        growth = _chol_growth(np.asarray(l_lo[i]), a[i])
        errs.append(_solve_err(ctx, a[i], x[i], b[i]) / growth)
    return secs, max(errs)


@register("posv_mixed_served", flops=_fl("posv_mixed_served"), tol=30)
def _t_posv_mixed_served(ctx):
    """Round 13: the mixed SERVING path — a Session keeps the
    low-precision Cholesky resident (refine/) and refines each solve
    to working accuracy; the timed call is one warm served solve.
    Growth-scaled like every mixed row."""
    import slate_tpu as st
    from slate_tpu.refine import RefinePolicy, default_factor_dtype
    from slate_tpu.runtime import Session
    n = ctx.n
    a = ctx.spd(n)
    A = ctx.herm(a)
    b = np.asarray(ctx.gen("randn", n, 2, 1))
    lo = default_factor_dtype(ctx.dtype)
    sess = Session()
    h = sess.register(
        A, op="chol",
        refine=RefinePolicy(factor_dtype=lo) if lo else None)
    sess.warmup(h, nrhs=2)
    x, secs = ctx.timed(lambda: sess.solve(h, b))
    growth = 1.0
    if lo is not None:
        from slate_tpu.linalg import elementwise as _ew
        from slate_tpu.refine import jax_dtype
        L_lo, info_lo = st.potrf(_ew.copy(A, dtype=jax_dtype(lo)))
        if int(info_lo) == 0:
            growth = _chol_growth(L_lo, a)
    return secs, _solve_err(ctx, a, x, b) / growth


# -- LU family --------------------------------------------------------------

@register("getrf", flops=_fl("getrf"))
def _t_getrf(ctx):
    import slate_tpu as st
    import jax
    n = ctx.n
    a = ctx.gen("randn", n, n)
    A = ctx.dense(a)
    fn = jax.jit(st.getrf)
    (LU, perm, info), secs = ctx.timed(lambda: fn(A))
    lu = _np64(LU.dense_canonical())
    npad = lu.shape[0]
    l = np.tril(lu, -1) + np.eye(npad)
    u = np.triu(lu)
    pa = _np64(A.dense_canonical())[np.asarray(perm)]
    # backward bound with the pivot-growth factor: |PA - LU| <=
    # c*eps*n*|L||U| (scaling by |A| alone fails correct f32 results
    # at n=4096 where growth ~ n^(2/3) pushes the ratio past tol)
    err = _rel(np.linalg.norm(pa - l @ u, 1),
               ctx.eps * n * np.linalg.norm(l, 1) * np.linalg.norm(u, 1))
    return secs, err


def _lu_solver_case(ctx, solver, **kw):
    import slate_tpu as st
    n = ctx.n
    a = ctx.gen("randn", n, n)
    A = ctx.dense(a)
    b = ctx.gen("randn", n, 8, 1)
    B = ctx.dense(b)
    out, secs = ctx.timed(lambda: solver(st, A, B, **kw))
    return secs, _solve_err(ctx, a, out.to_numpy(), b)


register("gesv", flops=_fl("gesv"))(
    lambda ctx: _lu_solver_case(ctx, lambda st, A, B: st.gesv(A, B)[0]))
@register("gesv_nopiv", flops=_fl("gesv_nopiv"), tol=30)
def _t_gesv_nopiv(ctx):
    """No pivoting on a random matrix: growth is unbounded by design,
    so the residual is normalized by the REALIZED growth ‖L‖‖U‖/‖A‖
    (_lu_growth) rather than hidden behind the old flat tol=1e4. The
    timed call is the factor+solve composition gesv_nopiv itself runs,
    returning the factor so growth needs no second factorization."""
    import jax.numpy as jnp
    import slate_tpu as st
    n = ctx.n
    a = ctx.gen("randn", n, n)
    A = ctx.dense(a)
    b = ctx.gen("randn", n, 8, 1)
    B = ctx.dense(b)

    def solve():
        # gesv_nopiv's own composition (linalg/lu.py), factor kept
        LU, info = st.getrf_nopiv(A)
        X = st.getrs(LU, jnp.arange(LU.mt * LU.nb, dtype=jnp.int32), B)
        return X, LU

    (X, LU), secs = ctx.timed(solve)
    err = _solve_err(ctx, a, X.to_numpy(), b) / _lu_growth(LU, a)
    return secs, err
register("gesv_rbt", flops=_fl("gesv_rbt"), tol=30)(
    lambda ctx: _lu_solver_case(
        ctx, lambda st, A, B: st.gesv_rbt(A, B)[0]))
def _gesv_calu(st, A, B):
    from slate_tpu.core.types import MethodLU, Options
    return st.gesv(A, B, Options(method_lu=MethodLU.CALU))[0]


register("gesv_tntpiv", flops=_fl("gesv_tntpiv"))(
    lambda ctx: _lu_solver_case(ctx, _gesv_calu))
def _gesv_mixed_case(ctx, solver):
    """Shared mixed-LU row body: one-tier-down factor dtype, bound
    growth-scaled by the LOW-precision factor's ‖L‖‖U‖/‖A‖ (round 13 —
    see _posv_mixed_case)."""
    import slate_tpu as st
    n = ctx.n
    a = ctx.gen("randn", n, n)
    A = ctx.dense(a)
    b = ctx.gen("randn", n, 8, 1)
    B = ctx.dense(b)
    fd = _mixed_factor_dtype(ctx)
    kw = {} if fd is None else {"factor_dtype": fd}
    (X, info, iters), secs = ctx.timed(lambda: solver(st, A, B, **kw))
    growth = 1.0
    if fd is not None:
        from slate_tpu.linalg import elementwise as _ew
        LU_lo, _, info_lo = st.getrf(_ew.copy(A, dtype=fd))
        if int(info_lo) == 0:
            growth = _lu_growth(LU_lo, a)
    return secs, _solve_err(ctx, a, X.to_numpy(), b) / growth


register("gesv_mixed", flops=_fl("gesv_mixed"), tol=30)(
    lambda ctx: _gesv_mixed_case(
        ctx, lambda st, A, B, **kw: st.gesv_mixed(A, B, **kw)))
register("gesv_mixed_gmres", flops=_fl("gesv_mixed_gmres"), tol=30)(
    lambda ctx: _gesv_mixed_case(
        ctx, lambda st, A, B, **kw: st.gesv_mixed_gmres(A, B, **kw)))


@register("gesv_mixed_batched", flops=_fl("gesv_mixed_batched"), tol=30)
def _t_gesv_mixed_batched(ctx):
    """Round 13: batched mixed LU — a B=4 diagonally-boosted stack
    through ONE bucket program (lo LU + per-item-masked IR); worst
    per-item error, growth-scaled per item."""
    import slate_tpu as st
    from slate_tpu.linalg import batched as lb
    n = ctx.n
    bsz = 4
    a = np.stack([np.asarray(ctx.gen("randn", n, n, i))
                  for i in range(bsz)])
    a = a + n * np.eye(n, dtype=a.dtype)
    b = np.stack([np.asarray(ctx.gen("randn", n, 2, 10 + i))
                  for i in range(bsz)])
    fd = _mixed_factor_dtype(ctx)
    # ladder-less sweeps (c64/bf16): explicit working-dtype factor —
    # the verbs' ladder default raises by design (see _posv sibling)
    kw = {"factor_dtype": fd if fd is not None else ctx.dtype}
    (X, info, iters), secs = ctx.timed(
        lambda: st.gesv_mixed_batched(a, b, **kw))
    x = np.asarray(X)
    lu_lo, _, _ = lb.getrf_mixed_batched(a, fd if fd is not None
                                         else ctx.dtype)
    errs = []
    for i in range(bsz):
        growth = _lu_growth_arr(np.asarray(lu_lo[i]), a[i])
        errs.append(_solve_err(ctx, a[i], x[i], b[i]) / growth)
    return secs, max(errs)


@register("gesv_mixed_served", flops=_fl("gesv_mixed_served"), tol=30)
def _t_gesv_mixed_served(ctx):
    """Round 13: the mixed LU SERVING path (Session + refine/ — the
    low-precision resident refines each solve; non-convergence takes
    the counted working-precision fallback, so the row stays correct
    either way)."""
    import slate_tpu as st
    from slate_tpu.refine import RefinePolicy, default_factor_dtype
    from slate_tpu.runtime import Session
    n = ctx.n
    a = ctx.gen("randn", n, n)
    A = ctx.dense(a)
    b = np.asarray(ctx.gen("randn", n, 8, 1))
    lo = default_factor_dtype(ctx.dtype)
    sess = Session()
    h = sess.register(
        A, op="lu", refine=RefinePolicy(factor_dtype=lo) if lo else None)
    sess.warmup(h, nrhs=8)
    x, secs = ctx.timed(lambda: sess.solve(h, b))
    growth = 1.0
    if lo is not None:
        from slate_tpu.linalg import elementwise as _ew
        from slate_tpu.refine import jax_dtype
        LU_lo, _, info_lo = st.getrf(_ew.copy(A, dtype=jax_dtype(lo)))
        if int(info_lo) == 0:
            growth = _lu_growth(LU_lo, a)
    return secs, _solve_err(ctx, a, x, b) / growth


@register("getri", flops=_fl("getri"))
def _t_getri(ctx):
    import slate_tpu as st
    n = ctx.n
    a = ctx.gen("randn", n, n)
    A = ctx.dense(a)
    LU, perm, info = st.getrf(A)
    out, secs = ctx.timed(lambda: st.getri(LU, perm))
    got = _np64(out.to_numpy())[:n, :n]
    an = _np64(a)
    err = _rel(np.linalg.norm(an @ got - np.eye(n), 1), ctx.eps * n *
               np.linalg.norm(an, 1) * np.linalg.norm(got, 1))
    return secs, err


# -- QR / LS ----------------------------------------------------------------

@register("geqrf", tol=30,  # orthogonality |QᴴQ−I|/(ε·m) sits ~5-10
          flops=_fl("geqrf"))
def _t_geqrf(ctx):
    import slate_tpu as st
    import jax
    m, n = ctx.m, ctx.n
    a = ctx.gen("randn", m, n)
    A = ctx.dense(a)
    fn = jax.jit(lambda A_: st.geqrf(A_).vr)
    _, secs = ctx.timed(lambda: fn(A))
    QR = st.geqrf(A)
    q = _np64(st.qr_multiply_explicit(QR).to_numpy())
    r = np.triu(_np64(QR.r_matrix.to_numpy()))
    an = _np64(a)
    err_f = _rel(np.linalg.norm(an - q @ r, 1),
                 ctx.eps * m * np.linalg.norm(an, 1))
    err_o = _rel(np.abs(q.conj().T @ q - np.eye(q.shape[1])).max(),
                 ctx.eps * m)
    return secs, max(err_f, err_o)


@register("gelqf", tol=30,
          flops=_fl("gelqf"))
def _t_gelqf(ctx):
    import slate_tpu as st
    n = ctx.n
    a = ctx.gen("randn", n, ctx.m)
    A = ctx.dense(a)
    LQ, secs = ctx.timed(lambda: st.gelqf(A))
    # gelqf = geqrf of Aᴴ: check Aᴴ = Q·R
    q = _np64(st.qr_multiply_explicit(LQ).to_numpy())
    r = np.triu(_np64(LQ.r_matrix.to_numpy()))
    ah = _np64(a).conj().T
    err = _rel(np.linalg.norm(ah - q @ r, 1),
               ctx.eps * max(ctx.m, n) * np.linalg.norm(ah, 1))
    return secs, err


@register("cholqr", tol=30, flops=_fl("cholqr"))
def _t_cholqr(ctx):
    import slate_tpu as st
    m = max(ctx.m, 2 * ctx.n)
    n = ctx.n
    a = ctx.gen("randn", m, n)
    A = ctx.dense(a)
    (Q, R), secs = ctx.timed(lambda: st.cholqr(A))
    q = _np64(Q.to_numpy())
    r = np.triu(_np64(R.to_numpy()))
    an = _np64(a)
    err_f = _rel(np.linalg.norm(an - q @ r, 1),
                 ctx.eps * m * np.linalg.norm(an, 1))
    # CholQR orthogonality degrades as ε·κ² — use the factor check only
    return secs, err_f


@register("gels", flops=_fl("gels"))
def _t_gels(ctx):
    import slate_tpu as st
    m, n = max(ctx.m, ctx.n), ctx.n
    a = ctx.gen("randn", m, n)
    A = ctx.dense(a)
    b = ctx.gen("randn", m, 4, 1)
    B = ctx.dense(b)
    X, secs = ctx.timed(lambda: st.gels(A, B))
    x = _np64(X.to_numpy()[:n])
    an, bn = _np64(a), _np64(b)
    rr = an.conj().T @ (an @ x - bn)
    err = _rel(np.linalg.norm(rr, 1),
               ctx.eps * m * np.linalg.norm(an, 1) ** 2
               * max(np.linalg.norm(x, 1), 1e-300))
    return secs, err


# -- eigen / svd ------------------------------------------------------------

@register("heev", flops=_fl("heev"))
def _t_heev(ctx):
    import slate_tpu as st
    import jax
    n = ctx.n
    a = ctx.gen("heev_arith", n, n, cond=100.0)
    A = ctx.herm(a)
    # NO outer jit: at n >= eig._DC_MIN_N the Auto path is the
    # host-orchestrated DC driver (device-jitted stages inside) and is
    # not traceable whole — the reference's heev is likewise a host
    # task loop around device kernels
    w, secs = ctx.timed(lambda: st.heev(A, want_vectors=False)[0])
    wref = np.linalg.eigvalsh(_np64(a))
    err = _rel(np.abs(np.asarray(w) - wref).max(),
               ctx.eps * n * max(np.abs(wref).max(), 1e-300))
    return secs, err


@register("heev_2stage", flops=_fl("heev_2stage"))
def _t_heev_2stage(ctx):
    """Two-stage stage-1 (he2hb + hb2td bulge chase, round 3)."""
    import slate_tpu as st
    from slate_tpu.core.types import MethodEig, Options
    n = ctx.n
    a = ctx.gen("heev_arith", n, n, cond=100.0)
    A = ctx.herm(a)
    # heev itself falls back to he2td when n < 3·nb (the hb2td window
    # requirement) — no tester-side guard needed
    opts = Options(method_eig=MethodEig.DC, eig_stage1="two_stage")
    (w, Z), secs = ctx.timed(lambda: st.heev(A, opts))
    z = _np64(Z.to_numpy())
    wn = _np64(w)
    an = _np64(a)
    res = _rel(np.abs(an @ z - z * wn[None, :]).max(),
               ctx.eps * n * max(np.abs(wn).max(), 1e-300))
    orth = _rel(np.abs(z.conj().T @ z - np.eye(n)).max(), ctx.eps * n)
    return secs, max(res, orth)


@register("hb2td")  # no flops model: the chase's 4·n²·nb depends on nb,
                    # which the registry lambda cannot see — time-only row
def _t_hb2td(ctx):
    """Band→tridiag bulge chase invariants (eigenvalues preserved)."""
    import slate_tpu as st
    n = ctx.n
    a = ctx.gen("heev_arith", n, n, cond=100.0)
    if n < 3 * ctx.nb:
        # hb2td needs a 3-bandwidth window; re-tile small test sizes
        A = ctx.herm(a)
        A = st.hermitian(np.tril(_np64(a)), nb=max(8, n // 8),
                         uplo=A.uplo)
    else:
        A = ctx.herm(a)
    band, refl = st.he2hb(A)
    (out, secs) = ctx.timed(lambda: st.hb2td(band))
    d, e = _np64(out[0]), _np64(out[1])
    t = np.diag(d) + np.diag(e, 1) + np.diag(e, -1)
    bf = _np64(band.full_dense_canonical())
    err = _rel(np.abs(np.sort(np.linalg.eigvalsh(t))
                      - np.sort(np.linalg.eigvalsh(bf))).max(),
               ctx.eps * n * max(np.abs(bf).max(), 1e-300))
    return secs, err


@register("heev_vec", flops=_fl("heev_vec"))
def _t_heev_vec(ctx):
    import slate_tpu as st
    n = ctx.n
    a = ctx.gen("heev_arith", n, n, cond=100.0)
    A = ctx.herm(a)
    (w, Z), secs = ctx.timed(lambda: st.heev(A))
    z = _np64(Z.to_numpy())
    wn = _np64(w)
    an = _np64(a)
    res = _rel(np.abs(an @ z - z * wn).max(),
               ctx.eps * n * max(np.abs(wn).max(), 1e-300))
    orth = _rel(np.abs(z.conj().T @ z - np.eye(n)).max(), ctx.eps * n)
    return secs, max(res, orth)


@register("hegv", flops=_fl("hegv"), tol=30)
def _t_hegv(ctx):
    import slate_tpu as st
    n = ctx.n
    a = ctx.gen("heev_arith", n, n, cond=100.0)
    bsp = ctx.spd(n, 1)
    A, B = ctx.herm(a), ctx.herm(bsp)
    (w, X, info), secs = ctx.timed(lambda: st.hegv(A, B))
    x = _np64(X.to_numpy())
    wn = _np64(w)
    an = _np64(a)
    bn = _np64(bsp)
    res = _rel(np.abs(an @ x - (bn @ x) * wn).max(),
               ctx.eps * n * max(np.abs(wn).max(), 1e-300)
               * np.linalg.norm(bn, 1))
    return secs, res


@register("svd", flops=_fl("svd"))
def _t_svd(ctx):
    import slate_tpu as st
    import jax
    m, n = ctx.m, ctx.n
    a = ctx.gen("svd_geo", m, n, cond=100.0)
    A = ctx.dense(a)
    s, secs = ctx.timed(lambda: st.svd(A)[0])  # host-orchestrated (see heev)
    sref = np.linalg.svd(_np64(a), compute_uv=False)
    err = _rel(np.abs(np.asarray(s) - sref).max(),
               ctx.eps * max(m, n) * sref[0])
    return secs, err


@register("svd_vec", flops=_fl("svd_vec"))
def _t_svd_vec(ctx):
    import slate_tpu as st
    m, n = ctx.m, ctx.n
    a = ctx.gen("svd_geo", m, n, cond=100.0)
    A = ctx.dense(a)
    (s, U, V), secs = ctx.timed(lambda: st.svd(A, want_vectors=True))
    k = min(m, n)
    u = _np64(U.to_numpy())
    v = _np64(V.to_numpy())
    sn = _np64(s)
    an = _np64(a)
    rec = _rel(np.abs(u @ np.diag(sn) @ v.conj().T - an).max(),
               ctx.eps * max(m, n) * sn[0])
    orth = _rel(max(np.abs(u.conj().T @ u - np.eye(k)).max(),
                    np.abs(v.conj().T @ v - np.eye(k)).max()),
                ctx.eps * max(m, n))
    return secs, max(rec, orth)


@register("stedc")
def _t_stedc(ctx):
    from slate_tpu.linalg.stedc import stedc
    n = ctx.n
    rng = np.random.default_rng(ctx.seed)
    d, e = rng.standard_normal(n), rng.standard_normal(n - 1)
    stedc(d, e)  # warmup
    t0 = time.perf_counter()
    w, z = stedc(d, e)
    secs = time.perf_counter() - t0
    z = np.asarray(z)  # device path returns a jax.Array basis
    t = np.diag(d) + np.diag(e, 1) + np.diag(e, -1)
    # eps of the basis dtype: the device merge path runs f32 bases on
    # accelerators (f64 on CPU meshes with x64)
    epsz = np.finfo(z.dtype).eps
    res = _rel(np.abs(t @ z - z * w).max(),
               epsz * n * max(np.abs(w).max(), 1e-300))
    orth = _rel(np.abs(z.T @ z - np.eye(n)).max(), epsz * n)
    return secs, max(res, orth)


@register("steqr")
def _t_steqr(ctx):
    import slate_tpu as st
    n = min(ctx.n, 256)  # own QR iteration is host-bound; keep small
    rng = np.random.default_rng(ctx.seed)
    d, e = rng.standard_normal(n), rng.standard_normal(n - 1)
    t0 = time.perf_counter()
    w, z = st.steqr(d, e)
    secs = time.perf_counter() - t0
    t = np.diag(d) + np.diag(e, 1) + np.diag(e, -1)
    epsd = np.finfo(np.float64).eps
    res = _rel(np.abs(t @ z - z * w).max(),
               epsd * n * max(np.abs(w).max(), 1e-300))
    return secs, res


@register("bdsqr")
def _t_bdsqr(ctx):
    from slate_tpu.linalg.svd import bdsqr
    n = ctx.n
    rng = np.random.default_rng(ctx.seed)
    d, e = rng.standard_normal(n), rng.standard_normal(n - 1)
    t0 = time.perf_counter()
    s, u, vt = bdsqr(d, e, compute_uv=True)
    secs = time.perf_counter() - t0
    B = np.diag(d) + np.diag(e, 1)
    # eps of the COMPUTED dtype: bdsqr's rotations run at the backend
    # working precision (f32 when x64 is off), not the f64 inputs'
    epsd = np.finfo(np.asarray(u).dtype).eps
    res = _rel(np.abs(B @ np.asarray(vt).T - np.asarray(u)
                      * np.asarray(s)).max(),
               epsd * n * max(np.abs(np.asarray(s)).max(), 1e-300))
    return secs, res


# -- indefinite / band / condest -------------------------------------------

@register("hesv", flops=_fl("hesv"), tol=30)
def _t_hesv(ctx):
    import slate_tpu as st
    import jax.numpy as jnp
    n = ctx.n
    a = ctx.gen("randn", n, n)
    a = 0.5 * (a + jnp.conj(a).T)  # Hermitian: complex dtypes run too
    A = ctx.herm(a)
    b = ctx.gen("randn", n, 4, 1)
    B = ctx.dense(b)
    X, secs = ctx.timed(lambda: st.hesv(A, B)[0])
    # Aasen growth-scaled bound (replaces the flat tol=100 escape).
    # hesv wraps hetrf in IR/fallback logic, so the factor for the
    # growth estimate is re-derived once here, outside the timed region.
    LT, _, _ = st.hetrf(A)
    err = _solve_err(ctx, a, X.to_numpy(), b) / _aasen_growth(LT, a)
    return secs, err


@register("gbsv", flops=lambda m, n: 0.0)
def _t_gbsv(ctx):
    import slate_tpu as st
    n = ctx.n
    kl = ku = max(1, ctx.nb // 8)
    rng = np.random.default_rng(ctx.seed)
    a = np.zeros((n, n))
    for off in range(-ku, kl + 1):
        a += np.diag(rng.standard_normal(n - abs(off)), -off)
    a += (kl + ku + 3) * np.diag(np.sign(rng.standard_normal(n)))
    b = rng.standard_normal((n, 2))
    import jax.numpy as jnp
    A = st.gb_pack(jnp.asarray(a, ctx.dtype), kl, ku)
    b = jnp.asarray(b, ctx.dtype)
    (x, info), secs = ctx.timed(lambda: st.gbsv(A, b))
    return secs, _solve_err(ctx, a, np.asarray(x), b)


@register("pbsv", flops=lambda m, n: 0.0)
def _t_pbsv(ctx):
    import slate_tpu as st
    n = ctx.n
    kd = max(1, ctx.nb // 4)
    rng = np.random.default_rng(ctx.seed)
    a = np.zeros((n, n))
    for off in range(kd + 1):
        d = rng.standard_normal(n - off)
        a += np.diag(d, -off) + (np.diag(d, off) if off else 0)
    a += (2 * kd + 4) * np.eye(n)
    b = rng.standard_normal((n, 2))
    import jax.numpy as jnp
    A = st.pb_pack(jnp.asarray(a, ctx.dtype), kd)
    b = jnp.asarray(b, ctx.dtype)
    (x, info), secs = ctx.timed(lambda: st.pbsv(A, b))
    return secs, _solve_err(ctx, a, np.asarray(x), b)


def _condest_case(ctx, which):
    import slate_tpu as st
    from slate_tpu.core.types import Norm
    n = ctx.n
    if which == "pocondest":
        a = ctx.spd(n)
        A = ctx.herm(a)
        L, _ = st.potrf(A)
        est, secs = ctx.timed(lambda: st.pocondest(L, st.norm(A, Norm.One)))
    elif which == "trcondest":
        L = ctx.tri(ctx.gen("randn", n, n))
        a = np.asarray(L.full_dense_canonical())[:n, :n]
        est, secs = ctx.timed(lambda: st.trcondest(L))
    else:
        a = ctx.gen("randn", n, n)
        A = ctx.dense(a)
        LU, perm, _ = st.getrf(A)
        est, secs = ctx.timed(
            lambda: st.gecondest(LU, perm, st.norm(A, Norm.One)))
    an = _np64(a)
    true = 1.0 / (np.linalg.norm(an, 1) * np.linalg.norm(
        np.linalg.inv(an), 1))
    got = float(est)
    # Higham's estimator is within a small factor of the true value;
    # treat a 10× band as a pass (scaled to tol=3 convention: /3.3)
    ratio = max(got / max(true, 1e-300), true / max(got, 1e-300))
    return secs, ratio / 3.3


for _r in ("gecondest", "pocondest", "trcondest"):
    register(_r)(lambda ctx, _r=_r: _condest_case(ctx, _r))


# -- band BLAS-3 (gbmm/hbmm/tbsm — reference test_gbmm.cc etc.) -------------

def _band_dense(ctx, kl, ku, herm=False):
    rng = np.random.default_rng(ctx.seed)
    n = ctx.n
    a = np.zeros((n, n))
    for off in range(-ku, kl + 1):
        a += np.diag(rng.standard_normal(n - abs(off)), -off)
    if herm:
        a = 0.5 * (a + a.T)
    return a


@register("gbmm", flops=lambda m, n: 0.0)
def _t_gbmm(ctx):
    import slate_tpu as st
    import jax
    import jax.numpy as jnp
    n = ctx.n
    kl = ku = max(1, ctx.nb // 8)
    a = _band_dense(ctx, kl, ku)
    b = ctx.gen("randn", n, n, 1)
    A = st.band(jnp.asarray(a, ctx.dtype), ctx.nb, kl, ku, grid=ctx.grid)
    B = ctx.dense(b)
    C = st.zeros(n, n, ctx.nb, ctx.dtype, grid=ctx.grid)
    out, secs = ctx.timed(jax.jit(lambda: st.gbmm(1.0, A, B, 0.0, C)))
    ref = _np64(a) @ _np64(b)
    err = _prod_err(ctx, out.to_numpy(), ref, a, b)
    return secs, err


@register("hbmm", flops=lambda m, n: 0.0)
def _t_hbmm(ctx):
    import slate_tpu as st
    import jax
    import jax.numpy as jnp
    from slate_tpu.core.types import Side, Uplo
    n = ctx.n
    kd = max(1, ctx.nb // 8)
    a = _band_dense(ctx, kd, kd, herm=True)
    b = ctx.gen("randn", n, n, 1)
    A = st.hermitian_band(jnp.asarray(np.tril(a), ctx.dtype), ctx.nb, kd,
                          Uplo.Lower, grid=ctx.grid)
    B = ctx.dense(b)
    C = st.zeros(n, n, ctx.nb, ctx.dtype, grid=ctx.grid)
    out, secs = ctx.timed(
        jax.jit(lambda: st.hbmm(Side.Left, 1.0, A, B, 0.0, C)))
    ref = _np64(a) @ _np64(b)
    err = _prod_err(ctx, out.to_numpy(), ref, a, b)
    return secs, err


@register("tbsm", flops=lambda m, n: 0.0)
def _t_tbsm(ctx):
    import slate_tpu as st
    import jax
    import jax.numpy as jnp
    from slate_tpu.core.types import Side, Uplo
    n = ctx.n
    kd = max(1, ctx.nb // 8)
    # scale the band by 1/(kd+1) for diagonal dominance (see tri():
    # random triangular solves overflow f32 at n=4096 otherwise)
    a = np.tril(_band_dense(ctx, kd, 0)) / (kd + 1)
    a[np.arange(n), np.arange(n)] = 2.0 + np.abs(a.diagonal())
    b = ctx.gen("randn", n, 4, 1)
    A = st.triangular_band(jnp.asarray(a, ctx.dtype), ctx.nb, kd,
                           Uplo.Lower, grid=ctx.grid)
    B = ctx.dense(b)
    out, secs = ctx.timed(jax.jit(lambda: st.tbsm(Side.Left, 1.0, A, B)))
    return secs, _solve_err(ctx, a, out.to_numpy(), np.asarray(b))


@register("tbsm_pivots", flops=lambda m, n: 0.0)
def _t_tbsm_pivots(ctx):
    """Standalone pivoted triangular-band solve (slate::tbsm pivoted
    path): factor a general band with gbtrf, apply tbsm_pivots, then
    finish with the banded-U back-substitution and check the full
    solve residual."""
    import jax.numpy as jnp
    import slate_tpu as st
    from slate_tpu.linalg import band_packed as bp
    n = ctx.n
    kl, ku = max(1, ctx.nb // 8), max(1, ctx.nb // 16)
    a = _band_dense(ctx, kl, ku)
    a += np.diag(2.0 * kl * np.ones(n))  # well-conditioned band
    b = np.asarray(ctx.gen("randn", n, 4, 1))
    F, info = bp.gbtrf(bp.gb_pack(jnp.asarray(a, ctx.dtype), kl, ku))
    bj = jnp.asarray(b, ctx.dtype)  # device operand built off the clock
    y, secs = ctx.timed(lambda: st.tbsm_pivots(F, bj))
    x = bp._gb_backward(F.urows, jnp.asarray(y), F.urows.shape[1], F.n)
    return secs, _solve_err(ctx, a, np.asarray(x), b)


# -- elementwise / aux (reference test_add.cc, test_copy.cc, ...) -----------

@register("geadd")
def _t_geadd(ctx):
    import slate_tpu as st
    import jax
    n = ctx.n
    a, b = ctx.gen("randn", ctx.m, n), ctx.gen("randn", ctx.m, n, 1)
    A, B = ctx.dense(a), ctx.dense(b)
    out, secs = ctx.timed(jax.jit(lambda: st.add(2.5, A, -0.5, B)))
    ref = 2.5 * _np64(a) - 0.5 * _np64(b)
    err = _rel(np.abs(out.to_numpy() - ref).max(),
               ctx.eps * max(np.abs(ref).max(), 1e-300))
    return secs, err


@register("gecopy")
def _t_gecopy(ctx):
    import slate_tpu as st
    import jax.numpy as jnp
    n = ctx.n
    a = ctx.gen("randn", ctx.m, n)
    A = ctx.dense(a)
    out, secs = ctx.timed(lambda: st.copy(A, dtype=jnp.float64))
    err = _rel(np.abs(out.to_numpy() - _np64(a)).max(),
               ctx.eps * max(np.abs(np.asarray(a)).max(), 1e-300))
    return secs, err


@register("gescale")
def _t_gescale(ctx):
    import slate_tpu as st
    import jax
    n = ctx.n
    a = ctx.gen("randn", ctx.m, n)
    A = ctx.dense(a)
    out, secs = ctx.timed(jax.jit(lambda: st.scale(3.0, 2.0, A)))
    err = _rel(np.abs(out.to_numpy() - 1.5 * _np64(a)).max(),
               ctx.eps * max(np.abs(np.asarray(a)).max(), 1e-300))
    return secs, err


@register("gescale_row_col")
def _t_gescale_row_col(ctx):
    import slate_tpu as st
    import jax
    import jax.numpy as jnp
    m, n = ctx.m, ctx.n
    a = ctx.gen("randn", m, n)
    r = np.abs(np.asarray(ctx.gen("rands", m, 1, 2))).ravel() + 0.5
    c = np.abs(np.asarray(ctx.gen("rands", n, 1, 3))).ravel() + 0.5
    A = ctx.dense(a)
    R, C = jnp.asarray(r, ctx.dtype), jnp.asarray(c, ctx.dtype)
    out, secs = ctx.timed(jax.jit(lambda: st.scale_row_col(R, C, A)))
    ref = r[:, None] * _np64(a) * c[None, :]
    err = _rel(np.abs(out.to_numpy() - ref).max(),
               ctx.eps * max(np.abs(ref).max(), 1e-300))
    return secs, err


@register("geset")
def _t_geset(ctx):
    import slate_tpu as st
    import jax
    n = ctx.n
    A = ctx.dense(ctx.gen("randn", ctx.m, n))
    out, secs = ctx.timed(jax.jit(lambda: st.set_matrix(0.25, 2.0, A)))
    got = out.to_numpy()
    ref = np.full((ctx.m, n), 0.25)
    np.fill_diagonal(ref, 2.0)
    err = _rel(np.abs(got - ref).max(), ctx.eps)
    return secs, err


@register("redistribute")
def _t_redistribute(ctx):
    import slate_tpu as st
    from slate_tpu.core.grid import ProcessGrid
    n = ctx.n
    a = ctx.gen("randn", ctx.m, n)
    A = ctx.dense(a)
    # re-shard onto a different grid shape (1×1 when no grid is active —
    # still exercises the data path)
    if ctx.grid is not None and ctx.grid.size > 1:
        tgt = ProcessGrid.create(ctx.grid.q, ctx.grid.p)
    else:
        tgt = ProcessGrid.create(1, 1)
    out, secs = ctx.timed(lambda: st.redistribute(A, tgt))
    err = _rel(np.abs(out.to_numpy() - np.asarray(a)).max(), ctx.eps)
    return secs, err


# -- factor-apply stages (getrs/potrs/hetrs, unmqr/unmlq, hegst, trtrm) -----

@register("getrs", flops=lambda m, n: 2 * n * n * 8)
def _t_getrs(ctx):
    import slate_tpu as st
    n = ctx.n
    a = ctx.gen("randn", n, n)
    A = ctx.dense(a)
    LU, perm, _ = st.getrf(A)
    b = ctx.gen("randn", n, 8, 1)
    B = ctx.dense(b)
    out, secs = ctx.timed(lambda: st.getrs(LU, perm, B))
    return secs, _solve_err(ctx, a, out.to_numpy(), b)


@register("potrs", flops=lambda m, n: 2 * n * n * 8)
def _t_potrs(ctx):
    import slate_tpu as st
    n = ctx.n
    a = ctx.spd(n)
    A = ctx.herm(a)
    L, _ = st.potrf(A)
    b = ctx.gen("randn", n, 8, 1)
    B = ctx.dense(b)
    out, secs = ctx.timed(lambda: st.potrs(L, B))
    return secs, _solve_err(ctx, a, out.to_numpy(), b)


@register("hetrf", flops=_fl("hesv"), tol=30)
def _t_hetrf(ctx):
    import slate_tpu as st
    import jax.numpy as jnp
    n = ctx.n
    a = ctx.gen("randn", n, n)
    a = 0.5 * (a + jnp.conj(a).T)  # Hermitian: complex dtypes run too
    A = ctx.herm(a)
    (LT, perm, info), secs = ctx.timed(lambda: st.hetrf(A))
    b = ctx.gen("randn", n, 4, 1)
    B = ctx.dense(b)
    X = st.hetrs(LT, perm, B)
    # Aasen growth-scaled bound (replaces the flat tol=100 escape)
    err = _solve_err(ctx, a, X.to_numpy(), b) / _aasen_growth(LT, a)
    return secs, err


@register("unmqr", tol=30)
def _t_unmqr(ctx):
    import slate_tpu as st
    import jax
    from slate_tpu.core.types import Side
    m, n = max(ctx.m, ctx.n), ctx.n
    a = ctx.gen("randn", m, n)
    A = ctx.dense(a)
    QR = st.geqrf(A)
    c = ctx.gen("randn", m, 8, 1)
    C = ctx.dense(c)
    out, secs = ctx.timed(
        jax.jit(lambda: st.unmqr(Side.Left, QR, C, trans=True)))
    # QᴴC then Q·(QᴴC) must give back C (orthogonality in action)
    back = st.unmqr(Side.Left, QR, out)
    err = _rel(np.abs(back.to_numpy() - np.asarray(c)).max(),
               ctx.eps * m * max(np.abs(np.asarray(c)).max(), 1e-300))
    return secs, err


@register("unmlq", tol=30)
def _t_unmlq(ctx):
    import slate_tpu as st
    from slate_tpu.core.types import Side
    m, n = ctx.n, max(ctx.m, ctx.n)
    a = ctx.gen("randn", m, n)  # wide
    A = ctx.dense(a)
    LQ = st.gelqf(A)
    c = ctx.gen("randn", n, 4, 1)
    C = ctx.dense(c)
    out, secs = ctx.timed(lambda: st.unmlq(Side.Left, LQ, C, trans=True))
    back = st.unmlq(Side.Left, LQ, out)
    err = _rel(np.abs(back.to_numpy() - np.asarray(c)).max(),
               ctx.eps * n * max(np.abs(np.asarray(c)).max(), 1e-300))
    return secs, err


@register("hegst", tol=30)
def _t_hegst(ctx):
    import slate_tpu as st
    n = ctx.n
    a = ctx.gen("heev_arith", n, n, cond=10.0)
    bsp = ctx.spd(n, 1)
    A, B = ctx.herm(a), ctx.herm(bsp)
    L, _ = st.potrf(B)
    out, secs = ctx.timed(lambda: st.hegst(A, L))
    # check: L·Ã·Lᴴ == A
    lref = np.tril(_np64(L.full_dense_canonical()))[:n, :n]
    got = _np64(out.full_dense_canonical())[:n, :n]
    got = np.tril(got) + np.tril(got, -1).conj().T
    rec = lref @ got @ lref.conj().T
    an = _np64(a)
    an = np.tril(an) + np.tril(an, -1).conj().T if ctx.uplo == "lower" \
        else an
    err = _rel(np.abs(rec - an).max(),
               ctx.eps * n * max(np.abs(an).max(), 1e-300)
               * max(np.linalg.norm(lref, 1) ** 2, 1.0))
    return secs, err


@register("trtrm", flops=_fl("trtri"))
def _t_trtrm(ctx):
    import slate_tpu as st
    n = ctx.n
    L = ctx.tri(ctx.gen("randn", n, n))
    out, secs = ctx.timed(lambda: st.trtrm(L))
    lref = _np64(L.full_dense_canonical())[:n, :n]
    got = _np64(out.full_dense_canonical())[:n, :n]
    got = np.tril(got) + np.tril(got, -1).conj().T
    ref = lref.conj().T @ lref
    err = _rel(np.abs(got - ref).max(),
               ctx.eps * n * max(np.abs(ref).max(), 1e-300))
    return secs, err


# -- band factorizations + reductions + values-only tridiag -----------------

@register("gbtrf", flops=lambda m, n: 0.0)
def _t_gbtrf(ctx):
    import slate_tpu as st
    import jax.numpy as jnp
    n = ctx.n
    kl = ku = max(1, ctx.nb // 8)
    a = _band_dense(ctx, kl, ku)
    a += (kl + ku + 3) * np.eye(n)
    A = st.band(jnp.asarray(a, ctx.dtype), ctx.nb, kl, ku, grid=ctx.grid)
    (LU, perm, info), secs = ctx.timed(lambda: st.gbtrf(A))
    b = ctx.gen("randn", n, 2, 1)
    B = ctx.dense(b)
    X = st.gbtrs(LU, perm, B)
    return secs, _solve_err(ctx, a, X.to_numpy(), b)


@register("pbtrf", flops=lambda m, n: 0.0)
def _t_pbtrf(ctx):
    import slate_tpu as st
    import jax.numpy as jnp
    from slate_tpu.core.types import Uplo
    n = ctx.n
    kd = max(1, ctx.nb // 4)
    a = _band_dense(ctx, kd, kd, herm=True)
    a += (2 * kd + 4) * np.eye(n)
    A = st.hermitian_band(jnp.asarray(np.tril(a), ctx.dtype), ctx.nb, kd,
                          Uplo.Lower, grid=ctx.grid)
    (L, info), secs = ctx.timed(lambda: st.pbtrf(A))
    b = ctx.gen("randn", n, 2, 1)
    B = ctx.dense(b)
    X = st.pbtrs(L, B)
    return secs, _solve_err(ctx, a, X.to_numpy(), b)


@register("he2hb", tol=30)
def _t_he2hb(ctx):
    import slate_tpu as st
    n = ctx.n
    a = ctx.gen("heev_arith", n, n, cond=100.0)
    A = ctx.herm(a)
    (band, refl), secs = ctx.timed(lambda: st.he2hb(A))
    bf = _np64(band.full_dense_canonical())
    an = _np64(a)
    npad = bf.shape[0]
    if npad != n:
        # padding block is exactly decoupled; shift its diagonal past
        # the Gershgorin bound so pad eigenvalues sort strictly last
        # (same trick as eig._heev_band_dense)
        big = (2 * ctx.nb + 1) * np.abs(bf).max() + 1.0
        idx = np.arange(npad)
        bf[idx[n:], idx[n:]] = big
    werr = np.abs(np.sort(np.linalg.eigvalsh(bf))[:n]
                  - np.sort(np.linalg.eigvalsh(an))).max()
    err = _rel(werr, ctx.eps * n * max(np.abs(an).max(), 1e-300))
    return secs, err


@register("ge2tb", tol=30)
def _t_ge2tb(ctx):
    import slate_tpu as st
    m, n = max(ctx.m, ctx.n), ctx.n
    a = ctx.gen("svd_geo", m, n, cond=100.0)
    A = ctx.dense(a)
    out, secs = ctx.timed(lambda: st.ge2tb(A))
    bf = _np64(out[0])  # (mpad, npad) band array (see svd.ge2tb)
    sref = np.linalg.svd(_np64(a), compute_uv=False)
    sgot = np.linalg.svd(bf, compute_uv=False)[: sref.size]
    err = _rel(np.abs(np.sort(sgot) - np.sort(sref)).max(),
               ctx.eps * max(m, n) * max(sref[0], 1e-300))
    return secs, err


@register("sterf")
def _t_sterf(ctx):
    import slate_tpu as st
    n = ctx.n
    rng = np.random.default_rng(ctx.seed)
    d, e = rng.standard_normal(n), rng.standard_normal(n - 1)
    import jax
    import jax.numpy as jnp
    rdt = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    dj = jnp.asarray(d, rdt)
    w, secs = ctx.timed(lambda: st.sterf(dj, jnp.asarray(e, rdt)))
    t = np.diag(d) + np.diag(e, 1) + np.diag(e, -1)
    wref = np.linalg.eigvalsh(t)
    err = _rel(np.abs(np.sort(np.asarray(w)) - wref).max(),
               ctx.eps * n * max(np.abs(wref).max(), 1e-300))
    return secs, err


@register("stedc_grid")
def _t_stedc_grid(ctx):
    """stedc with the merge GEMMs sharded over the process grid
    (reference stedc is grid-distributed, src/stedc_merge.cc:98-102)."""
    from slate_tpu.linalg.stedc import stedc
    n = ctx.n
    rng = np.random.default_rng(ctx.seed)
    d, e = rng.standard_normal(n), rng.standard_normal(n - 1)
    t0 = time.perf_counter()
    w, z = stedc(d, e, use_device=True, grid=ctx.grid)
    secs = time.perf_counter() - t0
    z = np.asarray(z)
    t = np.diag(d) + np.diag(e, 1) + np.diag(e, -1)
    epsz = np.finfo(z.dtype).eps
    res = _rel(np.abs(t @ z - z * w).max(),
               epsz * n * max(np.abs(w).max(), 1e-300))
    orth = _rel(np.abs(z.T @ z - np.eye(n)).max(), epsz * n)
    return secs, max(res, orth)


@register("gbnorm")
def _t_gbnorm(ctx):
    import slate_tpu as st
    import jax
    import jax.numpy as jnp
    from slate_tpu.core.types import Norm
    n = ctx.n
    kl = ku = max(1, ctx.nb // 8)
    a = _band_dense(ctx, kl, ku)
    A = st.band(jnp.asarray(a, ctx.dtype), ctx.nb, kl, ku, grid=ctx.grid)
    errs = []
    secs = 0.0
    for nk, ref in ((Norm.One, lambda x: np.linalg.norm(x, 1)),
                    (Norm.Inf, lambda x: np.linalg.norm(x, np.inf)),
                    (Norm.Fro, lambda x: np.linalg.norm(x, "fro"))):
        out, s = ctx.timed(jax.jit(lambda nk=nk: st.norm(A, nk)))
        secs += s
        r = ref(_np64(a))
        errs.append(_rel(abs(float(out) - r),
                         ctx.eps * n * max(r, 1e-300)))
    return secs, max(errs)


@register("hbnorm")
def _t_hbnorm(ctx):
    import slate_tpu as st
    import jax
    import jax.numpy as jnp
    from slate_tpu.core.types import Norm, Uplo
    n = ctx.n
    kd = max(1, ctx.nb // 8)
    a = _band_dense(ctx, kd, kd, herm=True)
    A = st.hermitian_band(jnp.asarray(np.tril(a), ctx.dtype), ctx.nb, kd,
                          Uplo.Lower, grid=ctx.grid)
    out, secs = ctx.timed(jax.jit(lambda: st.norm(A, Norm.One)))
    r = np.linalg.norm(_np64(a), 1)
    err = _rel(abs(float(out) - r), ctx.eps * n * max(r, 1e-300))
    return secs, err


@register("col_norms")
def _t_col_norms(ctx):
    import slate_tpu as st
    import jax
    from slate_tpu.core.types import Norm
    m, n = ctx.m, ctx.n
    a = ctx.gen("randn", m, n)
    A = ctx.dense(a)
    out, secs = ctx.timed(jax.jit(lambda: st.col_norms(A, Norm.Max)))
    ref = np.abs(_np64(a)).max(axis=0)
    err = _rel(np.abs(np.asarray(out)[:n] - ref).max(),
               ctx.eps * max(ref.max(), 1e-300))
    return secs, err


@register("getrf_nopiv", tol=30)
def _t_getrf_nopiv(ctx):
    # residual below is already ‖L‖‖U‖-normalized and the operand is
    # diagonally dominant — the old flat tol=1e4 was vestigial slack
    import slate_tpu as st
    n = ctx.n
    a = ctx.gen("randn", n, n)
    a = a + n * np.eye(n)  # diagonally dominant: no-pivot is stable here
    A = ctx.dense(a)
    (LU, info), secs = ctx.timed(lambda: st.getrf_nopiv(A))
    lu = _np64(LU.dense_canonical())
    npad = lu.shape[0]
    l = np.tril(lu, -1) + np.eye(npad)
    u = np.triu(lu)
    an = _np64(A.dense_canonical())
    err = _rel(np.linalg.norm(an - l @ u, 1),
               ctx.eps * n * np.linalg.norm(l, 1) * np.linalg.norm(u, 1))
    return secs, err


@register("tsqr", tol=30)
def _t_tsqr(ctx):
    import slate_tpu as st
    m, n = max(ctx.m, 4 * ctx.n), ctx.n
    a = ctx.gen("randn", m, n)
    A = ctx.dense(a)
    (Q, R), secs = ctx.timed(lambda: st.tsqr(A))
    q = _np64(Q.to_numpy())
    r = np.triu(_np64(R.to_numpy()))[:n, :n]
    an = _np64(a)
    err_f = _rel(np.linalg.norm(an - q @ r, 1),
                 ctx.eps * m * np.linalg.norm(an, 1))
    err_o = _rel(np.abs(q.conj().T @ q - np.eye(n)).max(), ctx.eps * m)
    return secs, max(err_f, err_o)


# -- method-variant rows (P10 dispatch coverage: each Method* enum arm
#    measured under the sweep; the reference's test.cc registers method
#    sweeps the same way)

@register("gemm_a", flops=_fl("gemm"))
def _t_gemm_a(ctx):
    """Stationary-A gemm (MethodGemm.A — reduce instead of bcast)."""
    import slate_tpu as st
    import jax
    from slate_tpu.core.types import MethodGemm, Options
    n = ctx.n
    a = ctx.gen("randn", ctx.m, n)
    b = ctx.gen("randn", n, ctx.m, 1)
    A, B = ctx.dense(a), ctx.dense(b)
    C0 = st.zeros(ctx.m, ctx.m, ctx.nb, ctx.dtype, grid=ctx.grid)
    opts = Options(method_gemm=MethodGemm.A)
    out, secs = ctx.timed(jax.jit(lambda: st.gemm(1.0, A, B, 0.0, C0,
                                                  opts)))
    ref = _np64(a) @ _np64(b)
    err = _prod_err(ctx, out.to_numpy(), ref, a, b)
    return secs, err


@register("gemm_summa", flops=_fl("gemm"))
def _t_gemm_summa(ctx):
    """Explicit hand-scheduled SUMMA (MethodGemm.SUMMA, shard_map)."""
    import slate_tpu as st
    import jax
    from slate_tpu.core.types import MethodGemm, Options
    if ctx.grid is None or ctx.grid.size == 1:
        # SUMMA needs a mesh; degrade to the auto path on 1x1
        return _REGISTRY["gemm"](ctx)
    n = ctx.n
    a = ctx.gen("randn", n, n)
    b = ctx.gen("randn", n, n, 1)
    A, B = ctx.dense(a), ctx.dense(b)
    C0 = st.zeros(n, n, ctx.nb, ctx.dtype, grid=ctx.grid)
    opts = Options(method_gemm=MethodGemm.SUMMA)
    out, secs = ctx.timed(jax.jit(lambda: st.gemm(1.0, A, B, 0.0, C0,
                                                  opts)))
    ref = _np64(a) @ _np64(b)
    err = _prod_err(ctx, out.to_numpy(), ref, a, b)
    return secs, err


def _trsm_variant(ctx, method):
    import slate_tpu as st
    import jax
    from slate_tpu.core.types import MethodTrsm, Options, Side
    n = ctx.n
    L = ctx.tri(ctx.gen("randn", n, n))
    b = ctx.gen("randn", n, n, 1)
    B = ctx.dense(b)
    opts = Options(method_trsm=method)
    out, secs = ctx.timed(
        jax.jit(lambda: st.trsm(Side.Left, 1.0, L, B, opts)))
    lref = _np64(L.full_dense_canonical())[:n, :n]
    return secs, _solve_err(ctx, lref, out.to_numpy(), np.asarray(b))


def _t_trsm_a(ctx):
    from slate_tpu.core.types import MethodTrsm
    return _trsm_variant(ctx, MethodTrsm.A)


def _t_trsm_b(ctx):
    from slate_tpu.core.types import MethodTrsm
    return _trsm_variant(ctx, MethodTrsm.B)


register("trsm_a")(_t_trsm_a)
register("trsm_b")(_t_trsm_b)


@register("hemm_a", flops=_fl("hemm"))
def _t_hemm_a(ctx):
    """Stationary-A hemm (MethodHemm.A — the listReduce analog)."""
    import slate_tpu as st
    import jax
    import jax.numpy as jnp
    from slate_tpu.core.types import MethodHemm, Options, Side
    n = ctx.n
    a = ctx.gen("randn", n, n)
    a = 0.5 * (a + jnp.conj(a).T)
    A = ctx.herm(a)
    b = ctx.gen("randn", n, n, 1)
    B = ctx.dense(b)
    C = st.zeros(n, n, ctx.nb, ctx.dtype, grid=ctx.grid)
    opts = Options(method_hemm=MethodHemm.A)
    out, secs = ctx.timed(
        jax.jit(lambda: st.hemm(Side.Left, 1.0, A, B, 0.0, C, opts)))
    ref = _np64(a) @ _np64(b)
    err = _prod_err(ctx, out.to_numpy(), ref, a, b)
    return secs, err


@register("gels_cholqr", flops=_fl("gels"), tol=30)
def _t_gels_cholqr(ctx):
    """MethodGels.CholQR (reference gels_cholqr.cc path)."""
    import slate_tpu as st
    from slate_tpu.core.types import MethodGels, Options
    m, n = max(ctx.m, 2 * ctx.n), ctx.n
    a = ctx.gen("randn", m, n)
    b = ctx.gen("randn", m, 2, 1)
    opts = Options(method_gels=MethodGels.CholQR)
    X, secs = ctx.timed(lambda: st.gels(ctx.dense(a), ctx.dense(b), opts))
    x = _np64(X.to_numpy()[:n])
    an, bn = _np64(a), _np64(b)
    rr = an.conj().T @ (an @ x - bn)
    err = _rel(np.linalg.norm(rr, 1),
               ctx.eps * m * np.linalg.norm(an, 1) ** 2
               * max(np.linalg.norm(x, 1), 1e-300))
    return secs, err


@register("heev_qr", flops=_fl("heev"))
def _t_heev_qr(ctx):
    """MethodEig.QR (native steqr tridiagonal stage)."""
    import slate_tpu as st
    from slate_tpu.core.types import MethodEig, Options
    n = ctx.n
    a = ctx.gen("heev_arith", n, n, cond=100.0)
    A = ctx.herm(a)
    opts = Options(method_eig=MethodEig.QR)
    (w, Z), secs = ctx.timed(lambda: st.heev(A, opts))
    wref = np.linalg.eigvalsh(_np64(a))
    err = _rel(np.abs(np.asarray(w, np.float64) - wref).max(),
               ctx.eps * n * max(np.abs(wref).max(), 1e-300))
    return secs, err


@register("gesv_calu", flops=_fl("gesv"), tol=30)
def _t_gesv_calu(ctx):
    """MethodLU.CALU: tournament-pivoted LU (round-5 mesh-breadth row —
    the reference sweeps CALU under mpirun, test/run_tests.py)."""
    from slate_tpu.core.types import MethodLU, Options
    return _lu_solver_case(
        ctx, lambda st, A, B: st.gesv(A, B,
                                      Options(method_lu=MethodLU.CALU))[0])


@register("gesv_threshold", flops=_fl("gesv"), tol=30)
def _t_gesv_threshold(ctx):
    """pivot_threshold < 1: tournament panels (PivotThreshold analog)."""
    from slate_tpu.core.types import Options
    return _lu_solver_case(
        ctx, lambda st, A, B: st.gesv(A, B,
                                      Options(pivot_threshold=0.5))[0])


@register("hesv_rbt", flops=_fl("hesv"), tol=30)
def _t_hesv_rbt(ctx):
    """MethodHesv.RBT: butterfly + no-pivot LDLH + IR."""
    import jax.numpy as jnp
    from slate_tpu.core.types import MethodHesv, Options
    n = ctx.n
    a = ctx.gen("randn", n, n)
    a = 0.5 * (a + jnp.conj(a).T)  # Hermitian: complex dtypes run too
    A = ctx.herm(a)
    b = ctx.gen("randn", n, 4, 1)
    B = ctx.dense(b)
    opts = Options(method_hesv=MethodHesv.RBT)
    import slate_tpu as st
    X, secs = ctx.timed(lambda: st.hesv(A, B, opts)[0])
    return secs, _solve_err(ctx, a, X.to_numpy(), b)


@register("stedc_vals")
def _t_stedc_vals(ctx):
    """Values-only D&C (O(n) state per node, src/stedc.cc jobz='N')."""
    from slate_tpu.linalg.stedc import stedc
    n = ctx.n
    rng = np.random.default_rng(ctx.seed)
    d, e = rng.standard_normal(n), rng.standard_normal(n - 1)
    t0 = time.perf_counter()
    w, z = stedc(d, e, compute_z=False)
    secs = time.perf_counter() - t0
    assert z is None
    t = np.diag(d) + np.diag(e, 1) + np.diag(e, -1)
    wref = np.linalg.eigvalsh(t)
    epsd = np.finfo(np.float64).eps
    err = _rel(np.abs(w - wref).max(),
               epsd * n * max(np.abs(wref).max(), 1e-300))
    return secs, err


@register("synorm")
def _t_synorm(ctx):
    """Symmetric-kind norms (internal_synorm analog)."""
    import slate_tpu as st
    import jax
    import jax.numpy as jnp
    from slate_tpu.core.types import Norm, Uplo
    n = ctx.n
    a = ctx.gen("randn", n, n)
    a = 0.5 * (a + a.T)
    A = st.symmetric(jnp.tril(np.asarray(a)), nb=ctx.nb, uplo=Uplo.Lower,
                     grid=ctx.grid)
    full = _np64(a)
    errs = []
    secs = 0.0
    for nk, ref in ((Norm.One, lambda x: np.linalg.norm(x, 1)),
                    (Norm.Fro, lambda x: np.linalg.norm(x, "fro")),
                    (Norm.Max, lambda x: np.abs(x).max())):
        out, s = ctx.timed(jax.jit(lambda nk=nk: st.norm(A, nk)))
        secs += s
        r = ref(full)
        errs.append(_rel(abs(float(out) - r),
                         ctx.eps * n * max(r, 1e-300)))
    return secs, max(errs)


def _tz_case(ctx, which):
    """Trapezoid/triangular elementwise kernels (the reference's tz*
    device kernel family: tzadd/tzcopy/tzscale/tzset)."""
    import slate_tpu as st
    import jax.numpy as jnp
    n = ctx.n
    a = ctx.gen("randn", ctx.m, n)
    T = ctx.tri(a, diag_boost=False)
    tn = _np64(T.full_dense_canonical())[:ctx.m, :n]
    if which == "tzadd":
        B = ctx.tri(ctx.gen("randn", ctx.m, n, 1), diag_boost=False)
        bn = _np64(B.full_dense_canonical())[:ctx.m, :n]
        out, secs = ctx.timed(lambda: st.add(2.0, T, 1.0, B))
        ref = 2.0 * tn + bn
        got = _np64(out.full_dense_canonical())[:ctx.m, :n]
    elif which == "tzscale":
        out, secs = ctx.timed(lambda: st.scale(3.0, 2.0, T))
        ref = 1.5 * tn
        got = _np64(out.full_dense_canonical())[:ctx.m, :n]
    elif which == "tzcopy":
        tgt = jnp.complex128 if np.iscomplexobj(tn) else jnp.float64
        out, secs = ctx.timed(lambda: st.copy(T, dtype=tgt))
        ref = tn
        got = _np64(out.full_dense_canonical())[:ctx.m, :n]
    else:  # tzset
        out, secs = ctx.timed(lambda: st.set_matrix(0.5, 3.0, T))
        got = _np64(out.full_dense_canonical())[:ctx.m, :n]
        tri_mask = np.tril(np.ones((ctx.m, n), bool)) \
            if ctx.uplo == "lower" else np.triu(np.ones((ctx.m, n), bool))
        ref = np.where(tri_mask, 0.5, 0.0)
        np.fill_diagonal(ref, 3.0)
    err = _rel(np.abs(got - ref).max(),
               ctx.eps * max(np.abs(ref).max(), 1e-300))
    return secs, err


for _r in ("tzadd", "tzscale", "tzcopy", "tzset"):
    register(_r)(lambda ctx, _r=_r: _tz_case(ctx, _r))


# -- `--ref` cross-check mode ----------------------------------------------
# The reference tester's `--ref y` runs the same problem through
# ScaLAPACK and compares norms (test/test_gemm.cc:210-278). Our
# reference oracle is the host LAPACK via numpy: each runner rebuilds
# the IDENTICAL deterministic problem (same matgen seeds), solves it
# both ways, and reports (ref seconds, scaled cross-difference).

REF_RUNNERS: Dict[str, Callable] = {}


def _ref(name):
    def deco(fn):
        REF_RUNNERS[name] = fn
        return fn
    return deco


@_ref("gemm")
def _r_gemm(ctx):
    import slate_tpu as st
    n = ctx.n
    a = ctx.gen("randn", ctx.m, n)
    b = ctx.gen("randn", n, ctx.m, 1)
    C0 = st.zeros(ctx.m, ctx.m, ctx.nb, ctx.dtype, grid=ctx.grid)
    ours = st.gemm(1.0, ctx.dense(a), ctx.dense(b), 0.0, C0).to_numpy()
    an, bn = _np64(a), _np64(b)
    t0 = time.perf_counter()
    ref = an @ bn
    secs = time.perf_counter() - t0
    err = _rel(np.abs(_np64(ours) - ref).max(),
               ctx.eps * n * max(np.abs(ref).max(), 1e-300))
    return secs, err


@_ref("gesv")
def _r_gesv(ctx):
    import slate_tpu as st
    n = ctx.n
    a = ctx.gen("randn", n, n)
    b = ctx.gen("randn", n, 8, 1)
    X, _ = st.gesv(ctx.dense(a), ctx.dense(b))
    an, bn = _np64(a), _np64(b)
    t0 = time.perf_counter()
    ref = np.linalg.solve(an, bn)
    secs = time.perf_counter() - t0
    err = _rel(np.abs(_np64(X.to_numpy()) - ref).max(),
               ctx.eps * n * np.linalg.cond(an, 1)
               * max(np.abs(ref).max(), 1e-300))
    return secs, err


@_ref("posv")
def _r_posv(ctx):
    import slate_tpu as st
    n = ctx.n
    a = ctx.spd(n)
    b = ctx.gen("randn", n, 8, 1)
    X, _ = st.posv(ctx.herm(a), ctx.dense(b))
    an, bn = _np64(a), _np64(b)
    t0 = time.perf_counter()
    ref = np.linalg.solve(an, bn)
    secs = time.perf_counter() - t0
    err = _rel(np.abs(_np64(X.to_numpy()) - ref).max(),
               ctx.eps * n * max(np.abs(ref).max(), 1e-300))
    return secs, err


@_ref("gels")
def _r_gels(ctx):
    import slate_tpu as st
    m, n = max(ctx.m, ctx.n), ctx.n
    a = ctx.gen("randn", m, n)
    b = ctx.gen("randn", m, 4, 1)
    X = st.gels(ctx.dense(a), ctx.dense(b))
    an, bn = _np64(a), _np64(b)
    t0 = time.perf_counter()
    ref = np.linalg.lstsq(an, bn, rcond=None)[0]
    secs = time.perf_counter() - t0
    err = _rel(np.abs(_np64(X.to_numpy()[:n]) - ref).max(),
               ctx.eps * m * max(np.abs(ref).max(), 1e-300)
               * np.linalg.cond(an))
    return secs, err


@_ref("heev")
def _r_heev(ctx):
    import slate_tpu as st
    n = ctx.n
    a = ctx.gen("heev_arith", n, n, cond=100.0)
    w, _ = st.heev(ctx.herm(a), want_vectors=False)
    t0 = time.perf_counter()
    ref = np.linalg.eigvalsh(_np64(a))
    secs = time.perf_counter() - t0
    err = _rel(np.abs(np.asarray(w, np.float64) - ref).max(),
               ctx.eps * n * max(np.abs(ref).max(), 1e-300))
    return secs, err


@_ref("svd")
def _r_svd(ctx):
    import slate_tpu as st
    m, n = ctx.m, ctx.n
    a = ctx.gen("svd_geo", m, n, cond=100.0)
    s, *_ = st.svd(ctx.dense(a))
    t0 = time.perf_counter()
    ref = np.linalg.svd(_np64(a), compute_uv=False)
    secs = time.perf_counter() - t0
    err = _rel(np.abs(np.asarray(s, np.float64) - ref).max(),
               ctx.eps * max(m, n) * ref[0])
    return secs, err


def run_one(routine: str, m: int, n: int, nb: int, grid, dtype, seed: int,
            iters: int, uplo: str = "lower", trans: str = "n",
            origin: str = "device"):
    """Returns (seconds, gflops, scaled_error, ok)."""
    fn = _REGISTRY.get(routine)
    if fn is None:
        raise ValueError(
            f"unknown routine {routine}; --list shows all "
            f"{len(_REGISTRY)} registered")
    ctx = Ctx(m, n, nb, grid, dtype, seed, iters, uplo, trans, origin)
    secs, err = fn(ctx)
    flops = getattr(fn, "_flops", lambda m, n: 0.0)(m, n)
    gflops = flops / secs / 1e9 if secs > 0 else 0.0
    return secs, gflops, float(err), bool(err < _TOLS[routine])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--routine", default="gemm,posv,gesv,gels",
                    help="comma list, or 'all'")
    ap.add_argument("--list", action="store_true",
                    help="print registered routines and exit")
    ap.add_argument("--n", default="256,512")
    ap.add_argument("--m", default=None, help="defaults to n")
    ap.add_argument("--nb", type=int, default=64)
    ap.add_argument("--p", type=int, default=1)
    ap.add_argument("--q", type=int, default=1)
    ap.add_argument("--dtype", default="f32",
                    choices=["f32", "f64", "bf16", "c64", "c128"])
    ap.add_argument("--uplo", default="lower", choices=["lower", "upper"])
    ap.add_argument("--origin", default="device",
                    choices=["device", "host", "scalapack"],
                    help="where user data starts (reference "
                         "Origin::{Host,Devices,ScaLAPACK} sweeps)")
    ap.add_argument("--trans", default="n", choices=["n", "t", "c"])
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--iters", type=int, default=1)
    ap.add_argument("--ref", action="store_true",
                    help="also run the host-LAPACK (numpy) reference on "
                         "the identical problem and report its time + "
                         "the scaled cross-difference (the reference "
                         "tester's --ref y ScaLAPACK comparison)")
    ap.add_argument("--trace", default=None, help="write SVG timeline")
    args = ap.parse_args(argv)

    if args.list:
        for name in sorted(_REGISTRY):
            print(name)
        return 0

    from slate_tpu.compat.platform import enable_compile_cache

    enable_compile_cache()

    if args.dtype in ("f64", "c128"):
        # without x64 JAX silently truncates to f32 and every row fails
        # its f64-eps bound; enable it up front (before array creation)
        import jax

        jax.config.update("jax_enable_x64", True)

    import jax.numpy as jnp
    from slate_tpu.core.grid import ProcessGrid
    from slate_tpu.utils import trace as trace_mod

    dtype = {"f32": jnp.float32, "f64": jnp.float64, "bf16": jnp.bfloat16,
             "c64": jnp.complex64, "c128": jnp.complex128}[args.dtype]
    grid = None
    if args.p * args.q > 1:
        grid = ProcessGrid.create(args.p, args.q)
    if args.trace:
        trace_mod.Trace.clear()
        trace_mod.Trace.on()

    routines = sorted(_REGISTRY) if args.routine == "all" \
        else args.routine.split(",")
    sizes = [int(s) for s in args.n.split(",")]
    ms = [int(s) for s in args.m.split(",")] if args.m else sizes
    hdr = (f"{'routine':<18} {'m':>6} {'n':>6} {'nb':>5} {'grid':>5} "
           f"{'time(s)':>10} {'GFLOP/s':>10} {'scaled-err':>10} status")
    print(hdr)
    print("-" * len(hdr))
    failures = 0
    for routine in routines:
        for m, n in zip(ms, sizes):
            with trace_mod.Block(routine):
                try:
                    secs, gf, err, ok = run_one(
                        routine, m, n, args.nb, grid, dtype, args.seed,
                        args.iters, args.uplo, args.trans, args.origin)
                except Exception as e:  # surface per-row, keep sweeping
                    print(f"{routine:<18} {m:>6} {n:>6} {args.nb:>5} "
                          f"{args.p}x{args.q:>3} {'-':>10} {'-':>10} "
                          f"{'-':>10} ERROR: {e}")
                    failures += 1
                    continue
            status = "pass" if ok else "FAILED"
            failures += 0 if ok else 1
            print(f"{routine:<18} {m:>6} {n:>6} {args.nb:>5} "
                  f"{args.p}x{args.q:>3} {secs:>10.4f} {gf:>10.1f} "
                  f"{err:>10.2e} {status}")
            if args.ref and routine in REF_RUNNERS:
                try:  # surface per-row, keep sweeping (as run_one does)
                    ctx = Ctx(m, n, args.nb, grid, dtype, args.seed, 1,
                              args.uplo, args.trans)
                    rsecs, rerr = REF_RUNNERS[routine](ctx)
                except Exception as e:
                    print(f"{routine + '/ref':<18} {m:>6} {n:>6} "
                          f"{args.nb:>5} {'host':>5} {'-':>10} "
                          f"{'-':>10} {'-':>10} ERROR: {e}")
                    failures += 1
                    continue
                rok = rerr < 10 * _TOLS[routine]
                failures += 0 if rok else 1
                print(f"{routine + '/ref':<18} {m:>6} {n:>6} "
                      f"{args.nb:>5} {'host':>5} {rsecs:>10.4f} "
                      f"{'-':>10} {rerr:>10.2e} "
                      f"{'pass' if rok else 'FAILED'}")
    if args.trace:
        trace_mod.Trace.off()
        path = trace_mod.Trace.finish(args.trace)
        print(f"# trace written to {path}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
