#!/usr/bin/env python
"""Benchmark driver: headline GFLOP/s/chip for the gemm driver.

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}.

Baseline (BASELINE.md): the reference's only in-repo measurement is dgemm
n=10000 nb=384 on 4 ranks × 1 NVIDIA GPU in 0.712 s ≈ 0.7 TFLOP/s per GPU
(fp64, /root/reference/docs/usage.md:36-44). TPU v5 has no fp64 datapath,
so we benchmark the same driver in fp32 (the TPU working precision for
this framework; fp64-class accuracy is delivered via mixed-precision
iterative refinement — see posv_mixed/gesv_mixed) and report
vs_baseline against the 700 GFLOP/s/chip reference number.

Methodology: each routine is iterated K times inside ONE jit via
lax.scan (with a real data dependence between iterations so XLA cannot
hoist the work), synced by fetching a scalar, and timed at two K values
— the difference cancels dispatch/transfer overhead. Extra per-routine
numbers go to stderr; the driver only parses stdout.

It runs where JAX_PLATFORMS puts it and stamps the platform it ran on;
a row that fails fails the run (exit 1, an "error" key on the line).
"""

import json
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp

# model-GFLOP formulas: the one home is the FLOP ledger (ISSUE 4) —
# bench.py, slate_tpu/tester.py, and runtime/session.py all share it
from slate_tpu.obs import flops as model_flops
# bytes/roofline side of the ledger (ISSUE 5): XLA cost harvest +
# intensity/roof join for the --phases roofline rows
from slate_tpu.obs import costs as obs_costs
from slate_tpu.obs import roofline as obs_roofline

BASELINE_GFLOPS_PER_CHIP = 700.0  # reference SLATE dgemm per-GPU (docs/usage.md)


def _timed_scalar(fn, *args):
    t0 = time.perf_counter()
    v = float(fn(*args))
    dt = time.perf_counter() - t0
    if v != v:  # NaN guard — benchmark must compute something real
        raise RuntimeError("benchmark produced NaN")
    return dt


def _per_iter_seconds(step, carry0, consts, k1=4, k2=16):
    """Time a scan of k iterations of step at two lengths; the slope is
    the pure per-iteration time (dispatch + sync overhead cancels).

    ``consts`` are passed as jit *arguments* — closing over large arrays
    would bake them into the HLO as constants."""

    @partial(jax.jit, static_argnums=0)
    def run(k, carry, cs):
        def body(c, _):
            return step(c, cs), None
        c, _ = jax.lax.scan(body, carry, None, length=k)
        return jnp.real(jnp.ravel(c)[0])

    _ = _timed_scalar(run, k2, carry0, consts)  # warm both compilations
    _ = _timed_scalar(run, k1, carry0, consts)
    t1 = min(_timed_scalar(run, k1, carry0, consts) for _ in range(2))
    t2 = min(_timed_scalar(run, k2, carry0, consts) for _ in range(2))
    return max((t2 - t1) / (k2 - k1), 1e-9)


def bench_gemm(n=8192, nb=512, dtype=jnp.float32, precision=None):
    """``precision``: None = XLA default (1-pass bf16 on fp32 data — the
    peak-rate headline); "high" = bf16x3, the SAME compute budget the
    factorization trailing updates run at (Options.update_precision),
    i.e. the apples-to-apples denominator for potrf/getrf/geqrf
    pct-of-gemm (the reference compares dgemm and dpotrf at one
    precision too)."""
    import contextlib

    import slate_tpu as st
    from slate_tpu.matgen import generate_matrix

    a = generate_matrix("randn", n, n, dtype, seed=1)
    b = generate_matrix("randn", n, n, dtype, seed=2)
    A = st.from_dense(a, nb=nb)
    B = st.from_dense(b, nb=nb)
    C0 = st.zeros(n, n, nb, dtype)

    alpha = 1.0 / (2.0 * n ** 0.5)  # keeps the iterate's norm roughly stable

    def step(c_data, cs):
        A, B, C0 = cs
        # the carry is the RIGHT operand: C_{k+1} = α·A·C_k + β·B, a chain
        # of dependent matmuls XLA cannot hoist out of the scan
        out = st.gemm(alpha, A, B.with_data(c_data), 1e-3, C0)
        return out.data

    ctx = jax.default_matmul_precision(precision) if precision \
        else contextlib.nullcontext()
    with ctx:
        t = _per_iter_seconds(step, B.data, (A, B, C0))
    return model_flops.gemm(n, n, n) / 1e9 / t, t


def bench_potrf(n=8192, nb=1024, dtype=jnp.float32, opts=None):
    import slate_tpu as st
    from slate_tpu.core.types import Options, Uplo
    from slate_tpu.matgen import random_spd

    a = random_spd(n, dtype=dtype, seed=3)
    A = st.hermitian(jnp.tril(a), nb=nb, uplo=Uplo.Lower)
    opts = opts or Options()

    def step(a_data, cs):
        (A,) = cs
        L, _ = st.potrf(A.with_data(a_data), opts)
        # tiny L-dependent perturbation keeps the chain live without
        # changing the factored matrix materially
        return a_data + 1e-30 * L.data

    t = _per_iter_seconds(step, A.data, (A,), k1=2, k2=6)
    return model_flops.potrf(n) / 1e9 / t, t


def bench_getrf(n=8192, nb=1024, dtype=jnp.float32, opts=None):
    import slate_tpu as st
    from slate_tpu.core.types import Options
    from slate_tpu.matgen import generate_matrix

    a = generate_matrix("randn", n, n, dtype, seed=4)
    # diagonal dominance keeps the iterated factor chain stable
    a = a + n * jnp.eye(n, dtype=dtype)
    A = st.from_dense(a, nb=nb)
    opts = opts or Options()

    def step(a_data, cs):
        (A,) = cs
        LU, perm, _ = st.getrf(A.with_data(a_data), opts)
        return a_data + 1e-30 * LU.data

    t = _per_iter_seconds(step, A.data, (A,), k1=2, k2=6)
    return model_flops.getrf(n) / 1e9 / t, t


def bench_getrf_calu(n=8192, nb=1024, dtype=jnp.float32):
    """MethodLU.CALU (tournament pivoting) — PERF_HISTORY.md's recommended LU
    method at scale; benched alongside partial pivot per VERDICT r2."""
    from slate_tpu.core.types import MethodLU, Options
    return bench_getrf(n=n, nb=nb, dtype=dtype,
                       opts=Options(method_lu=MethodLU.CALU))


def bench_geqrf(n=8192, nb=1024, dtype=jnp.float32, opts=None):
    import slate_tpu as st
    from slate_tpu.core.types import Options
    from slate_tpu.matgen import generate_matrix

    a = generate_matrix("randn", n, n, dtype, seed=5)
    A = st.from_dense(a, nb=nb)
    opts = opts or Options()

    def step(a_data, cs):
        (A,) = cs
        qr = st.geqrf(A.with_data(a_data), opts)
        return a_data + 1e-30 * qr.vr

    t = _per_iter_seconds(step, A.data, (A,), k1=2, k2=6)
    return model_flops.geqrf(n, n) / 1e9 / t, t


# ---------------------------------------------------------------------------
# heev / svd rows (round 6, VERDICT r5 next-round #4)
# ---------------------------------------------------------------------------

def _eager_slope(fn, k1=1, k2=2):
    """Steady-state per-call seconds for a NON-jittable driver (heev/svd
    route their secular/deflation stages through the host, so the scan
    methodology cannot wrap them). One shared implementation with
    tester.Ctx.timed's --iters mode: utils/timing.eager_slope_seconds
    (warm call, k1/k2 batches with one sync each, resolution floor)."""
    from slate_tpu.utils.timing import eager_slope_seconds

    _, secs = eager_slope_seconds(fn, k1, k2, reps=1)
    return secs


def bench_heev(n=8192, nb=1024, dtype=jnp.float32):
    """Slope-timed heev (values + vectors) with the model-GFLOP
    convention of the reference's tester (blas::Gflop::heev as used by
    test/test_heev.cc; lawn41 counts): values = (4/3)·n³ (the he2td
    reduction dominates the flops), +2·n³ for the eigenvector
    back-transform. Also times the reduction stage alone so the row
    can NAME the dominant stage (VERDICT r5: 'identifies the dominant
    stage (expected: back-transforms)')."""
    import slate_tpu as st
    from slate_tpu.core.types import Uplo
    from slate_tpu.linalg import eig as eig_mod
    from slate_tpu.matgen import random_spd

    a = random_spd(n, dtype=dtype, seed=11)
    A = st.hermitian(jnp.tril(a), nb=nb, uplo=Uplo.Lower)
    t_red = _eager_slope(lambda: eig_mod.he2td(A))
    t_vals = _eager_slope(lambda: st.heev(A, want_vectors=False)[0])
    t_vecs = _eager_slope(lambda: st.heev(A, want_vectors=True))
    stages = {
        "reduction": t_red,
        "tridiag_dc": max(t_vals - t_red, 0.0),
        "back_transform": max(t_vecs - t_vals, 0.0),
    }
    return {
        "n": n, "nb": nb,
        "values_s": round(t_vals, 4),
        "vectors_s": round(t_vecs, 4),
        "values_gflops": round(model_flops.heev(n) / 1e9 / t_vals, 1),
        "vectors_gflops": round(
            model_flops.heev(n, vectors=True) / 1e9 / t_vecs, 1),
        "stages_s": {k: round(v, 4) for k, v in stages.items()},
        "dominant_stage": max(stages, key=stages.get),
    }


def bench_svd(n=8192, nb=1024, dtype=jnp.float32):
    """Slope-timed svd (values + vectors); model GFLOP per the
    reference tester's blas::Gflop::gesvd convention (lawn41 gebrd
    count): values = (8/3)·n³, +4·n³ for the two (U and V)
    back-transforms. The ge2bd reduction stage is timed alone to name
    the dominant stage."""
    import importlib

    import slate_tpu as st
    from slate_tpu.matgen import generate_matrix

    # linalg/__init__ re-exports the svd FUNCTION under the module's
    # name; import the module itself for the ge2bd stage
    svd_mod = importlib.import_module("slate_tpu.linalg.svd")
    a = generate_matrix("svd_geo", n, n, dtype, seed=12, cond=100.0)
    A = st.from_dense(a, nb=nb)
    t_red = _eager_slope(lambda: svd_mod.ge2bd(A))
    t_vals = _eager_slope(lambda: st.svd(A, want_vectors=False)[0])
    t_vecs = _eager_slope(lambda: st.svd(A, want_vectors=True))
    stages = {
        "bidiagonalization": t_red,
        "gk_dc": max(t_vals - t_red, 0.0),
        "back_transform": max(t_vecs - t_vals, 0.0),
    }
    return {
        "n": n, "nb": nb,
        "values_s": round(t_vals, 4),
        "vectors_s": round(t_vecs, 4),
        "values_gflops": round(model_flops.svd(n, n) / 1e9 / t_vals, 1),
        "vectors_gflops": round(
            model_flops.svd(n, n, vectors=True) / 1e9 / t_vecs, 1),
        "stages_s": {k: round(v, 4) for k, v in stages.items()},
        "dominant_stage": max(stages, key=stages.get),
    }


# ---------------------------------------------------------------------------
# factorization phase timer (round 6, ISSUE 2 acceptance artifact)
# ---------------------------------------------------------------------------

def bench_factor_phases(n=1024, nb=256, dtype=jnp.float32):
    """Before/after phase decomposition of the round-6 fast paths.

    PIVOT TERM (getrf): total minus getrf_nopiv at the same size, for
    the pivot-FUSED default vs the MATERIALIZED-copy arm
    (Options(lu_pivot_fusion=False) — same iterative structure, the
    old per-level full-width permuted copy). TRAILING-COPY TERM
    (potrf): one (n−nb)-square rank-nb trailing update through the old
    herk_lower_rec concat recursion vs the new in-place slab update
    (blocked.herk_trailing_inplace), plus end-to-end potrf through the
    default in-place iterative dispatch vs the true 2×2 recursion
    (crossover forced to 0 for the legacy arm). Round 7 adds the
    LOOKAHEAD A/B (pipeline vs sequential schedule per driver — a
    control pair off-TPU, see the in-body honesty note) and the
    batched-vs-tree CALU tournament round timing. All slope-timed
    inside one jit (the bench.py scan methodology)."""
    import slate_tpu as st
    from slate_tpu.core.types import Options, Uplo
    from slate_tpu.linalg import cholesky as chol_mod
    from slate_tpu.matgen import generate_matrix, random_spd
    from slate_tpu.ops import blocked

    out = {"n": n, "nb": nb}

    a0 = generate_matrix("randn", n, n, dtype, seed=4)
    a0 = a0 + n * jnp.eye(n, dtype=dtype)
    A = st.from_dense(a0, nb=nb)

    def t_getrf(opts):
        def step(a_data, cs):
            (A,) = cs
            LU, perm, _ = st.getrf(A.with_data(a_data), opts)
            return a_data + 1e-30 * LU.data
        return _per_iter_seconds(step, A.data, (A,), k1=2, k2=6)

    def step_nopiv(a_data, cs):
        (A,) = cs
        LU, _ = st.getrf_nopiv(A.with_data(a_data))
        return a_data + 1e-30 * LU.data

    t_fused = t_getrf(Options())
    t_mat = t_getrf(Options(lu_pivot_fusion=False))
    t_np = _per_iter_seconds(step_nopiv, A.data, (A,), k1=2, k2=6)

    # THE pivot-copy term, isolated: one full-width materialized row
    # permute of the n×n iterate — what the materialized arm writes at
    # every level and the fused arm never does (its permutation rides
    # the trailing-update READS; zero standalone copies, HLO-asserted
    # in tests/test_fastpaths.py). The end-to-end fused/materialized
    # totals above are recorded for context but are noise-dominated at
    # CPU smoke sizes (and XLA:CPU materializes gathers either way —
    # the read-fusion is a TPU lowering property, re-measure on-chip).
    import numpy as np

    perm0 = jnp.asarray(np.random.default_rng(0).permutation(n), jnp.int32)

    def step_permute(x, cs):
        (p,) = cs
        return x[p]

    t_perm = _per_iter_seconds(step_permute, a0, (perm0,), k1=2, k2=10)
    nt = n // nb
    out["getrf_ms"] = {
        "fused": round(t_fused * 1e3, 3),
        "materialized": round(t_mat * 1e3, 3),
        "nopiv": round(t_np * 1e3, 3),
        "pivot_term_before": round((t_mat - t_np) * 1e3, 3),
        "pivot_term_after": round((t_fused - t_np) * 1e3, 3),
        "permute_copy_per_level": round(t_perm * 1e3, 3),
        "permute_copy_before_total": round(t_perm * nt * 1e3, 3),
        "permute_copy_after_total": 0.0,  # fused into reads, by construction
    }

    # trailing-copy term: identical rank-nb update, two write disciplines
    s = n - nb
    c0 = generate_matrix("randn", s, s, dtype, seed=6)
    p0 = generate_matrix("randn", s, nb, dtype, seed=7)

    def step_rec(c, cs):
        (pan,) = cs
        return blocked.herk_lower_rec(c, pan, prec="high")

    def step_inplace(c, cs):
        (pan,) = cs
        return blocked.herk_trailing_inplace(c, pan, 0, nb, prec="high")

    t_rec = _per_iter_seconds(step_rec, c0, (p0,), k1=2, k2=8)
    t_inp = _per_iter_seconds(step_inplace, c0, (p0,), k1=2, k2=8)

    spd = random_spd(n, dtype=dtype, seed=3)
    Ah = st.hermitian(jnp.tril(spd), nb=nb, uplo=Uplo.Lower)

    def t_potrf(opts):
        def step(a_data, cs):
            (Ah,) = cs
            L, _ = st.potrf(Ah.with_data(a_data), opts)
            return a_data + 1e-30 * L.data
        return _per_iter_seconds(step, Ah.data, (Ah,), k1=2, k2=6)

    t_iter = t_potrf(Options())
    saved_base = chol_mod._POTRF_ITER_BASE
    chol_mod._POTRF_ITER_BASE = 0  # legacy arm = the TRUE 2x2 recursion
    try:
        t_recur = t_potrf(Options(factor_iter_large=False))
    finally:
        chol_mod._POTRF_ITER_BASE = saved_base
    out["potrf_ms"] = {
        "iter_inplace": round(t_iter * 1e3, 3),
        "recursion": round(t_recur * 1e3, 3),
        "trailing_update_concat_rec": round(t_rec * 1e3, 3),
        "trailing_update_inplace": round(t_inp * 1e3, 3),
        "trailing_copy_saving": round((t_rec - t_inp) * 1e3, 3),
    }

    # --- round 7: lookahead A/B (panel-hidden vs exposed schedule) ---
    # The default (lookahead=1) pipeline vs the sequential round-6
    # schedule (lookahead=0), per driver. HONESTY (per the round-6
    # precedent): XLA:CPU executes its thunk sequence serially, so NO
    # overlap is expected off-TPU and these totals should read as a
    # wash (they are recorded as the control pair); the schedule
    # DECOUPLING is the structurally-asserted term
    # (tests/test_lookahead.py jaxpr + scheduled-HLO guards) and the
    # time saving is a TPU/mesh scheduler property — re-measure
    # on-chip. The batched-vs-tree CALU round A/B below IS
    # CPU-measurable (different lowering: one batched fori program per
    # round vs the custom-call's sequential per-block loop).
    t_seq_potrf = t_potrf(Options(lookahead=0))
    t_seq_getrf = t_getrf(Options(lookahead=0))

    aq = generate_matrix("randn", n, n, dtype, seed=8)
    Aq = st.from_dense(aq, nb=nb)

    def t_geqrf(opts):
        def step(a_data, cs):
            (Aq,) = cs
            qr = st.geqrf(Aq.with_data(a_data), opts)
            return a_data + 1e-30 * qr.vr
        return _per_iter_seconds(step, Aq.data, (Aq,), k1=2, k2=6)

    t_qr1 = t_geqrf(Options())
    t_qr0 = t_geqrf(Options(lookahead=0))
    out["lookahead_ms"] = {
        "potrf_lookahead1": round(t_iter * 1e3, 3),
        "potrf_lookahead0": round(t_seq_potrf * 1e3, 3),
        "getrf_lookahead1": round(t_fused * 1e3, 3),
        "getrf_lookahead0": round(t_seq_getrf * 1e3, 3),
        "geqrf_lookahead1": round(t_qr1 * 1e3, 3),
        "geqrf_lookahead0": round(t_qr0 * 1e3, 3),
        "cpu_measurable": False,  # overlap is a TPU/mesh scheduler term
    }

    # --- round 7: batched-vs-tree CALU tournament round timing ---
    from slate_tpu.linalg import lu as lu_mod

    panel0 = generate_matrix("randn", n, nb, dtype, seed=9)

    def t_tournament(batched):
        def step(x, cs):
            p = lu_mod._tournament_perm(x, nb, nb, n, n, batched=batched)
            return x + 1e-30 * jnp.sum(p.astype(x.dtype))
        return _per_iter_seconds(step, panel0, (), k1=2, k2=8)

    t_round_b = t_tournament(True)
    t_round_t = t_tournament(False)
    out["calu_round_ms"] = {
        "batched": round(t_round_b * 1e3, 3),
        "tree": round(t_round_t * 1e3, 3),
        "cpu_measurable": True,  # lowering difference, visible off-TPU
    }
    return out


def _single_call_costs(name, n, nb, dtype=jnp.float32):
    """XLA cost/memory analysis of ONE application of a driver verb
    (the scan programs time well but XLA counts a while body once, so
    per-iteration bytes must come from a single-call program). Returns
    a ProgramCosts; degrades to partial=True on any backend gap."""
    import slate_tpu as st
    from slate_tpu.core.types import Uplo
    from slate_tpu.matgen import generate_matrix, random_spd

    if name == "gemm":
        a = generate_matrix("randn", n, n, dtype, seed=1)
        A = st.from_dense(a, nb=nb)
        fn = jax.jit(lambda x, y: st.gemm(
            1.0, A.with_data(x), A.with_data(y), 0.0,
            st.zeros(n, n, nb, dtype)).data)
        args = (A.data, A.data)
    elif name == "potrf":
        a = random_spd(n, dtype=dtype, seed=3)
        A = st.hermitian(jnp.tril(a), nb=nb, uplo=Uplo.Lower)
        fn = jax.jit(lambda x: st.potrf(A.with_data(x))[0].data)
        args = (A.data,)
    elif name in ("getrf", "getrf_calu"):
        a = generate_matrix("randn", n, n, dtype, seed=4)
        a = a + n * jnp.eye(n, dtype=dtype)
        A = st.from_dense(a, nb=nb)
        from slate_tpu.core.types import MethodLU, Options
        opts = (Options(method_lu=MethodLU.CALU)
                if name == "getrf_calu" else Options())
        fn = jax.jit(lambda x: st.getrf(A.with_data(x), opts)[0].data)
        args = (A.data,)
    elif name == "geqrf":
        a = generate_matrix("randn", n, n, dtype, seed=5)
        A = st.from_dense(a, nb=nb)
        fn = jax.jit(lambda x: st.geqrf(A.with_data(x)).vr)
        args = (A.data,)
    else:
        raise ValueError(name)
    return obs_costs.program_costs(fn.lower(*args).compile())


def _mixed_roofline_rows(n, nb, dtype=jnp.float32):
    """Roofline rows for the mixed-precision solves (ROADMAP item 2):
    ``gesv_mixed``/``posv_mixed`` with a bf16 factor refined to the
    f32 working precision. The verbs carry a host-side convergence
    loop (not jittable whole), so the bytes column composes the
    COMPONENT programs exactly as one mixed solve executes them: one
    low-precision factor, (iters+1) low-precision
    solve-using-factor passes, and iters working-precision residual
    gemms — precision-conversion copies uncounted, so bytes are a
    documented lower bound and the intensity column an upper bound.
    The point is the SHIFT: the bf16 factor halves the dominant
    factor-phase bytes while the model flops stay the lawn41 count,
    so intensity moves up vs the uniform-precision verb (the
    ``factor_intensity_lo``/``factor_intensity_working`` pair shows
    it directly — the MXU lever the Session wires in next round).
    CPU-smoke honesty (PERF_HISTORY.md Round 11): XLA:CPU materializes
    f32<->bf16 converts around every gemm, so on this host the lo
    intensity reads LOWER — the shift is a TPU (native-bf16) claim;
    the column pair is the before/after hook for the on-chip re-run.
    One eager call per verb credits the flop ledger (the PR-6
    instrumented wrappers) and the composed bytes are credited under
    the verb name, so ``LEDGER.gflops_report()`` renders the same
    intensity column."""
    import slate_tpu as st
    from slate_tpu.core.types import Uplo
    from slate_tpu.matgen import generate_matrix, random_spd
    from slate_tpu.obs.flops import LEDGER

    machine = obs_roofline.MachineModel.from_env()
    factor_dtype = jnp.bfloat16
    rows = []
    for name in ("posv_mixed", "gesv_mixed"):
        if name == "posv_mixed":
            a = random_spd(n, dtype=dtype, seed=13)
            A = st.hermitian(jnp.tril(a), nb=nb, uplo=Uplo.Lower)
            A_lo = st.hermitian(jnp.tril(a).astype(factor_dtype),
                                nb=nb, uplo=Uplo.Lower)
            fl = (model_flops.potrf(n)
                  + model_flops.solve_flops("chol", n, n, 1))
            verb = st.posv_mixed
        else:
            a = generate_matrix("randn", n, n, dtype, seed=14)
            a = a + n * jnp.eye(n, dtype=dtype)
            A = st.from_dense(a, nb=nb)
            A_lo = st.from_dense(a.astype(factor_dtype), nb=nb)
            fl = (model_flops.getrf(n)
                  + model_flops.solve_flops("lu", n, n, 1))
            verb = st.gesv_mixed
        B = st.from_dense(jnp.ones((n, 1), dtype), nb=nb)
        B_lo = st.from_dense(jnp.ones((n, 1), factor_dtype), nb=nb)
        # timed: the real verb, eagerly (host loop included); this
        # call also credits the flop ledger through the api wrapper
        x, info, iters_ = verb(A, B, factor_dtype=factor_dtype)
        jax.block_until_ready(x.data)
        t0 = time.perf_counter()
        x, info, iters_ = verb(A, B, factor_dtype=factor_dtype)
        jax.block_until_ready(x.data)
        secs = time.perf_counter() - t0
        iters = max(abs(int(iters_)), 1)
        # component programs, analyzed at the same (n, nb)
        if name == "posv_mixed":
            f_pc = obs_costs.program_costs(jax.jit(
                lambda ad: st.chol_factor(A_lo.with_data(ad))[0].data
            ).lower(A_lo.data).compile())
            L_lo, _ = st.chol_factor(A_lo)
            s_pc = obs_costs.program_costs(jax.jit(
                lambda ld, bd: st.chol_solve_using_factor(
                    L_lo.with_data(ld), B_lo.with_data(bd)).data
            ).lower(L_lo.data, B_lo.data).compile())
        else:
            f_pc = obs_costs.program_costs(jax.jit(
                lambda ad: st.lu_factor(A_lo.with_data(ad))[0].data
            ).lower(A_lo.data).compile())
            LU_lo, perm_lo, _ = st.lu_factor(A_lo)
            s_pc = obs_costs.program_costs(jax.jit(
                lambda ld, bd: st.lu_solve_using_factor(
                    LU_lo.with_data(ld), perm_lo,
                    B_lo.with_data(bd)).data
            ).lower(LU_lo.data, B_lo.data).compile())
        g_pc = obs_costs.program_costs(jax.jit(
            lambda ad, xd, bd: st.gemm(
                -1.0, A.with_data(ad), B.with_data(xd), 1.0,
                B.with_data(bd)).data
        ).lower(A.data, B.data, B.data).compile())
        comp = [f_pc, s_pc, g_pc]
        if any(pc.bytes_accessed is None for pc in comp):
            bytes_mixed = None
        else:
            bytes_mixed = (f_pc.bytes_accessed
                           + (iters + 1) * s_pc.bytes_accessed
                           + iters * g_pc.bytes_accessed)
        obs_costs.BYTES.record(name, bytes_mixed or 0.0)
        row = obs_roofline.roofline_row(
            name, fl, bytes_mixed, secs, None, machine)
        row["factor_dtype"] = str(jnp.dtype(factor_dtype))
        row["working_dtype"] = str(jnp.dtype(dtype))
        row["refine_iters"] = int(iters_)
        row["factor_bytes_lo"] = f_pc.bytes_accessed
        row["factor_intensity_lo"] = obs_roofline.intensity(
            model_flops.potrf(n) if name == "posv_mixed"
            else model_flops.getrf(n), f_pc.bytes_accessed)
        # the uniform-precision factor at the working dtype — the
        # baseline the intensity shift is measured against
        w_pc = _single_call_costs(
            "potrf" if name == "posv_mixed" else "getrf", n, nb,
            dtype=dtype)
        row["factor_bytes_working"] = w_pc.bytes_accessed
        row["factor_intensity_working"] = obs_roofline.intensity(
            model_flops.potrf(n) if name == "posv_mixed"
            else model_flops.getrf(n), w_pc.bytes_accessed)
        rows.append(row)
        ai = row["intensity"]
        print(f"# roofline {name}  n={n} (bf16 factor): "
              + (f"intensity {ai:.1f} flop/B, factor "
                 f"{row['factor_intensity_lo']:.1f} vs "
                 f"{row['factor_intensity_working']:.1f} flop/B "
                 f"uniform, iters={int(iters_)}"
                 if ai is not None else "bytes unavailable"),
              file=sys.stderr)
    # the ledger-side join: intensity columns for the mixed verbs as
    # gflops_report renders them (flops credited by the instrumented
    # api wrappers ÷ the bytes credited above)
    report = LEDGER.gflops_report().get("per_op", {})
    mixed_report = {k: v for k, v in report.items()
                    if k in ("gesv_mixed", "posv_mixed")}
    return {"rows": rows, "gflops_report": mixed_report}


def _served_mixed_roofline_rows(n, nb, dtype=jnp.float32,
                                factor_dtype="bfloat16", requests=6):
    """Round 13: the MEASURED successor of _mixed_roofline_rows'
    composed lower bound — a serving Session with a refined resident
    (register(..., refine=...)) serves a small workload, and the rows
    come from the ANALYZED programs the refine/ engine actually
    executed (Session.cost_log: the low-precision factor, the
    refine_start initial solve, the refine_step residual+apply), with
    the per-execution bytes the ledger credited and the measured
    iteration count. No composition estimate: these are the programs
    a production mixed serve runs, at their true bytes."""
    import numpy as np

    import slate_tpu as st
    from slate_tpu.refine import RefinePolicy
    from slate_tpu.runtime import Session

    rng = np.random.default_rng(31)
    rows = []
    for op in ("chol", "lu"):
        base = rng.standard_normal((n, n)).astype(np.dtype(dtype))
        if op == "chol":
            dense = base @ base.T + n * np.eye(n, dtype=np.dtype(dtype))
            A = st.hermitian(np.tril(dense), nb=nb, uplo=st.Uplo.Lower)
            model_factor = model_flops.potrf(n)
        else:
            dense = base + n * np.eye(n, dtype=np.dtype(dtype))
            A = st.from_dense(dense, nb=nb)
            model_factor = model_flops.getrf(n)
        sess = Session()
        h = sess.register(A, op=op,
                          refine=RefinePolicy(factor_dtype=factor_dtype))
        sess.warmup(h)
        for i in range(requests):
            sess.solve(h, rng.standard_normal(n).astype(np.dtype(dtype)))
        snap = sess.metrics.snapshot()
        hist = snap["histograms"].get("refine_iterations", {})
        by_what = {}
        for r in sess.cost_log:
            by_what.setdefault(r["what"], r)
        frow = by_what.get("factor", {})
        srow = by_what.get("refine_step", {})
        row = {
            "op": op, "n": n, "nb": nb,
            "working_dtype": str(jnp.dtype(dtype)),
            "factor_dtype": factor_dtype,
            "iters_mean": hist.get("mean") or 0.0,
            "factor_bytes_measured": frow.get("bytes_accessed"),
            "factor_intensity_measured": obs_roofline.intensity(
                model_factor, frow.get("bytes_accessed")),
            "step_bytes_measured": srow.get("bytes_accessed"),
            "step_model_flops": srow.get("model_flops"),
            "step_intensity_measured": obs_roofline.intensity(
                srow.get("model_flops") or 0.0,
                srow.get("bytes_accessed")),
            # the serve-side ledger split (useful vs refinement) as a
            # production scrape would read it
            "serve_refine_flops": sess.metrics.get("refine_flops_total"),
            "serve_solve_flops": sess.metrics.get("solve_flops_total"),
            "refine_fallbacks": sess.metrics.get(
                "refine_fallbacks_total"),
        }
        rows.append(row)
        fi = row["factor_intensity_measured"]
        print(f"# roofline served-mixed {op} n={n}: factor intensity "
              + (f"{fi:.1f} flop/B" if fi is not None else "n/a")
              + f" (measured), iters {row['iters_mean']:.1f}, "
              f"refine/useful flops "
              f"{row['serve_refine_flops']:.3g}/"
              f"{row['serve_solve_flops']:.3g}", file=sys.stderr)
    return rows


def _roofline_rows(n, model_fl, seconds):
    """One roofline row per headline verb: model flops ÷ XLA
    bytes-accessed (single-call program) joined with the measured
    per-iteration seconds; machine roofs from SLATE_TPU_PEAK_GFLOPS /
    SLATE_TPU_HBM_GBPS when set (obs/roofline.py). The analyzed
    program is built at the SAME nb the timed bench_* function used —
    tile size changes bytes-accessed and temp HBM, so mixing tilings
    would join one program's seconds with another's bytes."""
    bench_nb = {"gemm": 512}  # bench_gemm default; factor verbs: 1024
    machine = obs_roofline.MachineModel.from_env()
    rows = []
    for name, secs in seconds.items():
        pc = _single_call_costs(name, n, bench_nb.get(name, 1024))
        row = obs_roofline.roofline_row(
            name, model_fl[name], pc.bytes_accessed, secs,
            pc.collective_bytes or None, machine)
        row["xla_flops"] = pc.flops
        row["temp_bytes"] = pc.temp_bytes
        row["peak_bytes"] = pc.peak_bytes
        rows.append(row)
        ai = row["intensity"]
        print(f"# roofline {name}  n={n}: intensity "
              f"{ai:.1f} flop/B" if ai is not None else
              f"# roofline {name}  n={n}: bytes unavailable",
              file=sys.stderr)
    return rows


def main():
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("n", nargs="?", type=int, default=16384)
    ap.add_argument("--phases", action="store_true",
                    help="also run the factorization phase timer "
                         "(pivot term + trailing-copy term, "
                         "before/after the round-6 fast paths)")
    ap.add_argument("--phases-n", type=int, default=None,
                    help="size for the phase timer (default: min(n, "
                         "1024) so the CPU smoke stays cheap)")
    ap.add_argument("--eig-n", type=int, default=None,
                    help="comma-free single size for the heev/svd rows "
                         "(default: 8192 and 16384 on TPU, min(n, 256) "
                         "elsewhere); 0 disables the rows")
    ap.add_argument("--out", default=None,
                    help="also write the full JSON object to this file "
                         "(BENCH_*.json artifact, schema per PERF_HISTORY.md)")
    ap.add_argument("--tuning", default=None, nargs="?",
                    const="TUNING_r01.json", metavar="PATH",
                    help="measure through a tuning table (round 21): "
                         "activates PATH (bare flag: the committed "
                         "TUNING_r01.json) process-globally — the "
                         "batched small engine resolves nb/quantum "
                         "through it — and applies each dense op's "
                         "resolved inner_blocking/lookahead to its "
                         "bench; provenance recorded in the artifact")
    args = ap.parse_args()

    from slate_tpu.compat.platform import enable_compile_cache

    enable_compile_cache()
    # default raised 8192 → 16384 in round 3: the serial panel floor
    # amortizes with n (VERDICT r2 #3 asks for BASELINE-scale numbers);
    # 16384 is the largest size where gemm's 4 live operands fit the
    # 16 GiB of one v5e chip (n=32768 factorization-only numbers are in
    # PERF_HISTORY.md — a 32768² fp32 gemm needs ~70 GiB of operands)
    n = args.n
    # round 21: measure through a tuning table — activate it (the
    # batched small engine resolves through the process-global seam)
    # and resolve each dense op's Options up front; provenance lands
    # in the artifact so a tuned number can never masquerade as a
    # default-config one
    tuned_opts, tuned_prov = {}, {}
    if args.tuning:
        from slate_tpu import tuning as tn
        from slate_tpu.core.types import Options
        table = tn.TuningTable.from_path(args.tuning)
        tn.activate_table(table)
        backend = jax.default_backend()
        for opn in ("chol", "lu", "qr"):
            cfg = table.resolve(opn, n, "float32", backend)
            if cfg is not None:
                tuned_opts[opn] = cfg.apply(Options())
                tuned_prov[opn] = cfg.label()
        print(f"# tuning table {args.tuning}: resolved "
              f"{tuned_prov or 'nothing for this platform/size'}",
              file=sys.stderr)
    gemm_gflops, gemm_t = bench_gemm(n=n)
    print(f"# gemm   n={n} fp32: {gemm_gflops:9.1f} GFLOP/s  ({gemm_t*1e3:.1f} ms/iter)",
          file=sys.stderr)
    extra = {}
    # measured per-iter seconds per verb, for the --phases roofline join
    routine_secs = {"gemm": gemm_t}
    gemm_hi, t_hi = bench_gemm(n=n, precision="high")
    extra["gemm_high_gflops"] = round(gemm_hi, 1)
    print(f"# gemm(high) n={n}: {gemm_hi:9.1f} GFLOP/s  "
          f"({t_hi*1e3:.1f} ms/iter) — same precision budget as the "
          "factorizations", file=sys.stderr)
    op_of = {"potrf": "chol", "getrf": "lu", "geqrf": "qr"}
    for name, fn in (("potrf", bench_potrf), ("getrf", bench_getrf),
                     ("getrf_calu", bench_getrf_calu),
                     ("geqrf", bench_geqrf)):
        kw = {}
        if tuned_opts.get(op_of.get(name)) is not None:
            kw["opts"] = tuned_opts[op_of[name]]
        gflops, t = fn(n=n, **kw)
        routine_secs[name] = t
        extra[f"{name}_gflops"] = round(gflops, 1)
        extra[f"{name}_pct_of_gemm"] = round(100 * gflops / gemm_gflops, 1)
        extra[f"{name}_pct_of_gemm_high"] = round(
            100 * gflops / gemm_hi, 1)
        print(f"# {name}  n={n} fp32: {gflops:9.1f} GFLOP/s  "
              f"({t*1e3:.1f} ms/iter, {100*gflops/gemm_gflops:.0f}% of "
              f"gemm rate"
              f", {100*gflops/gemm_hi:.0f}% of gemm-high)",
              file=sys.stderr)

    # heev/svd rows (round 6): slope-timed, with stage decomposition.
    # On TPU the recorded configs are n=8192/16384 (BASELINE.md target
    # list); elsewhere a small-n smoke keeps the mechanism exercised.
    on_tpu = jax.default_backend() == "tpu"
    if args.eig_n == 0:
        eig_ns = []
    elif args.eig_n:
        eig_ns = [args.eig_n]
    else:
        eig_ns = ([8192, 16384] if on_tpu and n >= 16384
                  else [min(n, 8192)] if on_tpu else [min(n, 256)])
    eig_nb = 1024 if on_tpu else 64
    for ename, fn in (("heev", bench_heev), ("svd", bench_svd)):
        rows = []
        for en in eig_ns:
            row = fn(n=en, nb=min(eig_nb, en))
            rows.append(row)
            print(f"# {ename}  n={en}: vals {row['values_gflops']} "
                  f"GFLOP/s ({row['values_s']} s), vecs "
                  f"{row['vectors_gflops']} GFLOP/s "
                  f"({row['vectors_s']} s), dominant stage: "
                  f"{row['dominant_stage']}", file=sys.stderr)
        if rows:
            extra[ename] = rows

    if args.phases:
        pn = args.phases_n or min(n, 1024)
        pnb = max(64, min(1024, pn // 4))
        extra["factor_phases"] = bench_factor_phases(n=pn, nb=pnb)
        print(f"# phases n={pn} nb={pnb}: "
              f"{json.dumps(extra['factor_phases'])}", file=sys.stderr)
        # roofline rows (round 9): model flops ÷ XLA bytes-accessed per
        # verb, with the measured rate beside the attainable one when a
        # machine model is configured (obs/roofline.py)
        model_fl = {
            "gemm": model_flops.gemm(n, n, n),
            "potrf": model_flops.potrf(n),
            "getrf": model_flops.getrf(n),
            "getrf_calu": model_flops.getrf(n),
            "geqrf": model_flops.geqrf(n, n),
        }
        extra["roofline"] = _roofline_rows(n, model_fl, routine_secs)
        # mixed-precision intensity rows (round 11 satellite — ROADMAP
        # item 2): bf16-factor gesv_mixed/posv_mixed at the phase size
        extra["roofline_mixed"] = _mixed_roofline_rows(pn, pnb)
        # round 13: the measured per-execution rows from a SERVED
        # refined workload (the refine/ engine's analyzed programs) —
        # the composed lower bound above, replaced by the programs a
        # production mixed serve actually runs
        extra["roofline_mixed_served"] = _served_mixed_roofline_rows(
            min(pn, 256), min(pnb, 64))

    out = {
        "metric": f"gemm_gflops_per_chip_fp32_n{n}",
        "value": round(gemm_gflops, 1),
        "unit": "GFLOP/s",
        "vs_baseline": round(gemm_gflops / BASELINE_GFLOPS_PER_CHIP, 2),
        **extra,
    }
    if args.tuning:
        out["tuning"] = {"table": args.tuning, "resolved": tuned_prov}
    # the trajectory gate (tools/bench_gate.py) groups series by
    # the platform the run really used
    out["platform"] = jax.devices()[0].platform
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
        print(f"# artifact written to {args.out}", file=sys.stderr)
    print(json.dumps(out))


if __name__ == "__main__":
    try:
        main()
    except SystemExit:
        raise
    except Exception as e:  # one parseable JSON line, never a bare traceback
        print(json.dumps({
            "metric": "gemm_gflops_per_chip_fp32",
            "value": 0.0,
            "unit": "GFLOP/s",
            "vs_baseline": 0.0,
            "error": f"{type(e).__name__}: {e}",
        }))
        sys.exit(1)
