"""Many-small-problems engine (ISSUE 6 tentpole): batched tiled
factorizations/solves over [B, n, n] stacks, the pow2 batch-bucket
program cache, the api verbs' B×model ledger crediting, and the
Batcher's distinct-operator grouped dispatch.

The load-bearing invariant everywhere: the hand-batched kernels'
arithmetic is batch-independent, so a batched program's per-item lanes
are BIT-IDENTICAL to a loop of B=1 runs — which is what lets the
serving runtime swap per-request dispatch for one batched program per
bucket without changing a single bit of any response.
"""

import re

import numpy as np
import pytest

import slate_tpu as st
from slate_tpu.core.exceptions import SlateError
from slate_tpu.linalg import batched as lb
from slate_tpu.obs.flops import LEDGER, getrf as fl_getrf, \
    potrf as fl_potrf, geqrf as fl_geqrf, gels as fl_gels, solve_flops
from slate_tpu.runtime import Executor, Session

RNG = np.random.default_rng(1007)
# complex64 params of the cross-bucket sweeps carry the biggest compile
# bills and pin the few-ulp CPU caveat rather than the exact guarantee;
# they run under -m slow (tier-1 keeps c64 WITHIN-bucket exactness via
# test_bucket_padding_never_changes_bits)
C64_SLOW = pytest.param(np.complex64, marks=pytest.mark.slow)
DTYPES_FAST = [np.float32, np.float64, np.complex64]


def _stack(b, m, n, dtype):
    a = RNG.standard_normal((b, m, n)).astype(dtype)
    if np.issubdtype(dtype, np.complexfloating):
        a = (a + 1j * RNG.standard_normal((b, m, n))).astype(dtype)
    return a


def _spd_stack(b, n, dtype):
    a = _stack(b, n, n, dtype)
    return (a @ np.conj(np.swapaxes(a, 1, 2))
            + n * np.eye(n, dtype=dtype)).astype(dtype)


def _assert_lane_matches(dtype, got, want):
    """Cross-BUCKET lane comparison. Real dtypes: exact. Complex:
    XLA:CPU contracts the real mul/add pairs inside fused complex
    arithmetic into FMAs differently at different batch shapes (a
    single complex multiply reproduces it — NOT a reduction-order
    effect, and optimization_barrier does not stop it), so a c64 lane
    agrees with its B=1 run only to a few ulp across buckets. WITHIN a
    bucket program, complex lanes are exact too
    (test_bucket_padding_never_changes_bits). On TPU complex matmuls
    lower to real MXU pairs — this is a CPU-backend caveat, documented
    in PERF_HISTORY.md Round 10."""
    got, want = np.asarray(got), np.asarray(want)
    if np.issubdtype(dtype, np.complexfloating):
        eps = np.finfo(np.zeros(1, dtype).real.dtype).eps
        np.testing.assert_allclose(got, want, rtol=64 * eps,
                                   atol=64 * eps * np.abs(want).max())
    else:
        assert np.array_equal(got, want)


# -- bit-identity: batched vs loop of singles, across dtypes ---------------


@pytest.mark.parametrize("dtype", [pytest.param(
    np.float32, marks=pytest.mark.slow), np.float64, C64_SLOW])
def test_gesv_batched_bit_identical_to_singles(dtype):
    b, n = 5, 32                       # B=5: pads to the 8-bucket
    a = _stack(b, n, n, dtype)
    rhs = _stack(b, n, 2, dtype)
    x, info = lb.gesv_batched(a, rhs)
    assert np.all(np.asarray(info) == 0)
    for i in range(b):
        xi, _ = lb.gesv_batched(a[i:i + 1], rhs[i:i + 1])
        _assert_lane_matches(dtype, x[i], xi[0])
    # and it actually solves
    eps = np.finfo(np.zeros(1, dtype).real.dtype).eps
    resid = np.linalg.norm(a @ np.asarray(x) - rhs)
    assert resid / np.linalg.norm(rhs) < 100 * n * eps


@pytest.mark.parametrize("dtype", [pytest.param(
    np.float32, marks=pytest.mark.slow), np.float64, C64_SLOW])
def test_posv_batched_bit_identical_to_singles(dtype):
    b, n = 5, 32
    a = _spd_stack(b, n, dtype)
    rhs = _stack(b, n, 2, dtype)
    x, info = lb.posv_batched(a, rhs)
    assert np.all(np.asarray(info) == 0)
    for i in range(b):
        xi, _ = lb.posv_batched(a[i:i + 1], rhs[i:i + 1])
        _assert_lane_matches(dtype, x[i], xi[0])


@pytest.mark.parametrize("dtype", [pytest.param(
    np.float32, marks=pytest.mark.slow), np.float64, C64_SLOW])
def test_gels_batched_bit_identical_and_correct(dtype):
    b, m, n = 5, 48, 32
    a = _stack(b, m, n, dtype)
    rhs = _stack(b, m, 2, dtype)
    x, info = lb.gels_batched(a, rhs)
    assert np.all(np.asarray(info) == 0)
    for i in range(b):
        xi, _ = lb.gels_batched(a[i:i + 1], rhs[i:i + 1])
        _assert_lane_matches(dtype, x[i], xi[0])
    ref = np.stack([np.linalg.lstsq(a[i], rhs[i], rcond=None)[0]
                    for i in range(b)])
    tol = 1e-4 if np.dtype(dtype).itemsize <= 8 else 1e-10
    assert np.abs(np.asarray(x) - ref).max() < tol


@pytest.mark.parametrize("dtype", DTYPES_FAST)
def test_bucket_padding_never_changes_bits(dtype):
    # the same leading items through different paddings of the SAME
    # pow2 bucket: identical lanes for every dtype (one program, lanes
    # are independent — the padding cannot perturb a live lane)
    n = 32
    a = _stack(8, n, n, dtype)
    rhs = _stack(8, n, 2, dtype)
    x8, _ = lb.gesv_batched(a, rhs)                    # exact bucket
    x5, _ = lb.gesv_batched(a[:5], rhs[:5])            # padded 5 -> 8
    x6, _ = lb.gesv_batched(a[:6], rhs[:6])            # padded 6 -> 8
    assert np.array_equal(np.asarray(x8)[:5], np.asarray(x5))
    assert np.array_equal(np.asarray(x8)[:6], np.asarray(x6))
    # a DIFFERENT bucket (3 -> 4) is a different compiled shape: exact
    # for real dtypes, few-ulp for complex (see _assert_lane_matches)
    x3, _ = lb.gesv_batched(a[:3], rhs[:3])
    _assert_lane_matches(dtype, np.asarray(x8)[:3], np.asarray(x3))


def test_vector_rhs_matches_matrix_rhs_column():
    # [B, n] vectors go through the k>=2 pad internally and come back
    # rank-2; bits equal the same column solved as a [B, n, 1] stack
    n = 32
    a = _stack(4, n, n, np.float64)
    rhs = _stack(4, n, 1, np.float64)
    xm, _ = lb.gesv_batched(a, rhs)
    xv, _ = lb.gesv_batched(a, rhs[:, :, 0])
    assert xv.shape == (4, n)
    assert np.array_equal(np.asarray(xm)[:, :, 0], np.asarray(xv))


# -- factor/solve-using-factor drivers -------------------------------------


def test_getrf_getrs_batched_roundtrip():
    b, n = 4, 40
    a = _stack(b, n, n, np.float64)
    lu, perm, info = lb.getrf_batched(a)
    assert np.all(np.asarray(info) == 0)
    # gather semantics: a[perm] = L @ U per item
    lum = np.asarray(lu)
    l = np.tril(lum, -1) + np.eye(n)
    u = np.triu(lum)
    ap = np.take_along_axis(a, np.asarray(perm)[:, :, None], axis=1)
    assert np.abs(l @ u - ap).max() < 1e-10 * n
    rhs = _stack(b, n, 3, np.float64)
    x = lb.getrs_batched(lu, perm, rhs)
    assert np.abs(a @ np.asarray(x) - rhs).max() < 1e-9 * n
    # multi-panel (n > nb) batch-independence: lanes of the B=4 factor
    # equal a B=1 run bit-for-bit (the dtype sweep pins n=32 = one
    # panel; this is the blocked outer loop's pin)
    lu1, perm1, _ = lb.getrf_batched(a[1:2])
    assert np.array_equal(np.asarray(lu[1]), np.asarray(lu1[0]))
    assert np.array_equal(np.asarray(perm[1]), np.asarray(perm1[0]))


@pytest.mark.slow  # ~6 s (round-22 tier-1 budget); tier-1 sibling —
# the float64 arm of test_posv_batched_bit_identical_to_singles runs
# the same potrf_batched/potrs_batched pair lane-for-lane
def test_potrf_potrs_batched_roundtrip():
    b, n = 4, 40
    a = _spd_stack(b, n, np.float64)
    l, info = lb.potrf_batched(a)
    assert np.all(np.asarray(info) == 0)
    lm = np.asarray(l)
    assert np.abs(lm @ np.swapaxes(lm, 1, 2) - a).max() < 1e-10 * n
    rhs = _stack(b, n, 3, np.float64)
    x = lb.potrs_batched(l, rhs)
    assert np.abs(a @ np.asarray(x) - rhs).max() < 1e-9 * n


def test_geqrf_batched_factor_and_solve():
    b, m, n = 3, 48, 40
    a = _stack(b, m, n, np.float64)
    vr, taus, ts = lb.geqrf_batched(a)
    assert vr.shape == (b, m, n) and taus.shape == (b, n)
    # R's diagonal blocks live in the packed upper triangle
    r = np.triu(np.asarray(vr)[:, :n, :n])
    rhs = _stack(b, m, 2, np.float64)
    x = lb.gels_batched_using_factor(vr, taus, ts, rhs)
    ref = np.stack([np.linalg.lstsq(a[i], rhs[i], rcond=None)[0]
                    for i in range(b)])
    assert np.abs(np.asarray(x) - ref).max() < 1e-9
    # |diag R| matches numpy's QR up to sign
    rq = np.stack([np.abs(np.diag(np.linalg.qr(a[i], mode="r")))
                   for i in range(b)])
    assert np.abs(np.abs(np.diagonal(r, axis1=1, axis2=2)) - rq).max() \
        < 1e-9 * m


# -- per-item failure isolation --------------------------------------------


def test_singular_item_flags_itself_only():
    b, n = 5, 32
    a = _stack(b, n, n, np.float64)
    rhs = _stack(b, n, 2, np.float64)
    x_ref, _ = lb.gesv_batched(a, rhs)
    bad = a.copy()
    bad[2] = 0.0
    x, info = lb.gesv_batched(bad, rhs)
    info = np.asarray(info)
    assert info[2] != 0 and np.all(info[[0, 1, 3, 4]] == 0)
    for i in (0, 1, 3, 4):
        assert np.array_equal(np.asarray(x[i]), np.asarray(x_ref[i]))


@pytest.mark.slow  # ~8 s (round-10 headroom); per-item isolation stays
# tier-1 via the LU arm + the Batcher grouped-singular test
def test_non_spd_item_flags_itself_only():
    b, n = 4, 32
    a = _spd_stack(b, n, np.float64)
    rhs = _stack(b, n, 2, np.float64)
    x_ref, _ = lb.posv_batched(a, rhs)
    bad = a.copy()
    bad[1] = -bad[1]
    x, info = lb.posv_batched(bad, rhs)
    info = np.asarray(info)
    assert info[1] == 1 and np.all(info[[0, 2, 3]] == 0)
    for i in (0, 2, 3):
        assert np.array_equal(np.asarray(x[i]), np.asarray(x_ref[i]))


# -- pow2 bucket compilation + HLO structure -------------------------------


def test_bucket_compiles_once_per_pow2_bucket():
    lb.clear_programs()
    n = 32
    a = _stack(8, n, n, np.float32)
    rhs = _stack(8, n, 2, np.float32)
    lb.gesv_batched(a[:5], rhs[:5])        # 5 -> bucket 8: compile 1
    c1 = lb.bucket_stats()["compiles"]
    lb.gesv_batched(a[:6], rhs[:6])        # 6 -> bucket 8: cache hit
    lb.gesv_batched(a[:8], rhs[:8])        # 8 -> bucket 8: cache hit
    assert lb.bucket_stats()["compiles"] == c1
    lb.gesv_batched(a[:3], rhs[:3])        # 3 -> bucket 4: compile 2
    assert lb.bucket_stats()["compiles"] == c1 + 1


def test_batched_hlo_has_no_per_item_factorization_custom_call():
    # THE lowering claim (round 7's measurement, generalized): the
    # batched program must not contain per-item factorization custom
    # calls (a vmapped lax.linalg.lu lowers to a sequential per-item
    # custom-call loop). Batch parallelism lives inside fused ops.
    lb.clear_programs()
    n = 32
    a = _stack(4, n, n, np.float32)
    rhs = _stack(4, n, 2, np.float32)
    lb.gesv_batched(a, rhs)
    texts = lb.bucket_hlo("gesv_batched")
    assert texts, "expected a cached batched program"
    pat = re.compile(r"custom-call.*(getrf|potrf|geqrf|lu|cholesky)",
                     re.IGNORECASE)
    for t in texts:
        assert not pat.search(t)


# -- api verbs: B x model ledger crediting ---------------------------------


def test_api_batched_gesv_credits_b_times_model():
    """Tier-1 sibling of the 4-verb sweep below (round-22 budget):
    one verb pins the B x model-formula crediting contract."""
    b, n, k = 3, 16, 2
    LEDGER.reset()
    st.gesv_batched(_stack(b, n, n, np.float32), _stack(b, n, k,
                                                        np.float32))
    assert LEDGER.snapshot()["per_op"]["gesv_batched"] == b * (
        fl_getrf(n) + solve_flops("lu", n, n, k))


@pytest.mark.slow  # ~8 s: four verb compiles (round-22 tier-1
# budget); tier-1 siblings — test_api_batched_gesv_credits_b_times_model
# (the B x model contract) and test_api_batched_verbs_validate_shapes
# (the API surface)
def test_api_batched_verbs_credit_b_times_model():
    b, m, n, k = 3, 24, 16, 2
    LEDGER.reset()
    a = _stack(b, n, n, np.float32)
    rhs = _stack(b, n, k, np.float32)
    st.gesv_batched(a, rhs)
    st.posv_batched(_spd_stack(b, n, np.float32), rhs)
    ta = _stack(b, m, n, np.float32)
    st.geqrf_batched(ta)
    st.gels_batched(ta, _stack(b, m, k, np.float32))
    per_op = LEDGER.snapshot()["per_op"]
    assert per_op["gesv_batched"] == b * (
        fl_getrf(n) + solve_flops("lu", n, n, k))
    assert per_op["posv_batched"] == b * (
        fl_potrf(n) + solve_flops("chol", n, n, k))
    assert per_op["geqrf_batched"] == b * fl_geqrf(m, n)
    assert per_op["gels_batched"] == b * fl_gels(m, n)


def test_api_batched_verbs_validate_shapes():
    with pytest.raises(SlateError):
        st.gesv_batched(np.zeros((4, 4)), np.zeros((4, 1)))  # no batch dim
    with pytest.raises(SlateError):
        st.gels_batched(np.zeros((2, 3, 8)), np.zeros((2, 3, 1)))  # m < n


# -- api mixed-precision verbs (satellite: ROADMAP item 2 first step) ------


def test_api_mixed_verbs_surface_iters_and_credit_ledger():
    n, nb = 32, 16
    a = RNG.standard_normal((n, n))
    spd = a @ a.T + n * np.eye(n)
    A = st.hermitian(np.tril(spd), nb=nb, uplo=st.Uplo.Lower)
    B = st.from_dense(RNG.standard_normal((n, 2)), nb=nb)
    LEDGER.reset()
    X, info, iters = st.api.posv_mixed(A, B)
    assert int(info) == 0 and isinstance(iters, int) and iters > 0
    assert np.abs(spd @ X.to_numpy() - B.to_numpy()).max() < 1e-10 * n
    per_op = LEDGER.snapshot()["per_op"]
    assert per_op["posv_mixed"] > 0


@pytest.mark.slow
def test_api_gesv_mixed_surfaces_iters():
    n, nb = 32, 16
    a = RNG.standard_normal((n, n))
    B = st.from_dense(RNG.standard_normal((n, 2)), nb=nb)
    LEDGER.reset()
    Ag = st.from_dense(a + n * np.eye(n), nb=nb)
    X2, info2, iters2 = st.api.gesv_mixed(Ag, B)
    assert int(info2) == 0 and iters2 > 0
    assert LEDGER.snapshot()["per_op"]["gesv_mixed"] > 0


@pytest.mark.slow
def test_api_mixed_gmres_verbs_surface_iters():
    n, nb = 32, 16
    a = RNG.standard_normal((n, n))
    spd = a @ a.T + n * np.eye(n)
    A = st.hermitian(np.tril(spd), nb=nb, uplo=st.Uplo.Lower)
    B = st.from_dense(RNG.standard_normal((n, 2)), nb=nb)
    LEDGER.reset()
    Ag = st.from_dense(a + n * np.eye(n), nb=nb)
    X3, info3, iters3 = st.api.gesv_mixed_gmres(Ag, B)
    assert int(info3) == 0 and iters3 > 0
    X4, info4, iters4 = st.api.posv_mixed_gmres(A, B)
    assert int(info4) == 0 and iters4 > 0
    per_op = LEDGER.snapshot()["per_op"]
    for verb in ("gesv_mixed_gmres", "posv_mixed_gmres"):
        assert per_op[verb] > 0


# -- serving: Session small ops + Batcher grouped dispatch ------------------


def _ops_and_rhs(nops=6, n=32, dtype=np.float32, spd=False):
    if spd:
        mats = [m for m in _spd_stack(nops, n, dtype)]
    else:
        mats = [m for m in _stack(nops, n, n, dtype)]
    rhs = [RNG.standard_normal(n).astype(dtype) for _ in range(nops)]
    return mats, rhs


def test_session_small_op_per_request_solve():
    mats, rhs = _ops_and_rhs(2)
    sess = Session()
    h = sess.register(mats[0])            # auto -> lu_small
    assert sess.small_group_key(h) == ("lu_small", 32, "float32")
    x = sess.solve(h, rhs[0])
    assert np.abs(mats[0] @ x - rhs[0]).max() < 1e-2
    # factor is resident now; a second solve hits
    sess.solve(h, rhs[1])
    snap = sess.metrics.snapshot()["counters"]
    assert snap["cache_hits"] == 1 and snap["cache_misses"] == 1
    with pytest.raises(SlateError):
        sess.solve_matrix(h, st.from_dense(rhs[0][:, None], nb=16))


def test_session_register_small_validation():
    sess = Session()
    with pytest.raises(SlateError):
        sess.register(np.zeros((4, 6)))               # not square
    with pytest.raises(SlateError):
        sess.register(np.zeros((4, 4)), op="lu")      # dense op, array
    with pytest.raises(SlateError):                   # small op, matrix
        sess.register(st.from_dense(np.eye(8), nb=4), op="lu_small")


@pytest.mark.parametrize("op,spd", [
    ("lu_small", False),
    pytest.param("chol_small", True, marks=pytest.mark.slow)])
def test_batcher_grouped_dispatch_bit_identical_to_per_request(op, spd):
    mats, rhs = _ops_and_rhs(6, spd=spd)
    # per-request reference: each request solved alone
    s_ref = Session()
    h_ref = [s_ref.register(m, op=op) for m in mats]
    ref = [s_ref.solve(h, b) for h, b in zip(h_ref, rhs)]
    # grouped: distinct operators coalesce into ONE bucket per shape
    sess = Session()
    hs = [sess.register(m, op=op) for m in mats]
    with Executor(sess, max_batch=16, max_wait=0.05) as ex:
        futs = [ex.submit(h, b) for h, b in zip(hs, rhs)]
        xs = [f.result(timeout=120) for f in futs]
    for a, b in zip(ref, xs):
        assert np.array_equal(a, b)      # cold: batched factor + solve
    with Executor(sess, max_batch=16, max_wait=0.05) as ex:
        futs = [ex.submit(h, b) for h, b in zip(hs, rhs)]
        xs2 = [f.result(timeout=120) for f in futs]
    for a, b in zip(ref, xs2):
        assert np.array_equal(a, b)      # warm: stacked resident solve
    c = sess.metrics.snapshot()["counters"]
    # cold bucket = 2 batched programs (factor the misses + solve all),
    # warm bucket = 1 (solve only); 6 misses then 6 hits
    assert c["batched_programs"] == 3
    assert c["cache_misses"] == 6 and c["cache_hits"] == 6
    occ = sess.metrics.snapshot()["histograms"]["bucket_occupancy"]
    assert occ["count"] == 2 and abs(occ["mean"] - 6 / 8) < 1e-9


def test_batcher_grouped_singular_item_fails_only_its_future():
    mats, rhs = _ops_and_rhs(5)
    ref = [Session() for _ in mats]
    h_ref = [s.register(m) for s, m in zip(ref, mats)]
    ref_x = [s.solve(h, b) for s, h, b in zip(ref, h_ref, rhs)]
    mats[2] = np.zeros_like(mats[2])
    sess = Session()
    hs = [sess.register(m) for m in mats]
    with Executor(sess, max_batch=16, max_wait=0.05) as ex:
        futs = [ex.submit(h, b) for h, b in zip(hs, rhs)]
        outs = []
        for f in futs:
            try:
                outs.append(f.result(timeout=120))
            except SlateError as e:
                outs.append(e)
    assert isinstance(outs[2], SlateError) and "info" in str(outs[2])
    for i in (0, 1, 3, 4):
        assert np.array_equal(outs[i], ref_x[i])


def test_batcher_same_operator_requests_still_batch():
    # N requests against ONE small operator: grouped dispatch stacks
    # the same resident factor N times — still one program, still
    # bit-identical to per-request
    mats, rhs = _ops_and_rhs(1)
    s_ref = Session()
    h0 = s_ref.register(mats[0])
    ref = [s_ref.solve(h0, b) for b in rhs * 3]
    sess = Session()
    h = sess.register(mats[0])
    with Executor(sess, max_batch=8, max_wait=0.05) as ex:
        futs = [ex.submit(h, b) for b in rhs * 3]
        xs = [f.result(timeout=120) for f in futs]
    for a, b in zip(ref, xs):
        assert np.array_equal(a, b)
    c = sess.metrics.snapshot()["counters"]
    assert c["batches_total"] == 1
    # duplicate-handle tallies must match B sequential per-request
    # solves: 1 miss (the first cold request) + 2 hits (review fix)
    assert c["cache_misses"] == 1 and c["cache_hits"] == 2


def test_session_warmup_small_op_primes_bucket_programs():
    from slate_tpu.obs.costs import BYTES
    mats, rhs = _ops_and_rhs(1)
    sess = Session()
    h = sess.register(mats[0])
    before = BYTES.snapshot()["per_op"].get("getrs_batched")
    sess.warmup(h)
    # the zero-rhs warmup PROBE populates the solve bucket program but
    # must not credit the bytes ledger as served traffic (review fix;
    # the factor is real cached work and IS credited)
    assert BYTES.snapshot()["per_op"].get("getrs_batched") == before
    c0 = lb.bucket_stats()["compiles"]
    x = sess.solve(h, rhs[0])           # must hit the primed programs
    assert lb.bucket_stats()["compiles"] == c0
    assert np.abs(mats[0] @ x - rhs[0]).max() < 1e-2
    assert BYTES.snapshot()["per_op"].get("getrs_batched") != before


def test_public_mixed_verbs_are_instrumented_wrappers():
    # st.gesv_mixed must be the api wrapper (flop-ledger crediting like
    # every public verb), not the raw linalg driver (review fix)
    from slate_tpu.linalg import lu as lu_mod
    assert st.gesv_mixed is st.api.gesv_mixed
    assert st.posv_mixed is st.api.posv_mixed
    assert st.gesv_mixed_gmres is st.api.gesv_mixed_gmres
    assert st.posv_mixed_gmres is st.api.posv_mixed_gmres
    assert st.gesv_mixed is not lu_mod.gesv_mixed


def test_bucket_hlo_filters_by_batch_and_n():
    # the bench's per-row structural flag asserts about the ROW's own
    # bucket program — the filter must single it out (review fix)
    lb.clear_programs()
    n = 32
    a = _stack(3, n, n, np.float32)
    rhs = _stack(3, n, 2, np.float32)
    lb.gesv_batched(a, rhs)              # 3 -> bucket 4
    assert len(lb.bucket_hlo("gesv_batched", batch=4, n=n)) == 1
    assert lb.bucket_hlo("gesv_batched", batch=8, n=n) == []
    assert lb.bucket_hlo("gesv_batched", batch=4, n=64) == []
