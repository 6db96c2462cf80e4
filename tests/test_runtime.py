"""Serving runtime (slate_tpu.runtime): resident-factor Session with the
HBM-budget LRU cache, request batcher, async executor, and metrics.

Reference analog: the tester's persistent-matrix amortization via
``*_solve_using_factor`` (include/slate/simplified_api.hh) — here grown
into a serving subsystem, so the tests check serving semantics: cache
hit/evict-under-budget, batched == per-request bit-identity, counters,
and future resolution under concurrent submits. All CPU-mesh, tier-1.
"""

import threading

import numpy as np
import pytest

import slate_tpu as st
from slate_tpu.core.exceptions import SlateError
from slate_tpu.linalg.band_packed import pb_pack
from slate_tpu.runtime import Batcher, Executor, Metrics, Session

RNG = np.random.default_rng(11)
N, NB = 64, 32


def _spd(n=N, dtype=np.float64):
    a = RNG.standard_normal((n, n)).astype(dtype)
    return a @ a.T + n * np.eye(n, dtype=dtype)


def _chol_handle(sess, n=N):
    spd = _spd(n)
    A = st.hermitian(np.tril(spd), nb=NB, uplo=st.Uplo.Lower)
    return sess.register(A, op="chol"), spd


def _lu_handle(sess, n=N):
    a = RNG.standard_normal((n, n)) + n * np.eye(n)
    return sess.register(st.from_dense(a, nb=NB), op="lu"), a


# -- Session: cache semantics ----------------------------------------------


def test_cache_hit_then_refactor_on_miss():
    sess = Session()
    h, spd = _chol_handle(sess)
    b = RNG.standard_normal(N)
    x1 = sess.solve(h, b)
    assert np.abs(spd @ x1 - b).max() < 1e-8
    x2 = sess.solve(h, b)
    assert np.array_equal(x1, x2)
    assert sess.metrics.get("cache_misses") == 1
    assert sess.metrics.get("cache_hits") == 1
    assert sess.metrics.get("factors_total") == 1
    # explicit eviction forces a refactor on the next solve
    assert sess.evict(h)
    x3 = sess.solve(h, b)
    assert np.abs(spd @ x3 - b).max() < 1e-8
    assert sess.metrics.get("cache_misses") == 2
    assert sess.metrics.get("evictions") == 1


def test_lru_eviction_respects_hbm_budget():
    sess = Session()
    handles = [_chol_handle(sess)[0] for _ in range(3)]
    b = RNG.standard_normal(N)
    sess.solve(handles[0], b)
    per_factor = sess.factor(handles[0]).nbytes
    assert per_factor > 0
    # budget fits exactly two factors
    sess.hbm_budget = 2 * per_factor
    for h in handles[1:]:
        sess.solve(h, b)
    assert sess.cached_bytes <= sess.hbm_budget
    # LRU order: the first operator was least recently used → evicted
    assert sess.cached_handles() == handles[1:]
    assert sess.metrics.get("evictions") == 1
    # refactor-on-miss brings it back, evicting the now-LRU second one
    sess.solve(handles[0], b)
    assert sess.cached_handles() == [handles[2], handles[0]]
    assert sess.cached_bytes <= sess.hbm_budget


def test_single_factor_over_budget_is_kept():
    sess = Session(hbm_budget=1)  # nothing fits
    h, spd = _chol_handle(sess)
    b = RNG.standard_normal(N)
    x = sess.solve(h, b)
    assert np.abs(spd @ x - b).max() < 1e-8
    assert len(sess.cached_handles()) == 1  # kept despite the budget
    assert sess.metrics.get("budget_overflows") == 1


def test_unknown_handle_and_reregister():
    sess = Session()
    with pytest.raises(SlateError):
        sess.solve("nope", np.zeros(N))
    h, _ = _lu_handle(sess)
    with pytest.raises(SlateError):
        sess.register(st.from_dense(np.eye(N), nb=NB), handle=h)
    sess.unregister(h)
    assert h not in sess
    # wide operators are rejected at registration (no LQ-resident path)
    with pytest.raises(SlateError):
        sess.register(st.from_dense(RNG.standard_normal((32, 64)), nb=16),
                      op="auto")
    # auto-allocated handles skip caller-chosen integers
    sess2 = Session()
    h1 = sess2.register(st.from_dense(np.eye(N), nb=NB), handle=1)
    h2 = sess2.register(st.from_dense(2 * np.eye(N), nb=NB))
    assert h1 == 1 and h2 != 1 and h2 in sess2


def test_per_operator_opts_not_shared():
    from slate_tpu.core.types import Options
    sess = Session()
    a = RNG.standard_normal((N, N)) + N * np.eye(N)
    h1 = sess.register(st.from_dense(a, nb=NB), op="lu")
    h2 = sess.register(st.from_dense(a, nb=NB), op="lu",
                       opts=Options(update_precision="highest"))
    assert sess._solve_fn(sess._ops[h1]) is not sess._solve_fn(
        sess._ops[h2])  # distinct closures: opts are part of the key
    b = RNG.standard_normal(N)
    for h in (h1, h2):
        assert np.abs(a @ sess.solve(h, b) - b).max() < 1e-8


# -- Session: operator kinds -----------------------------------------------


def test_qr_and_band_operators():
    sess = Session()
    # overdetermined least squares via resident QR
    m, n = 96, 48
    a = RNG.standard_normal((m, n))
    hq = sess.register(st.from_dense(a, nb=NB), op="auto")
    assert sess._ops[hq].op == "qr"
    b = RNG.standard_normal(m)
    x = sess.solve(hq, b)
    assert x.shape == (n,)
    # least-squares optimality: residual orthogonal to range(A)
    assert np.abs(a.T @ (a @ x - b)).max() < 1e-8
    # Hermitian positive-definite band via packed storage
    nb_, kd = 64, 3
    spd_band = np.tril(np.triu(_spd(nb_), -kd), kd)
    hb = sess.register(pb_pack(spd_band, kd), op="auto")
    assert sess._ops[hb].op == "band_chol"
    bb = RNG.standard_normal(nb_)
    xb = sess.solve(hb, bb)
    assert np.abs(spd_band @ xb - bb).max() < 1e-8


# -- Batching --------------------------------------------------------------


def test_batched_bucket_bit_matches_individual():
    """Acceptance: a batched bucket of K same-shape solves is identical
    to K individual *_solve_using_factor calls."""
    sess = Session()
    h, a = _lu_handle(sess)
    bs = [RNG.standard_normal(N) for _ in range(6)]
    individual = [sess.solve(h, b) for b in bs]
    # the individual path IS lu_solve_using_factor on the resident factor:
    res = sess.factor(h)
    direct = st.lu_solve_using_factor(
        res.payload[0], res.payload[1], st.from_dense(bs[0][:, None], nb=NB))
    np.testing.assert_allclose(direct.to_numpy()[:, 0], individual[0],
                               rtol=0, atol=1e-12)
    batcher = Batcher(sess, max_batch=8, max_wait=10.0)
    futs = [batcher.submit(h, b) for b in bs]
    batcher.flush()
    batched = [f.result(timeout=0) for f in futs]
    for ind, bat in zip(individual, batched):
        assert np.array_equal(ind, bat)  # bit-identical, not just close
    assert sess.metrics.get("batches_total") == 1
    # bucketing: different shapes never coalesce
    f1 = batcher.submit(h, RNG.standard_normal(N))
    f2 = batcher.submit(h, RNG.standard_normal((N, 2)))
    batcher.flush()
    assert f1.result(timeout=0).shape == (N,)
    assert f2.result(timeout=0).shape == (N, 2)
    assert sess.metrics.get("batches_total") == 3


def test_batcher_max_batch_splits():
    sess = Session()
    h, _ = _lu_handle(sess)
    batcher = Batcher(sess, max_batch=4, max_wait=10.0)
    futs = [batcher.submit(h, RNG.standard_normal(N)) for _ in range(10)]
    ready = batcher.pop_ready()  # two full buckets ready before deadline
    assert [len(r) for _, r in ready] == [4, 4]
    for key, reqs in ready:
        batcher.run(key, reqs)
    batcher.flush()  # deadline-flush the remaining 2
    assert all(f.result(timeout=0).shape == (N,) for f in futs)


# -- Executor --------------------------------------------------------------


def test_executor_futures_under_concurrent_submits():
    sess = Session()
    h, spd = _chol_handle(sess)
    sess.warmup(h)
    bs = [RNG.standard_normal(N) for _ in range(24)]
    results = [None] * len(bs)
    with Executor(sess, max_batch=8, max_wait=1e-3) as ex:
        def client(lo, hi):
            futs = [(i, ex.submit(h, bs[i])) for i in range(lo, hi)]
            for i, f in futs:
                results[i] = f.result(timeout=60)
        threads = [threading.Thread(target=client, args=(i * 8, (i + 1) * 8))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    for b, x in zip(bs, results):
        assert x is not None
        assert np.abs(spd @ x - b).max() < 1e-8
    m = sess.metrics
    assert m.get("requests_total") == 24
    assert m.get("solves_total") == 24
    # batching actually coalesced (fewer dispatches than requests)
    assert m.get("batches_total") < 24


def test_executor_deadline_flush_and_failfast():
    sess = Session()
    h, _ = _lu_handle(sess)
    with Executor(sess, max_batch=64, max_wait=5e-3) as ex:
        f = ex.submit(h, RNG.standard_normal(N))
        # far below max_batch: only the max-wait deadline can flush it
        assert f.result(timeout=60).shape == (N,)
        # an unregistered handle is a DETERMINISTIC failure: no retries
        bad = ex.submit("ghost", RNG.standard_normal(N))
        with pytest.raises(SlateError):
            bad.result(timeout=60)
    assert sess.metrics.get("retries") == 0
    assert sess.metrics.get("failed_batches") == 1


def test_executor_retries_transient_failures():
    sess = Session()
    h, _ = _lu_handle(sess)
    real_solve = sess.solve
    fail_left = [2]

    def flaky(handle, b):
        if fail_left[0]:
            fail_left[0] -= 1
            raise RuntimeError("transient dispatch failure")
        return real_solve(handle, b)

    sess.solve = flaky
    try:
        with Executor(sess, max_batch=4, max_wait=1e-3, retries=2) as ex:
            f = ex.submit(h, RNG.standard_normal(N))
            assert f.result(timeout=60).shape == (N,)  # 3rd attempt wins
    finally:
        sess.solve = real_solve
    assert sess.metrics.get("retries") == 2
    assert sess.metrics.get("failed_batches") == 0


def test_batcher_skips_cancelled_requests():
    sess = Session()
    h, a = _lu_handle(sess)
    batcher = Batcher(sess, max_batch=8, max_wait=10.0)
    futs = [batcher.submit(h, RNG.standard_normal(N)) for _ in range(4)]
    assert futs[1].cancel()  # client gives up before dispatch
    batcher.flush()
    for i, f in enumerate(futs):
        if i == 1:
            assert f.cancelled()
        else:
            assert f.result(timeout=0).shape == (N,)
    assert sess.metrics.get("cancelled_requests") == 0  # caught pre-solve
    # running the same (already-resolved) bucket again is a no-op
    snap_before = sess.metrics.get("batches_total")
    ready = batcher.pop_ready(force=True)
    assert ready == []
    assert sess.metrics.get("batches_total") == snap_before


def test_executor_flush_waits_for_inflight():
    sess = Session()
    h, spd = _chol_handle(sess)
    sess.warmup(h)
    with Executor(sess, max_batch=4, max_wait=1e-4) as ex:
        futs = [ex.submit(h, RNG.standard_normal(N)) for _ in range(8)]
        ex.flush()
        # flush's contract: everything submitted before it is solved
        assert all(f.done() for f in futs)
        assert all(f.result(timeout=0).shape == (N,) for f in futs)


def test_executor_no_lost_wakeup_with_large_max_wait():
    """Round-23 regression pin: a submit/flush notify that lands while
    the worker is mid-dispatch (after popping, before re-waiting) must
    not be lost — the worker re-checks the `_kick` flag before
    sleeping. Without it, this loop stalls out a full max_wait (here
    3600 s) the first time the race hits; the chaos forecast drill hit
    it within ~100 iterations. The production default max_wait=2e-3
    masked the bug as a ≤2 ms blip."""
    sess = Session()
    h, spd = _chol_handle(sess)
    sess.warmup(h)
    with Executor(sess, max_batch=1, max_wait=3600.0) as ex:
        for i in range(150):
            b = RNG.standard_normal(N)
            f = ex.submit(h, b)
            ex.flush()
            assert f.done(), f"submit {i} slept into max_wait"


def test_executor_warmup_aot():
    sess = Session()
    h, spd = _chol_handle(sess)
    with Executor(sess, max_wait=1e-3) as ex:
        ex.warmup([h])
        assert sess.metrics.get("aot_compiles") == 1
        assert sess.metrics.get("factors_total") == 1  # factored off-path
        b = RNG.standard_normal(N)
        x = ex.submit(h, b).result(timeout=60)
    assert np.abs(spd @ x - b).max() < 1e-8
    # warmup executable served the request-path solve bit-identically
    sess2 = Session()
    h2 = sess2.register(st.hermitian(np.tril(spd), nb=NB,
                                     uplo=st.Uplo.Lower), op="chol")
    assert np.array_equal(x, sess2.solve(h2, b))


@pytest.mark.parametrize("make", [_chol_handle, _lu_handle])
def test_warmup_covers_every_request_width(make):
    """One warmup at nrhs=1 serves every request width up to a tile
    with nothing lowered: the solve runs at the tile-padded width, and
    the right-hand side is padded and cropped on the host."""
    import jax.monitoring

    sess = Session()
    h, a = make(sess)
    sess.warmup(h)
    lowered = []

    def on(event, secs, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(event)
    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        for k in (1, 3, NB):
            b = RNG.standard_normal((N, k))
            x = sess.solve(h, b)
            assert x.shape == (N, k)
            assert np.abs(a @ x - b).max() < 1e-8
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    assert lowered == []


def test_warmup_compiles_factor_program():
    """Round 7: warmup AOT-compiles the whole-factor program (the
    lookahead-pipeline driver) per operand shape, so refactor-on-miss
    after an eviction reuses the executable — no request-path tracing
    or compilation."""
    sess = Session()
    h, spd = _chol_handle(sess)
    sess.warmup(h)
    assert sess.metrics.get("factor_aot_compiles") == 1
    assert sess.metrics.get("aot_compiles") == 1  # the solve program
    sess.warmup(h)  # idempotent: same shapes, no recompiles
    assert sess.metrics.get("factor_aot_compiles") == 1
    assert sess.evict(h)
    b = RNG.standard_normal(N)
    x = sess.solve(h, b)  # refactor-on-miss rides the AOT executable
    assert np.abs(spd @ x - b).max() < 1e-8
    assert sess.metrics.get("factors_total") == 2


def test_factor_program_bit_identical_warmed_vs_cold():
    """The AOT factor executable and the on-demand jitted factor are
    the same program: factors (hence solves) agree bit for bit."""
    spd = _spd()
    A = st.hermitian(np.tril(spd), nb=NB, uplo=st.Uplo.Lower)
    b = RNG.standard_normal(N)
    warm = Session()
    hw = warm.register(A, op="chol")
    warm.warmup(hw)
    cold = Session()
    hc = cold.register(A, op="chol")
    assert np.array_equal(warm.solve(hw, b), cold.solve(hc, b))


# -- Metrics ---------------------------------------------------------------


def test_metrics_counters_histograms_json(tmp_path):
    sess = Session()
    h, _ = _chol_handle(sess)
    for _ in range(3):
        sess.solve(h, RNG.standard_normal(N))
    snap = sess.metrics.snapshot()
    assert snap["counters"]["solves_total"] == 3
    assert snap["counters"]["cache_misses"] == 1
    assert snap["counters"]["flops_total"] > 0
    lat = snap["histograms"]["solve_latency"]
    assert lat["count"] == 3 and 0 < lat["p50"] <= lat["p99"] <= lat["max"]
    assert snap["derived"]["cache_hit_rate"] == pytest.approx(2 / 3)
    assert snap["derived"]["solves_per_sec"] > 0
    assert snap["derived"]["gflops"] > 0
    out = tmp_path / "metrics.json"
    text = sess.metrics.to_json(str(out))
    import json
    roundtrip = json.loads(out.read_text())
    assert roundtrip == json.loads(text)
    assert roundtrip["histograms"]["factor_latency"]["count"] == 1


def test_histogram_percentiles():
    m = Metrics()
    for v in range(1, 101):
        m.observe("lat", float(v))
    h = m.snapshot()["histograms"]["lat"]
    assert h["p50"] == pytest.approx(50, abs=1)
    assert h["p99"] == pytest.approx(99, abs=1)
    assert h["count"] == 100 and h["max"] == 100
