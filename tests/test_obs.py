"""Observability layer (slate_tpu.obs): span model, Chrome-trace
export + schema validation, FLOP ledger, Prometheus exposition, HTTP
endpoint, device-trace merger, and the satellite fixes (Trace lock,
Histogram empty-snapshot nulls).

Reference analog: include/slate/internal/Trace.hh Block/SVG grown into
structured spans + trace_event export; the tester's --timer-level
timers map grown into Metrics histograms + Prometheus text. Fast: the
jax-touching tests use one tiny (n=32, nb=16) LU operator; everything
else is pure-host.
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import slate_tpu as st
from slate_tpu import obs
from slate_tpu.obs import flops as model_flops
from slate_tpu.obs.tracing import Tracer
from slate_tpu.runtime import Batcher, Executor, Metrics, Session
from slate_tpu.utils import trace as legacy_trace

RNG = np.random.default_rng(23)
N, NB = 32, 16


def _lu_session(tracer=None):
    sess = Session(tracer=tracer)
    a = RNG.standard_normal((N, N)) + N * np.eye(N)
    h = sess.register(st.from_dense(a, nb=NB), op="lu")
    return sess, h, a


# -- span model -------------------------------------------------------------


def test_zero_spans_when_tracing_disabled():
    """Acceptance: with tracing disabled the runtime records zero
    spans (the span() fast path hands out one shared no-op object)."""
    tracer = Tracer()  # disabled by default
    assert tracer.span("anything") is obs.NOOP_SPAN  # no allocation
    sess, h, a = _lu_session(tracer=tracer)
    batcher = Batcher(sess, max_batch=4, max_wait=10.0)
    futs = [batcher.submit(h, RNG.standard_normal(N)) for _ in range(3)]
    batcher.flush()
    for f in futs:
        f.result(timeout=0)
    assert tracer.spans() == []


def test_span_tree_through_batcher_coalescing():
    """Acceptance: a served solve yields a CONNECTED span tree —
    batched request spans share the batch span as parent; the
    factor/solve (and dispatch/block) spans nest under the batch."""
    tracer = Tracer().on()
    sess, h, a = _lu_session(tracer=tracer)
    batcher = Batcher(sess, max_batch=8, max_wait=10.0)
    futs = [batcher.submit(h, RNG.standard_normal(N)) for _ in range(4)]
    batcher.flush()
    for f in futs:
        f.result(timeout=0)
    spans = tracer.spans()
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    (batch,) = by_name["serve.batch"]
    reqs = by_name["serve.request"]
    assert len(reqs) == 4
    # the satellite contract: batched request spans share the batch
    # span as parent (and its trace id)
    assert all(r.parent_id == batch.span_id for r in reqs)
    assert all(r.trace_id == batch.trace_id for r in reqs)
    assert all(r.kind == "request" for r in reqs)
    assert all("queue_s" in r.attrs and "total_s" in r.attrs for r in reqs)
    # factor + solve nest under the batch; dispatch/block under solve
    (solve,) = by_name["serve.solve"]
    (factor,) = by_name["serve.factor"]
    assert solve.parent_id == batch.span_id
    assert factor.parent_id == batch.span_id
    assert by_name["serve.dispatch"][0].parent_id == solve.span_id
    assert by_name["serve.block"][0].parent_id == solve.span_id
    # attribute vocabulary (op, shape, dtype, nb, cache hit/miss, handle)
    assert solve.attrs["op"] == "lu" and solve.attrs["n"] == N
    assert solve.attrs["nb"] == NB and solve.attrs["cache_hit"] is False
    assert "lookahead" in solve.attrs and "handle" in solve.attrs
    # connectedness: one root (the batch), every parent resolves
    ids = {s.span_id for s in spans}
    roots = [s for s in spans if s.parent_id is None]
    assert roots == [batch]
    assert all(s.parent_id in ids for s in spans if s.parent_id is not None)


def test_chrome_trace_schema_valid():
    tracer = Tracer().on()
    sess, h, a = _lu_session(tracer=tracer)
    batcher = Batcher(sess, max_batch=4, max_wait=10.0)
    for _ in range(2):
        batcher.submit(h, RNG.standard_normal(N))
    batcher.flush()
    obj = obs.chrome_trace(tracer.spans())
    assert obs.validate_chrome_trace(obj) == []
    xev = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    assert xev, "no events exported"
    # required keys + monotone ts, re-checked directly (not only via
    # the validator under test)
    for e in xev:
        for k in ("ph", "ts", "dur", "pid", "tid", "name", "args"):
            assert k in e
    ts = [e["ts"] for e in xev]
    assert ts == sorted(ts)
    # both views: a thread lane (pid 0) and a phase-class lane (pid 1)
    assert {e["pid"] for e in xev} == {0, 1}
    # round-trips through json
    assert obs.validate_chrome_trace(json.loads(json.dumps(obj))) == []


def test_chrome_trace_validator_catches_violations():
    good = {"ph": "X", "ts": 1.0, "dur": 2.0, "pid": 0, "tid": 0,
            "name": "a", "args": {"span_id": 1, "parent_id": None}}
    assert obs.validate_chrome_trace({"traceEvents": [good]}) == []
    missing = {k: v for k, v in good.items() if k != "dur"}
    assert obs.validate_chrome_trace({"traceEvents": [missing]})
    non_monotone = [dict(good, ts=5.0), dict(good, ts=1.0)]
    assert any("monotone" in e for e in
               obs.validate_chrome_trace({"traceEvents": non_monotone}))
    # child escaping its parent's interval
    parent = dict(good, args={"span_id": 1, "parent_id": None})
    child = dict(good, ts=2.0, dur=10.0,
                 args={"span_id": 2, "parent_id": 1})
    assert any("nested" in e for e in
               obs.validate_chrome_trace({"traceEvents": [parent, child]}))


def test_error_capture_and_slow_request_log():
    tracer = Tracer(slow_threshold=0.0).on()  # everything is "slow"
    sess, h, a = _lu_session(tracer=tracer)
    with Executor(sess, max_batch=4, max_wait=1e-3, retries=0) as ex:
        ok = ex.submit(h, RNG.standard_normal(N))
        assert ok.result(timeout=60).shape == (N,)
        bad = ex.submit("ghost", RNG.standard_normal(N))
        with pytest.raises(Exception):
            bad.result(timeout=60)
    spans = tracer.spans()
    errored = [s for s in spans if s.status == "error"]
    assert errored, "failed dispatch recorded no error spans"
    assert any("unknown handle" in (s.error or "") for s in errored)
    # the slow-request log captured the (threshold-0) request spans
    assert len(tracer.slow_log) >= 1
    assert all(s.kind == "request" for s in tracer.slow_log)


def test_span_bridges_to_legacy_timers_and_svg(tmp_path):
    """The span model subsumes utils.trace.phase: finishing a span
    feeds the coarse timers map and (when Trace is on) the SVG."""
    tracer = Tracer().on()
    legacy_trace.Trace.clear()
    legacy_trace.Trace.on()
    try:
        before = legacy_trace.timers.get("obs.bridge", 0.0)
        with tracer.span("obs.bridge"):
            time.sleep(0.002)
        assert legacy_trace.timers["obs.bridge"] > before
        assert any(e.name == "obs.bridge"
                   for e in legacy_trace.Trace.events())
        path = legacy_trace.Trace.finish(str(tmp_path / "t.svg"))
        assert path and "obs.bridge" in open(path).read()
    finally:
        legacy_trace.Trace.off()
        legacy_trace.Trace.clear()


# -- satellite: Trace thread-safety -----------------------------------------


def test_trace_record_thread_safe_under_concurrent_writers():
    """Two threads hammer Trace.record (as Executor worker + main do)
    while a third snapshots/clears: no lost events in the final tally,
    no exceptions from mutation-during-iteration."""
    legacy_trace.Trace.clear()
    legacy_trace.Trace.on()
    try:
        per_thread = 2000
        errs = []

        def writer(lane):
            try:
                for i in range(per_thread):
                    legacy_trace.Trace.record(f"w{lane}", float(i),
                                              float(i) + 0.5, lane)
            except Exception as e:  # pragma: no cover
                errs.append(e)

        def reader():
            try:
                for _ in range(200):
                    legacy_trace.Trace.events()
            except Exception as e:  # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=writer, args=(i,))
                   for i in range(2)] + [threading.Thread(target=reader)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert errs == []
        assert len(legacy_trace.Trace.events()) == 2 * per_thread
    finally:
        legacy_trace.Trace.off()
        legacy_trace.Trace.clear()


# -- satellite: Histogram empty snapshot ------------------------------------


def test_histogram_empty_snapshot_reports_null_min_max():
    """Empty histogram: min/max/mean are None (JSON null), NOT 0.0 —
    a real zero-latency sample must stay distinguishable."""
    m = Metrics()
    m._hists["empty"] = __import__(
        "slate_tpu.runtime.metrics", fromlist=["Histogram"]).Histogram()
    snap = m.snapshot()["histograms"]["empty"]
    assert snap["count"] == 0
    assert snap["min"] is None and snap["max"] is None
    assert snap["mean"] is None
    # ...and survives JSON round-trip as null
    assert json.loads(json.dumps(snap))["max"] is None
    # a REAL 0.0 sample is distinguishable from emptiness
    m.observe("real", 0.0)
    real = m.snapshot()["histograms"]["real"]
    assert real["min"] == 0.0 and real["max"] == 0.0 and real["count"] == 1


# -- FLOP ledger ------------------------------------------------------------


def test_flop_ledger_centralizes_model_formulas():
    # the formulas the three call sites used to duplicate
    assert model_flops.potrf(100) == 100 ** 3 / 3.0
    assert model_flops.getrf(100) == 2 * 100 ** 3 / 3.0
    assert model_flops.geqrf(200, 100) == 2 * 200 * 100 ** 2 - 2 * 100 ** 3 / 3
    assert model_flops.gemm(2, 3, 4) == 48
    assert model_flops.heev(10) == pytest.approx(4 / 3 * 1000)
    assert model_flops.heev(10, vectors=True) == pytest.approx(
        (4 / 3 + 2) * 1000)
    assert model_flops.svd(10, 10) == pytest.approx(8 / 3 * 1000)
    # the session accounting entry points
    assert model_flops.factor_flops("chol", 64, 64) == 64 ** 3 / 3.0
    assert model_flops.solve_flops("lu", 64, 64, 3) == 2 * 64 * 64 * 3
    assert model_flops.solve_flops("qr", 96, 48, 2) == (
        4 * 96 * 48 - 2 * 48 * 48) * 2
    # the tester's (m, n) table agrees with the canonical functions
    assert model_flops.tester_model("potrf")(64, 64) == model_flops.potrf(64)
    assert model_flops.tester_model("gemm")(8, 4) == 2.0 * 8 * 8 * 4


def test_driver_calls_increment_process_ledger():
    ledger = model_flops.LEDGER
    base = ledger.snapshot()
    a = RNG.standard_normal((N, N)) + N * np.eye(N)
    A = st.from_dense(a, nb=NB)
    LU, perm, info = st.lu_factor(A)
    X = st.lu_solve_using_factor(
        LU, perm, st.from_dense(RNG.standard_normal((N, 2)), nb=NB))
    snap = ledger.snapshot()
    assert snap["flops_total"] >= base["flops_total"] + model_flops.getrf(N)
    got = (snap["per_op"].get("lu_factor", 0.0)
           - base["per_op"].get("lu_factor", 0.0))
    assert got == pytest.approx(model_flops.getrf(N))
    got = (snap["per_op"].get("lu_solve_using_factor", 0.0)
           - base["per_op"].get("lu_solve_using_factor", 0.0))
    assert got == pytest.approx(model_flops.solve_flops("lu", N, N, 2))
    # gflops_report joins the ledger against the phase timers map
    rep = ledger.gflops_report({"api.lu_factor": 1.0})
    assert rep["per_op"]["lu_factor"]["gflops"] is not None


# -- Prometheus + HTTP endpoint ---------------------------------------------


def _fake_metrics():
    m = Metrics()
    m.inc("solves_total", 5)
    m.inc("cache_hits", 3)
    m.inc("cache_misses", 2)
    for v in (0.01, 0.02, 0.03):
        m.observe("solve_latency", v)
    return m


def test_prometheus_rendering():
    text = obs.render_prometheus(_fake_metrics())
    assert "# TYPE slate_tpu_solves_total counter" in text
    assert "slate_tpu_solves_total 5.0" in text
    assert 'slate_tpu_solve_latency{quantile="0.5"} 0.02' in text
    assert "slate_tpu_solve_latency_count 3" in text
    assert "slate_tpu_solve_latency_sum" in text
    assert "slate_tpu_cache_hit_rate 0.6" in text
    assert "slate_tpu_driver_flops_total" in text
    # exposition-format discipline: every non-comment line is
    # "name{labels} value"
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        name, value = line.rsplit(" ", 1)
        float(value)
        assert name[0].isalpha()
    # empty histograms render no min/max (the null contract)
    m = Metrics()
    from slate_tpu.runtime.metrics import Histogram
    m._hists["empty"] = Histogram()
    text = obs.render_prometheus(m)
    assert "empty_min" not in text and "empty_max" not in text
    assert "slate_tpu_empty_count 0" in text


def test_http_endpoint_serves_metrics_healthz_trace():
    tracer = Tracer().on()
    with tracer.span("serve.solve", op="lu"):
        pass
    m = _fake_metrics()
    with obs.ObsServer(m, tracer=tracer) as srv:
        body = urllib.request.urlopen(srv.url("/metrics"),
                                      timeout=10).read().decode()
        assert "slate_tpu_solves_total 5.0" in body
        health = json.loads(urllib.request.urlopen(
            srv.url("/healthz"), timeout=10).read().decode())
        assert health["status"] == "ok" and health["tracing"] is True
        tr = json.loads(urllib.request.urlopen(
            srv.url("/trace.json"), timeout=10).read().decode())
        assert obs.validate_chrome_trace(tr) == []
        assert any(e.get("name") == "serve.solve"
                   for e in tr["traceEvents"])
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(srv.url("/nope"), timeout=10)


def test_session_serve_obs_endpoint():
    sess, h, a = _lu_session()
    sess.solve(h, RNG.standard_normal(N))
    srv = sess.serve_obs()
    try:
        assert srv is sess.serve_obs()  # idempotent
        body = urllib.request.urlopen(srv.url("/metrics"),
                                      timeout=10).read().decode()
        assert "slate_tpu_solves_total 1.0" in body
        assert "slate_tpu_jit_cache_misses" in body
    finally:
        sess.close_obs()


# -- compile-time observability ---------------------------------------------


def test_warmup_records_compile_observability():
    sess, h, a = _lu_session()
    sess.warmup(h)
    snap = sess.metrics.snapshot()
    assert snap["counters"]["jit_cache_misses"] >= 2  # factor + solve
    lower = snap["histograms"]["warmup_lower_latency"]
    comp = snap["histograms"]["warmup_compile_latency"]
    assert lower["count"] == 2 and comp["count"] == 2  # factor + solve
    assert lower["min"] > 0 and comp["min"] > 0
    # per-shape compile log: factor program + solve program
    whats = sorted(e["what"] for e in sess.compile_log)
    assert whats == ["factor", "solve"]
    for e in sess.compile_log:
        assert e["op"] == "lu" and e["shape"] and e["lower_s"] > 0


# -- device-trace merger / lookahead overlap --------------------------------


def _dev_event(name, ts_us, dur_us):
    return {"ph": "X", "ts": ts_us, "dur": dur_us, "pid": 9, "tid": 1,
            "name": f"jit__potrf/{name}/fusion.1", "args": {}}


def test_lookahead_overlap_metric():
    # level-1 lookahead tile factor [10, 30] runs under level-0
    # trail_rest [0, 100]: fully hidden. level-2 lookahead [150, 170]
    # has NO concurrent level-1 trail_rest (it ran [100, 140]): exposed.
    events = [
        _dev_event("potrf_l0_trail_rest", 0, 100),
        _dev_event("potrf_l1_tile_lookahead", 10, 20),
        _dev_event("potrf_l1_trail_rest", 100, 40),
        _dev_event("potrf_l2_tile_lookahead", 150, 20),
    ]
    ov = obs.lookahead_overlap(events, driver="potrf")
    assert ov["levels"]["1"]["hidden_fraction"] == pytest.approx(1.0)
    assert ov["levels"]["2"]["hidden_fraction"] == pytest.approx(0.0)
    assert ov["panel_s"] == pytest.approx(40e-6)
    assert ov["hidden_s"] == pytest.approx(20e-6)
    assert ov["overlap_fraction"] == pytest.approx(0.5)
    # a lookahead=0 trace (no lookahead scopes) reports empty, not junk
    ov0 = obs.lookahead_overlap([_dev_event("potrf_l0_trail", 0, 10)])
    assert ov0["levels"] == {} and ov0["overlap_fraction"] == 0.0
    # TPU xplane exports carry the scope in args, not the name
    args_events = [
        {"ph": "X", "ts": 0, "dur": 100, "pid": 9, "tid": 1,
         "name": "fusion.7",
         "args": {"long_name": "jit__potrf/potrf_l0_trail_rest/dot"}},
        {"ph": "X", "ts": 10, "dur": 20, "pid": 9, "tid": 1,
         "name": "fusion.9",
         "args": {"long_name": "jit__potrf/potrf_l1_tile_lookahead/x"}},
    ]
    ova = obs.lookahead_overlap(args_events, driver="potrf")
    assert ova["overlap_fraction"] == pytest.approx(1.0)


def test_served_spans_on_the_profiler_clock(tmp_path):
    """While the JAX profiler records, the serving spans are its own
    host events (TraceAnnotations), with obs tracing off: a device
    trace shows what the host did in each gap with no clock to align."""
    import glob

    import jax
    from jax.profiler import ProfileData

    tracer = Tracer()  # obs tracing stays off
    sess, h, a = _lu_session(tracer=tracer)
    ex = Executor(sess, max_batch=4, max_wait=0.001)
    try:
        ex.warmup([h])
        jax.profiler.start_trace(str(tmp_path))
        try:
            for f in [ex.submit(h, RNG.standard_normal(N))
                      for _ in range(3)]:
                f.result(timeout=60)
        finally:
            jax.profiler.stop_trace()
    finally:
        ex.shutdown()
    assert tracer.spans() == []
    assert tracer.span("after") is obs.NOOP_SPAN  # off again, free again
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = {p.name: p for p in ProfileData.from_file(path).planes}["/host:CPU"]
    names = {e.name for line in host.lines for e in line.events}
    assert {"serve.batch", "serve.dispatch", "serve.block",
            "serve.crop"} <= names


# -- review-fix regression pins ---------------------------------------------


def test_served_solves_credit_ledger_per_execution():
    """The api.* verbs inside the Session's jitted factor/solve
    programs run only at jax-trace time and credit NOTHING (obs.driver
    is a no-op under a trace); the executed work lands in the process
    ledger as serve.factor/serve.solve — one credit PER solve, not per
    compiled shape."""
    ledger = model_flops.LEDGER
    sess, h, a = _lu_session()
    base = ledger.snapshot()["per_op"].get("serve.solve", 0.0)
    n_solves = 4
    for _ in range(n_solves):
        sess.solve(h, RNG.standard_normal(N))
    got = ledger.snapshot()["per_op"]["serve.solve"] - base
    assert got == pytest.approx(
        n_solves * model_flops.solve_flops("lu", N, N, 1))


def test_start_span_accepts_noop_parent():
    """A parent captured while tracing was off is the shared NOOP span
    (e.g. the Batcher's batch context before on()); start_span must
    treat it like an absent parent, not dereference its trace_id."""
    t = Tracer().on()
    sp = t.start_span("req", parent=obs.NOOP_SPAN)
    assert sp is not None and sp.parent_id is None
    t.finish_span(sp, parent=obs.NOOP_SPAN)  # finish side stays guarded
    assert t.spans()[0].parent_id is None


def test_render_prometheus_falsy_ledger_disables_section():
    text = obs.render_prometheus(Metrics(), ledger=False)
    assert "driver_flops" not in text
    assert "slate_tpu_uptime_seconds" in text


def test_legacy_timers_accumulate_thread_safe():
    """timers[k] += d is a load-add-store interleaving hazard across
    the Executor worker and submitting threads; add_timer serializes
    it, so the concurrent sum must be exact."""
    key = "obs_test_timer_race"
    legacy_trace.timers.pop(key, None)
    per_thread, dur = 2000, 0.001
    def work():
        for _ in range(per_thread):
            legacy_trace.add_timer(key, dur)
    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    got = legacy_trace.timers.pop(key)
    assert got == pytest.approx(4 * per_thread * dur)


def test_band_flop_models_consistent_across_verbs():
    """_band_of understands every band container (it used to return 0
    for band-kind TiledMatrix), and chol_factor/chol_solve on the SAME
    HermitianBand operand credit the same kd-band model (chol_solve
    used to credit dense potrf beside chol_factor's band_factor)."""
    from slate_tpu.api import _band_of
    from slate_tpu.linalg.band_packed import PackedBand
    kd, n, nb = 2, 16, 8
    a = np.eye(n) * (n + 4.0)
    for d in range(1, kd + 1):
        a += np.diag(np.ones(n - d), -d) + np.diag(np.ones(n - d), d)
    H = st.hermitian_band(a, nb, kd, st.Uplo.Lower)
    Bk = st.band(a, nb, 1, 2)
    pb = PackedBand(np.zeros((kd + 1, n)), n, kd, 0, hermitian=True)
    assert _band_of(H) == kd        # was 0 (TiledMatrix fell through)
    assert _band_of(Bk) == 3        # Band kind: kl+ku
    assert _band_of(pb) == kd       # packed hermitian-lower unchanged
    ledger = model_flops.LEDGER
    b0 = ledger.snapshot()["per_op"]
    st.chol_factor(H)
    f_factor = (ledger.snapshot()["per_op"]["chol_factor"]
                - b0.get("chol_factor", 0.0))
    assert f_factor == pytest.approx(model_flops.band_factor(n, kd))
    B = st.from_dense(RNG.standard_normal((n, 2)), nb=nb)
    b1 = ledger.snapshot()["per_op"]
    st.chol_solve(H, B)
    f_solve = (ledger.snapshot()["per_op"]["chol_solve"]
               - b1.get("chol_solve", 0.0))
    assert f_solve == pytest.approx(
        model_flops.band_factor(n, kd)
        + model_flops.solve_flops("band_chol", n, n, 2, band=kd))


def test_band_factor_credits_ledger_once():
    """Band factors run through the EAGER api verbs (whose driver hook
    credits the ledger); Session.factor must not credit serve.factor on
    top — one band factorization, exactly one ledger credit."""
    from slate_tpu.linalg.band_packed import pb_pack
    n, kd = 32, 2
    a = np.eye(n) * (n + 4.0)
    for d in range(1, kd + 1):
        a += np.diag(np.ones(n - d), -d) + np.diag(np.ones(n - d), d)
    sess = Session()
    h = sess.register(pb_pack(a, kd), op="auto")
    base = model_flops.LEDGER.snapshot()["flops_total"]
    sess.factor(h)
    delta = model_flops.LEDGER.snapshot()["flops_total"] - base
    assert delta == pytest.approx(model_flops.band_factor(n, kd))


def test_errored_attempt_trace_stays_validly_nested():
    """A failed dispatch attempt closes its request spans INSIDE the
    batch span's scope (Batcher.run) — children ending after their
    parent used to fail the package's own Chrome-trace nesting check
    on any retried workload."""
    tracer = Tracer().on()
    sess, h, a = _lu_session(tracer=tracer)
    calls = {"n": 0}
    orig = sess.solve_matrix
    def flaky(handle, B):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient tunnel failure")
        return orig(handle, B)
    sess.solve_matrix = flaky
    from slate_tpu.runtime import Executor
    with Executor(sess, max_batch=4, max_wait=0.01, retries=2) as ex:
        futs = [ex.submit(h, RNG.standard_normal(N)) for _ in range(3)]
        for f in futs:
            f.result(timeout=120)
    assert calls["n"] == 2  # one failure, one retry
    spans = tracer.spans()
    errored = {s.name for s in spans if s.status == "error"}
    assert "serve.batch" in errored and "serve.request" in errored
    assert obs.validate_chrome_trace(obs.chrome_trace(spans)) == []


# -- round 12: request lifecycle stages, backpressure, padding waste --------


def test_lifecycle_stage_histograms_with_exemplar_trace_ids():
    """Tentpole (c): a served request decomposes into per-stage
    histograms (queue wait, batch formation, dispatch, device execute,
    reply), each carrying the worst sample's exemplar trace-id — the
    join key from /metrics back into the trace."""
    tracer = Tracer().on()
    sess, h, a = _lu_session(tracer=tracer)
    batcher = Batcher(sess, max_batch=4, max_wait=10.0)
    futs = [batcher.submit(h, RNG.standard_normal(N)) for _ in range(3)]
    batcher.flush()
    for f in futs:
        f.result(timeout=0)
    snap = sess.metrics.snapshot()
    hists = snap["histograms"]
    assert hists["stage_queue_wait"]["count"] == 3   # one per request
    assert hists["stage_batch_form"]["count"] == 1   # one per batch
    assert hists["stage_dispatch"]["count"] == 1
    assert hists["stage_device_execute"]["count"] == 1
    assert hists["stage_reply"]["count"] == 1
    batch = [s for s in tracer.spans() if s.name == "serve.batch"][0]
    for stage in ("stage_queue_wait", "stage_batch_form", "stage_reply"):
        assert hists[stage]["exemplar"]["trace_id"] == batch.trace_id
    # dispatch/execute exemplars come from the solve span's trace —
    # the same trace (solve nests under the batch)
    assert hists["stage_dispatch"]["exemplar"]["trace_id"] == \
        batch.trace_id
    # the exemplar renders as a plain gauge in the exposition
    prom = obs.render_prometheus(sess.metrics, ledger=False,
                                 bytes_ledger=False)
    assert "slate_tpu_stage_queue_wait_exemplar_trace_id" in prom
    tracer.off()


def test_stage_histograms_populate_with_tracing_off():
    """The stage decomposition is metrics, not tracing: with the
    tracer disabled the histograms still fill (exemplar absent)."""
    sess, h, a = _lu_session()
    batcher = Batcher(sess, max_batch=4, max_wait=10.0)
    batcher.submit(h, RNG.standard_normal(N))
    batcher.flush()
    hists = sess.metrics.snapshot()["histograms"]
    assert hists["stage_dispatch"]["count"] == 1
    assert hists["stage_dispatch"]["exemplar"] is None


def test_backpressure_gauges_track_queue_state():
    """Satellite: queue depth, queued buckets, oldest-request age and
    max per-bucket backlog are /metrics gauges, updated on every
    enqueue/pop — plus the labeled per-bucket breakdown."""
    sess, h, a = _lu_session()
    batcher = Batcher(sess, max_batch=8, max_wait=10.0)
    for _ in range(3):
        batcher.submit(h, RNG.standard_normal(N))
    m = sess.metrics
    assert m.get_gauge("queue_depth") == 3.0
    assert m.get_gauge("queued_buckets") == 1.0
    assert m.get_gauge("max_bucket_backlog") == 3.0
    assert m.get_gauge("oldest_request_age_s") >= 0.0
    bp = batcher.backpressure()
    assert bp["queue_depth"] == 3 and len(bp["per_bucket"]) == 1
    (bucket,) = bp["per_bucket"].values()
    assert bucket["backlog"] == 3 and bucket["oldest_age_s"] >= 0.0
    batcher.flush()
    assert m.get_gauge("queue_depth") == 0.0
    assert m.get_gauge("max_bucket_backlog") == 0.0
    # and the Executor's in-flight gauge exists after a served batch
    from slate_tpu.runtime import Executor
    with Executor(sess, max_batch=4, max_wait=1e-3) as ex:
        ex.submit(h, RNG.standard_normal(N)).result(timeout=120)
        ex.flush()
    assert m.get_gauge("inflight_batches") == 0.0


def test_width_padding_waste_split_exactly():
    """Tentpole (c): pad_widths quantizes 3 coalesced columns to 4 —
    the executed fourth column's flops move to padding_waste_flops /
    the ledger's padding.waste op, solve_flops_total keeps ONLY the
    served columns, and their sum is the executed total."""
    sess, h, a = _lu_session()
    base = model_flops.LEDGER.snapshot()["per_op"].get("padding.waste",
                                                       0.0)
    batcher = Batcher(sess, max_batch=8, max_wait=10.0, pad_widths=True)
    futs = [batcher.submit(h, RNG.standard_normal(N)) for _ in range(3)]
    batcher.flush()
    for f in futs:
        f.result(timeout=0)
    m = sess.metrics
    per_col = model_flops.solve_flops("lu", N, N, 1)
    assert m.get("padding_waste_flops") == pytest.approx(per_col)
    assert m.get("solve_flops_total") == pytest.approx(3 * per_col)
    assert m.get("flops_total") - m.get("factor_flops_total") == \
        pytest.approx(4 * per_col)  # executed = useful + waste
    assert m.get("solves_total") == 3.0  # client columns only
    assert m.get_gauge("width_bucket_efficiency") == pytest.approx(0.75)
    delta = model_flops.LEDGER.snapshot()["per_op"]["padding.waste"] - base
    assert delta == pytest.approx(per_col)


def test_width_padding_waste_zero_at_pow2_occupancy():
    sess, h, a = _lu_session()
    batcher = Batcher(sess, max_batch=8, max_wait=10.0, pad_widths=True)
    futs = [batcher.submit(h, RNG.standard_normal(N)) for _ in range(4)]
    batcher.flush()
    for f in futs:
        f.result(timeout=0)
    assert sess.metrics.get("padding_waste_flops") == 0.0


def test_batch_bucket_padding_waste_counters():
    """The pow2 batch bucket of the small-problem engine: 3 distinct
    operators -> bucket 4 -> one padded lane's factor+solve flops in
    padding_waste_flops; a full 4-bucket credits exactly 0. The
    process ledger's padding.waste op moves at the linalg/batched
    layer (where the padding happens)."""
    nn = 8
    base = model_flops.LEDGER.snapshot()["per_op"].get("padding.waste",
                                                       0.0)
    sess = Session()
    hs = [sess.register(RNG.standard_normal((nn, nn)) + nn * np.eye(nn),
                        op="lu_small") for _ in range(3)]
    xs, infos = sess.solve_small_batched(
        hs, [RNG.standard_normal((nn, 1)) for _ in hs])
    assert infos == [0, 0, 0]
    waste = sess.metrics.get("padding_waste_flops")
    # one padded lane: solve (client width model) + miss-factor share.
    # Session counters live on the round-15 integer flop grid (the
    # attribution conservation invariant — runtime/session.py
    # _factor_flops/_solve_flops wrappers), so the model values are
    # rounded per call before summing.
    assert waste == (round(model_flops.solve_flops("lu", nn, nn, 1))
                     + round(model_flops.getrf(nn)))
    assert sess.metrics.get_gauge("batch_bucket_efficiency") == \
        pytest.approx(0.75)
    assert model_flops.LEDGER.snapshot()["per_op"]["padding.waste"] > base
    full = Session()
    hf = [full.register(RNG.standard_normal((nn, nn)) + nn * np.eye(nn),
                        op="lu_small") for _ in range(4)]
    full.solve_small_batched(hf, [RNG.standard_normal((nn, 1))
                                  for _ in hf])
    assert full.metrics.get("padding_waste_flops") == 0.0
    assert full.metrics.get_gauge("batch_bucket_efficiency") == 1.0


def test_bucket_bytes_split_between_verb_and_padding_waste():
    """_run_bucket splits the executed program's bytes by occupancy:
    verb share + padding.waste share = the full program bytes the
    round-9 crediting used to put on the verb alone."""
    from slate_tpu.linalg import batched as batched_mod
    from slate_tpu.obs import costs as costs_mod
    nn = 8
    a = np.stack([RNG.standard_normal((nn, nn)) + nn * np.eye(nn)
                  for _ in range(3)])
    b = np.stack([RNG.standard_normal((nn, 1)) for _ in range(3)])
    batched_mod.gesv_batched(a, b)  # warm the bucket program
    snap0 = costs_mod.BYTES.snapshot()
    batched_mod.gesv_batched(a, b)
    snap1 = costs_mod.BYTES.snapshot()
    verb = (snap1["per_op"]["gesv_batched"]["bytes"]
            - snap0["per_op"]["gesv_batched"]["bytes"])
    waste = (snap1["per_op"].get("padding.waste", {"bytes": 0.0})["bytes"]
             - snap0["per_op"].get("padding.waste", {"bytes": 0.0})["bytes"])
    if verb + waste > 0:  # XLA:CPU may report no bytes — skip honestly
        assert waste == pytest.approx((verb + waste) * 0.25)
