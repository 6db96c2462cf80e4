"""Live regression watchdog vs the committed baseline
(slate_tpu.obs.watchdog + tools/bench_gate.py --baseline-out).

Injected-regression detection (both directions), quiet-on-real-history
over the committed BASELINE_SERIES.json, bench_gate's tolerance policy
reused (10 % vs best-prior; only tpu gates), anomaly events into
trace + /metrics, and the baseline artifact's single-source-of-truth
contract (bench_gate exports exactly what the watchdog loads).
"""

import importlib.util
import json
import os
import types

import pytest

from slate_tpu import obs
from slate_tpu.obs.watchdog import (Watchdog, baseline_path,
                                    load_baseline, validate_baseline)
from slate_tpu.runtime import Metrics

_ROOT = os.path.join(os.path.dirname(__file__), os.pardir)


def _bench_gate():
    spec = importlib.util.spec_from_file_location(
        "_bench_gate", os.path.join(_ROOT, "tools", "bench_gate.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _synthetic(metric="serve.solves_per_sec", platform="tpu", best=100.0,
               direction="higher", n=512, kind="serve", **extra):
    row = {"kind": kind, "metric": metric, "platform": platform, "n": n,
           "batch": None, "op": None, "dtype": None,
           "direction": direction, "best": best}
    row.update(extra)
    return {"schema": "slate_tpu.baseline_series.v1", "tolerance": 0.10,
            "series": [row]}


# -- the committed artifact --------------------------------------------------


def test_committed_baseline_loads_and_validates():
    doc = load_baseline()
    assert doc["tolerance"] == 0.10
    assert doc["gated_platforms"] == ["tpu"]
    assert len(doc["series"]) > 20
    assert validate_baseline(doc) == []
    # direction annotation: residual, latency, queue-age (round 14
    # overload columns), recovery/failover/refactor series (round 17
    # failover columns), sync.* transfer-byte series (round 20 delta
    # replication), and the round-23 forecast columns (holdout MAE,
    # store overhead pct, record-path ns/sample — error and cost) are
    # lower-is-better, everything else higher
    for row in doc["series"]:
        want = ("lower" if (row["metric"].startswith("residual_")
                            or row["metric"].startswith("sync.")
                            or "latency" in row["metric"]
                            or "age_s" in row["metric"]
                            or "recovery" in row["metric"]
                            or "failover" in row["metric"]
                            or "refactor" in row["metric"]
                            or "mae" in row["metric"]
                            or "overhead" in row["metric"]
                            or "ns_per_sample" in row["metric"])
                else "higher")
        assert row["direction"] == want, row["metric"]
    # no committed series comes from a chip yet (the remote-TPU
    # records of rounds 2–5 were removed with that setup): every row is
    # CPU smoke, which reports but never gates
    assert not any(r["platform"] in doc["gated_platforms"]
                   for r in doc["series"])


def test_baseline_is_bench_gates_own_export(tmp_path):
    """Single source of truth: regenerating via bench_gate reproduces
    the committed file's series exactly (a stale committed baseline
    would silently blind the watchdog)."""
    bg = _bench_gate()
    records = [rec for p in bg.discover(_ROOT)
               for rec in bg.normalize_all(p)]
    doc = bg.baseline_series(records)
    committed = load_baseline()
    assert doc["series"] == committed["series"]
    # and the exporter's output validates under the watchdog's loader
    out = tmp_path / "BASELINE_SERIES.json"
    out.write_text(json.dumps(doc))
    assert validate_baseline(load_baseline(str(out))) == []


def test_malformed_baseline_rejected(tmp_path):
    bad = tmp_path / "b.json"
    bad.write_text(json.dumps({"schema": "wrong", "series": []}))
    with pytest.raises(ValueError):
        load_baseline(str(bad))
    with pytest.raises(ValueError):
        Watchdog(baseline={"schema": "slate_tpu.baseline_series.v1",
                           "series": [{"metric": "m"}]})


def test_default_path_points_at_repo_root():
    assert os.path.abspath(baseline_path()) == os.path.abspath(
        os.path.join(_ROOT, "BASELINE_SERIES.json"))


# -- detection ---------------------------------------------------------------


def test_injected_throughput_regression_detected():
    m = Metrics()
    wd = Watchdog(baseline=_synthetic(best=100.0), metrics=m)
    wd.observe("serve.solves_per_sec", 50.0, "tpu", n=512, kind="serve")
    rep = wd.check()
    assert not rep["ok"] and len(rep["anomalies"]) == 1
    row = rep["anomalies"][0]
    assert row["drop_pct"] == pytest.approx(50.0)
    assert row["gated"] and row["direction"] == "higher"
    assert m.get("watchdog_anomalies_total") == 1.0
    assert m.get_gauge("watchdog_anomaly_count") == 1.0


def test_injected_latency_rise_detected():
    """The injected-latency fixture: lower-is-better series, live p99
    10× the committed best -> anomaly."""
    wd = Watchdog(baseline=_synthetic(metric="request_latency_p99",
                                      best=1e-3, direction="lower"))
    wd.observe("request_latency_p99", 1e-2, "tpu", n=512, kind="serve")
    rep = wd.check()
    assert len(rep["anomalies"]) == 1
    assert rep["anomalies"][0]["drop_pct"] == pytest.approx(900.0)


def test_within_tolerance_is_quiet():
    wd = Watchdog(baseline=_synthetic(best=100.0))
    wd.observe("serve.solves_per_sec", 91.0, "tpu", n=512, kind="serve")
    rep = wd.check()
    assert rep["ok"] and rep["matched"] == 1


def test_cpu_platform_reports_informationally():
    """bench_gate policy reused: the same 50 % drop on a CPU-smoke
    series must not page — it lands in the informational list."""
    wd = Watchdog(baseline=_synthetic(platform="cpu", best=100.0))
    wd.observe("serve.solves_per_sec", 50.0, "cpu", n=512, kind="serve")
    rep = wd.check()
    assert rep["ok"] and not rep["anomalies"]
    assert len(rep["informational"]) == 1


def test_window_best_is_charitable():
    """A warmup transient inside an otherwise healthy window is not a
    regression: the live number is the window's best value."""
    wd = Watchdog(baseline=_synthetic(best=100.0))
    wd.observe("serve.solves_per_sec", 5.0, "tpu", n=512, kind="serve",
               t=10.0)  # cold start
    wd.observe("serve.solves_per_sec", 99.0, "tpu", n=512, kind="serve",
               t=11.0)
    assert wd.check(now=12.0)["ok"]
    # but an out-of-window recovery does not save a currently-bad series
    assert not wd.check(now=11.0 + wd.window_s + 1000)["matched"]


def test_quiet_on_real_history():
    """Replaying every committed series at its own best value against
    the committed baseline flags nothing."""
    doc = load_baseline()
    wd = Watchdog()
    for row in doc["series"]:
        wd.observe(row["metric"], row["best"], row["platform"],
                   n=row["n"], op=row["op"], batch=row["batch"],
                   dtype=row["dtype"], kind=row["kind"])
    rep = wd.check()
    assert rep["matched"] == len(doc["series"])
    assert rep["ok"] and not rep["informational"]


def test_unmatched_live_series_counted_not_flagged():
    wd = Watchdog(baseline=_synthetic())
    wd.observe("no.such.metric", 1.0, "tpu", n=4)
    rep = wd.check()
    assert rep["unmatched"] == 1 and rep["matched"] == 0 and rep["ok"]


def test_anomaly_emits_trace_event():
    tracer = obs.Tracer().on()
    wd = Watchdog(baseline=_synthetic(best=100.0), tracer=tracer)
    wd.observe("serve.solves_per_sec", 10.0, "tpu", n=512, kind="serve")
    wd.check()
    events = [s for s in tracer.spans() if s.name == "watchdog.anomaly"]
    assert len(events) == 1 and events[0].kind == "anomaly"
    assert events[0].attrs["metric"] == "serve.solves_per_sec"
    assert events[0].attrs["series_kind"] == "serve"
    tracer.off()


def test_watch_session_derives_headline_series():
    """watch_session reads only session.metrics — the serving headline
    numbers land as live observations under the caller's platform."""
    m = Metrics()
    m.inc("cache_hits", 3)
    m.inc("solves_total", 10)
    m.inc("solve_flops_total", 1e9)
    m.observe("solve_latency", 0.5)
    m.observe("request_latency", 0.01)
    wd = Watchdog(baseline=_synthetic(best=100.0, n=96))
    wd.watch_session(types.SimpleNamespace(metrics=m), platform="tpu",
                     n=96)
    assert ("serve", "serve.solves_per_sec", "tpu", 96, None, None,
            None) in wd._live
    assert ("serve", "request_latency_p99", "tpu", 96, None, None,
            None) in wd._live
    # live 10/0.5 = 20 solves/s vs best 100 -> anomaly
    rep = wd.check()
    assert len(rep["anomalies"]) == 1


def test_baseline_validators_agree_across_gate_and_watchdog(tmp_path):
    """The schema rules exist twice on purpose (bench_gate stays
    jax-import-free and standalone; watchdog needs package context) —
    this pin keeps the two rule sets from drifting: same schema id,
    same filename, and the same malformed documents rejected by both."""
    bg = _bench_gate()
    from slate_tpu.obs import watchdog as wmod
    assert bg.BASELINE_SCHEMA == wmod.BASELINE_SCHEMA
    assert bg.BASELINE_FILENAME == wmod.BASELINE_FILENAME
    sid = wmod.BASELINE_SCHEMA
    bad_docs = [
        {"schema": "wrong", "series": [{"metric": "m", "platform": "p",
                                        "best": 1.0,
                                        "direction": "higher"}]},
        {"schema": sid, "series": []},
        {"schema": sid, "series": [{"metric": "m", "platform": "p",
                                    "best": True, "direction": "higher"}]},
        {"schema": sid, "series": [{"metric": "m", "platform": "p",
                                    "best": 1.0,
                                    "direction": "sideways"}]},
        {"schema": sid, "series": [{"platform": "p", "best": 1.0,
                                    "direction": "higher"}]},
    ]
    for i, doc in enumerate(bad_docs):
        path = tmp_path / f"bad{i}.json"
        path.write_text(json.dumps(doc))
        with pytest.raises(bg.SchemaError):
            bg.validate_baseline_file(str(path))
        assert validate_baseline(doc) != [], doc
    # and a good doc passes both
    good = _synthetic()
    gp = tmp_path / "good.json"
    gp.write_text(json.dumps(good))
    bg.validate_baseline_file(str(gp))
    assert validate_baseline(good) == []


def test_direction_classifier_covers_latency_series():
    """A latency metric entering the baseline must come out
    lower-is-better — an inverted direction would make the watchdog
    read a p99 blowup as an improvement."""
    bg = _bench_gate()
    assert bg._direction("request_latency_p99") == "lower"
    assert bg._direction("serve.p99_latency_ms") == "lower"
    assert bg._direction("residual_posv_hemm") == "lower"
    assert bg._direction("serve.solves_per_sec") == "higher"
    assert bg._direction("potrf_gflops") == "higher"
    # round 23: forecast-error, store-overhead, and record-path-cost
    # series are lower-is-better
    assert bg._direction("holdout_mae") == "lower"
    assert bg._direction("store_overhead_pct") == "lower"
    assert bg._direction("record_ns_per_sample") == "lower"


# -- history-backed mode (round 23) ------------------------------------------


def test_history_mode_window_mean_catches_what_charity_hides():
    """The satellite window-fix: a window that spent most of its time
    regressed with one healthy spike PASSES the charitable deque path
    (window best) but FAILS the history-backed path (true window
    mean) — same observations, same baseline."""
    from slate_tpu.obs.timeseries import TimeseriesStore

    samples = [(float(t), 50.0) for t in range(10, 20)]  # regressed
    samples.append((20.0, 99.0))                         # one spike

    deque_wd = Watchdog(baseline=_synthetic(best=100.0))
    for t, v in samples:
        deque_wd.observe("serve.solves_per_sec", v, "tpu", n=512,
                         kind="serve", t=t)
    assert deque_wd.check(now=21.0)["ok"]  # charity: best-of-window

    store = TimeseriesStore(clock=lambda: 0.0)
    hist_wd = Watchdog(baseline=_synthetic(best=100.0), store=store)
    for t, v in samples:
        hist_wd.observe("serve.solves_per_sec", v, "tpu", n=512,
                        kind="serve", t=t)
    rep = hist_wd.check(now=21.0)
    assert not rep["ok"] and len(rep["anomalies"]) == 1
    row = rep["anomalies"][0]
    assert row["aggregate"] == "window_mean"
    # live is the exact mean (10*50 + 99) / 11
    assert row["live"] == pytest.approx((10 * 50.0 + 99.0) / 11)


def test_history_mode_observations_land_in_the_store():
    """One resident history, no duplicated deque state: observations
    go to the TimeseriesStore under the wd:-prefixed key vocabulary
    and the deque map stays empty."""
    from slate_tpu.obs.timeseries import TimeseriesStore

    store = TimeseriesStore(clock=lambda: 0.0)
    wd = Watchdog(baseline=_synthetic(best=100.0), store=store)
    wd.observe("serve.solves_per_sec", 95.0, "tpu", n=512, kind="serve",
               t=5.0)
    assert not wd._live
    names = store.names()
    assert len(names) == 1 and names[0].startswith("wd:")
    assert store.points(names[0]) == [(5.0, 95.0)]
    # the /history view of watchdog traffic is queryable like any series
    assert wd.check(now=6.0)["ok"]


def test_history_mode_matches_deque_verdict_on_clean_series():
    """Parity pin: on a steady series the two modes agree in verdict
    and (to float exactness on a constant window) in the live value —
    store=None stays the byte-identical round-12 path."""
    from slate_tpu.obs.timeseries import TimeseriesStore

    for live_v, want_ok in ((95.0, True), (50.0, False)):
        deque_wd = Watchdog(baseline=_synthetic(best=100.0))
        store_wd = Watchdog(baseline=_synthetic(best=100.0),
                            store=TimeseriesStore(clock=lambda: 0.0))
        for wd in (deque_wd, store_wd):
            for t in range(10, 15):
                wd.observe("serve.solves_per_sec", live_v, "tpu", n=512,
                           kind="serve", t=float(t))
            rep = wd.check(now=15.0)
            assert rep["ok"] is want_ok
            assert rep["matched"] == 1
        assert deque_wd._live and store_wd.store is not None


def test_history_mode_window_uses_tier_fallback():
    """A raw ring too small for the window still yields the TRUE
    window mean (the finest tier covers the forgotten prefix) — the
    whole point of backing the watchdog with the store."""
    from slate_tpu.obs.timeseries import TimeseriesStore

    store = TimeseriesStore(raw_capacity=4, tier_capacities=(100, 100),
                            clock=lambda: 0.0)
    wd = Watchdog(baseline=_synthetic(best=100.0), store=store,
                  window_s=300.0)
    # 20 samples at 10 s spacing, all regressed; ring holds only 4
    for i in range(20):
        wd.observe("serve.solves_per_sec", 50.0, "tpu", n=512,
                   kind="serve", t=float(10 * i))
    rep = wd.check(now=200.0)
    assert len(rep["anomalies"]) == 1
    assert rep["anomalies"][0]["live"] == pytest.approx(50.0)


def test_baseline_out_regenerates_over_invalid_committed_file(tmp_path):
    """--baseline-out must not be blocked by an invalid EXISTING
    baseline (it is the only tool that can regenerate one)."""
    import shutil
    bg = _bench_gate()
    root = tmp_path / "root"
    root.mkdir()
    # one real artifact + a corrupt committed baseline
    shutil.copy(os.path.join(_ROOT, "BENCH_SERVE_smoke.json"),
                root / "BENCH_SERVE_smoke.json")
    (root / "BASELINE_SERIES.json").write_text('{"schema": "stale"}')
    out = root / "BASELINE_SERIES.json"
    rc = bg.main(["--dir", str(root), "--baseline-out", str(out)])
    assert rc == 0
    assert validate_baseline(load_baseline(str(out))) == []
    # without --baseline-out the corrupt file DOES fail the gate
    (root / "BASELINE_SERIES.json").write_text('{"schema": "stale"}')
    assert bg.main(["--dir", str(root), "--check-schema"]) == 1


def test_watchdog_concurrent_observe_and_check():
    """Producer/consumer safety: observes from one thread while
    another loops check() — no 'mutated during iteration' crashes."""
    import threading
    wd = Watchdog(baseline=_synthetic(best=100.0))
    stop = threading.Event()
    errs = []

    def producer():
        i = 0
        while not stop.is_set():
            wd.observe("serve.solves_per_sec", 99.0, "tpu", n=512,
                       kind="serve")
            wd.observe(f"metric{i % 50}", 1.0, "cpu", n=i % 7)
            i += 1

    def consumer():
        try:
            for _ in range(200):
                wd.check()
        except Exception as e:  # pragma: no cover — the failure mode
            errs.append(e)

    t1 = threading.Thread(target=producer)
    t2 = threading.Thread(target=consumer)
    t1.start(); t2.start()
    t2.join(timeout=30)
    stop.set()
    t1.join(timeout=10)
    assert not errs


def test_persistent_anomaly_counts_once_per_transition():
    """A regression that persists across N check() calls (scrape-
    driven) is ONE regression: counter/log/trace fire on the
    ok -> anomalous transition only; recovery re-arms the series."""
    m = Metrics()
    tracer = obs.Tracer().on()
    wd = Watchdog(baseline=_synthetic(best=100.0), metrics=m,
                  tracer=tracer)
    wd.observe("serve.solves_per_sec", 50.0, "tpu", n=512, kind="serve",
               t=10.0)
    assert not wd.check(now=11.0)["ok"]
    assert m.get("watchdog_anomalies_total") == 1.0
    # still anomalous on the next scrape: reported, NOT re-counted
    rep = wd.check(now=12.0)
    assert len(rep["anomalies"]) == 1
    assert m.get("watchdog_anomalies_total") == 1.0
    assert len([s for s in tracer.spans()
                if s.name == "watchdog.anomaly"]) == 1
    assert m.get_gauge("watchdog_anomaly_count") == 1.0
    # recovery re-arms...
    wd.observe("serve.solves_per_sec", 99.0, "tpu", n=512, kind="serve",
               t=13.0)
    assert wd.check(now=14.0)["ok"]
    assert m.get_gauge("watchdog_anomaly_count") == 0.0
    # ...so a NEW regression (old samples aged out of the window)
    # counts again
    wd.observe("serve.solves_per_sec", 50.0, "tpu", n=512, kind="serve",
               t=200.0)
    assert not wd.check(now=201.0)["ok"]
    assert m.get("watchdog_anomalies_total") == 2.0
    tracer.off()


def test_listener_exception_counted_and_logged_once(caplog):
    """Round 22: a crashing listener (a dead incident hook) must not
    break the check loop OR silently vanish — every failure is counted
    in watchdog_listener_errors_total, the traceback logs ONCE per
    listener, and healthy listeners keep receiving rows."""
    import logging

    m = Metrics()
    wd = Watchdog(baseline=_synthetic(best=100.0), metrics=m)
    seen = []

    def bad(row):
        raise RuntimeError("dead incident hook")

    wd.add_listener(bad)
    wd.add_listener(seen.append)
    wd.observe("serve.solves_per_sec", 50.0, "tpu", n=512, kind="serve",
               t=10.0)
    with caplog.at_level(logging.ERROR, logger="slate_tpu.obs"):
        assert not wd.check(now=11.0)["ok"]  # does not raise
        assert m.get("watchdog_listener_errors_total") == 1.0
        assert len(seen) == 1  # the healthy listener still ran
        # recovery re-arms, a NEW transition fails the listener again:
        # counted again, NOT logged again
        wd.observe("serve.solves_per_sec", 99.0, "tpu", n=512,
                   kind="serve", t=13.0)
        assert wd.check(now=14.0)["ok"]
        wd.observe("serve.solves_per_sec", 50.0, "tpu", n=512,
                   kind="serve", t=200.0)
        assert not wd.check(now=201.0)["ok"]
    assert m.get("watchdog_listener_errors_total") == 2.0
    assert len(seen) == 2
    logged = [r for r in caplog.records
              if "watchdog listener" in r.getMessage()]
    assert len(logged) == 1
    assert "dead incident hook" in logged[0].exc_text
