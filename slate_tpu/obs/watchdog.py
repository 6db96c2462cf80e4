"""Online regression watchdog: live serving numbers vs committed bars.

Eight rounds of BENCH/MULTICHIP artifacts form a performance
trajectory that ``tools/bench_gate.py`` already normalizes and gates —
but only when a human (or CI) reruns the bench. This module watches
the *live* service: it loads the best-prior value of every committed
series from ``BASELINE_SERIES.json`` (the artifact
``tools/bench_gate.py --baseline-out`` exports — one source of truth,
schema-checked with the other artifacts), accepts live observations
per execution window (throughput, latency percentiles, roofline
fraction), and flags any gated series whose best live value over the
window falls beyond tolerance of the committed best — emitting
anomaly events into the trace and counters/gauges into ``/metrics``.

This is how the first on-chip session self-verifies the round-6/7
standing bars (getrf >= 15,000 GFLOP/s, potrf >= 40 % of gemm-high)
without a human rereading PERF.md: run the workload with the watchdog
attached and alarm on ``watchdog_anomalies_total``.

Tolerance policy is bench_gate's, reused verbatim (PERF_HISTORY.md Round 9):
10 % vs best-prior, only the ``tpu`` platform gates — CPU
smoke numbers are dispatch-noise-dominated and report as
informational. Direction is per-series ("higher" for throughput,
"lower" for latency/residual series), carried by the baseline
artifact.

Stdlib-only and jax-free (the obs import rule); the platform label is
the caller's (``jax.default_backend()`` at the call site).
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

from .tracing import log

BASELINE_SCHEMA = "slate_tpu.baseline_series.v1"
BASELINE_FILENAME = "BASELINE_SERIES.json"
DEFAULT_TOLERANCE = 0.10
GATED_PLATFORMS = ("tpu",)
DEFAULT_WINDOW_S = 60.0

# key fields of one series, in artifact order — the same vocabulary
# bench_gate._series_key speaks
_KEY_FIELDS = ("kind", "metric", "platform", "n", "batch", "op", "dtype")

_SeriesKey = Tuple


def baseline_path() -> str:
    """The committed artifact at the repo root."""
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, os.pardir, BASELINE_FILENAME)


def validate_baseline(doc: dict) -> List[str]:
    """Schema errors of a loaded baseline document (empty = valid)."""
    errs: List[str] = []
    if not isinstance(doc, dict):
        return ["baseline: top level is not an object"]
    if doc.get("schema") != BASELINE_SCHEMA:
        errs.append(f"baseline: schema {doc.get('schema')!r} != "
                    f"{BASELINE_SCHEMA!r}")
    series = doc.get("series")
    if not isinstance(series, list) or not series:
        return errs + ["baseline: series missing or empty"]
    for i, row in enumerate(series):
        for k in ("metric", "platform", "best", "direction"):
            if k not in row:
                errs.append(f"baseline series[{i}]: missing {k!r}")
                break
        else:
            if row["direction"] not in ("higher", "lower"):
                errs.append(f"baseline series[{i}]: direction "
                            f"{row['direction']!r}")
            if not isinstance(row["best"], (int, float)) \
                    or isinstance(row["best"], bool):
                errs.append(f"baseline series[{i}]: non-numeric best")
    return errs


def load_baseline(path: Optional[str] = None) -> dict:
    """Load + validate ``BASELINE_SERIES.json`` (default: the committed
    repo-root artifact). Raises ValueError on schema violations — a
    watchdog running against a malformed baseline would be silently
    blind, the worse failure mode."""
    path = baseline_path() if path is None else path
    with open(path) as f:
        doc = json.load(f)
    errs = validate_baseline(doc)
    if errs:
        raise ValueError(f"{os.path.basename(path)}: " + "; ".join(errs))
    return doc


def _series_key(row: dict) -> _SeriesKey:
    return tuple(row.get(k) for k in _KEY_FIELDS)


def _store_series_name(key: _SeriesKey) -> str:
    """Store series name of one watchdog key (round 23 history mode):
    the key fields joined in artifact order — stable and unique, so
    the /history view of watchdog traffic reads like the baseline."""
    return "wd:" + "|".join("" if f is None else str(f) for f in key)


class Watchdog:
    """Compares live per-window observations against the baseline.

    ``baseline``: a loaded document, a path, or None (the committed
    repo-root artifact). ``tolerance`` defaults to the baseline's own
    (bench_gate's 10 %). Live series that match no baseline key are
    counted (``unmatched``) but never flagged — the watchdog only
    speaks where history exists."""

    def __init__(self, baseline=None, metrics=None, tracer=None,
                 tolerance: Optional[float] = None,
                 window_s: float = DEFAULT_WINDOW_S,
                 gated_platforms: Tuple[str, ...] = GATED_PLATFORMS,
                 max_events: int = 4096, clock=time.monotonic,
                 store=None):
        if baseline is None or isinstance(baseline, str):
            baseline = load_baseline(baseline)
        else:
            errs = validate_baseline(baseline)
            if errs:
                raise ValueError("; ".join(errs))
        self.tolerance = (baseline.get("tolerance", DEFAULT_TOLERANCE)
                          if tolerance is None else tolerance)
        self.gated_platforms = tuple(gated_platforms)
        self.window_s = window_s
        self.metrics = metrics
        self.tracer = tracer
        self._clock = clock
        self._max = max_events
        self._baseline: Dict[_SeriesKey, dict] = {
            _series_key(row): row for row in baseline["series"]}
        # producer (serving thread observes) / consumer (scrape thread
        # checks) share the live map — same locking discipline as
        # SloTracker
        self._lock = threading.Lock()
        self._live: Dict[_SeriesKey, Deque[Tuple[float, float]]] = {}
        # round 23: history-backed mode — with a TimeseriesStore
        # attached, observations land in the store (one resident
        # history, no duplicated deque state) and check() reads a TRUE
        # over-window aggregate (the exact window mean from bucket
        # sum/count) instead of the round-12 charitable window-best.
        # store=None keeps the deque path byte-identical (pinned).
        self.store = store
        self._store_keys: Dict[str, _SeriesKey] = {}
        # series currently in the anomalous state: transition
        # detection (ok -> anomalous emits; staying anomalous does
        # not), so a scrape-driven check() loop counts REGRESSIONS in
        # watchdog_anomalies_total, not scrapes — the SloTracker
        # breach-transition discipline
        self._flagged: set = set()
        self.anomalies: List[dict] = []
        # round 21: anomaly listeners — called once per NEWLY-flagged
        # gated series (the same transition discipline as the counter)
        # with the anomaly row dict. The online tuner's trigger seam:
        # ShadowTuner.attach() registers here. Listener exceptions are
        # swallowed (a broken consumer must never kill the check loop)
        # — but COUNTED (watchdog_listener_errors_total) and logged
        # once per listener (round 22: a silently-dead incident
        # capture hook defeats the whole black box)
        self._listeners: List = []
        self._listener_warned: set = set()

    def add_listener(self, fn) -> None:
        """Register ``fn(row)`` to be called on each ok -> anomalous
        transition of a gated series (stdlib-only contract: ``row`` is
        the plain anomaly dict ``check()`` reports)."""
        self._listeners.append(fn)

    @property
    def series(self) -> Dict[_SeriesKey, dict]:
        return dict(self._baseline)

    # -- live feed ----------------------------------------------------------

    def observe(self, metric: str, value: float, platform: str,
                n: Optional[int] = None, op: Optional[str] = None,
                batch: Optional[int] = None, dtype: Optional[str] = None,
                kind: Optional[str] = None, t: Optional[float] = None):
        """One live sample of a series (the bench_gate key vocabulary:
        kind/metric/platform/n/batch/op/dtype)."""
        key = (kind, metric, platform, n, batch, op, dtype)
        t = self._clock() if t is None else t
        if self.store is not None:
            name = _store_series_name(key)
            with self._lock:
                self._store_keys[name] = key
            self.store.record_gauge(name, float(value), t=t)
            return
        with self._lock:
            q = self._live.get(key)
            if q is None:
                q = self._live[key] = deque(maxlen=self._max)
            q.append((t, float(value)))

    def watch_session(self, session, platform: str, n: Optional[int] = None,
                      op: Optional[str] = None, kind: Optional[str] = "serve",
                      t: Optional[float] = None):
        """Convenience: derive the serving headline series from a
        Session's metrics — solves/sec and GFLOP/s over accumulated
        device-solve time, the request-latency p99, and (when a
        MachineModel is configured) the serve.solve roofline fraction —
        and feed them as live observations under ``platform``/``n``."""
        snap = session.metrics.snapshot()
        derived = snap.get("derived", {})
        common = dict(platform=platform, n=n, op=op, kind=kind, t=t)
        if derived.get("solves_per_sec"):
            self.observe("serve.solves_per_sec", derived["solves_per_sec"],
                         **common)
        if derived.get("gflops"):
            self.observe("serve.gflops", derived["gflops"], **common)
        h = snap.get("histograms", {}).get("request_latency")
        if h and h.get("count"):
            self.observe("request_latency_p99", h["p99"], **common)
        # round 16: the numerical-health series — sampled-residual p99
        # (lower-is-better once a baseline row commits it; until then
        # the observation is counted unmatched, never flagged — the
        # first on-chip session owns committing its best)
        r = snap.get("histograms", {}).get("sampled_residual")
        if r and r.get("count"):
            self.observe("sampled_residual_p99", r["p99"], **common)
        frac = _serve_roof_fraction(snap)
        if frac is not None:
            self.observe("serve.roof_fraction", frac, **common)

    # -- the check ----------------------------------------------------------

    def check(self, now: Optional[float] = None) -> dict:
        """Compare every live series with history against its committed
        best. With no store attached the live number is the window's
        BEST achieved value (max for higher-is-better, min for lower)
        — charitable on purpose: a warmup transient inside an
        otherwise healthy window is not a regression. With a
        TimeseriesStore attached (round 23) the live number is the
        TRUE window mean (exact, from bucket sum/count — anomaly rows
        carry ``aggregate: "window_mean"``): charity was also how a
        window that spent 55 s regressed and 5 s healthy passed. A gated-platform drop beyond tolerance is an
        anomaly; other platforms report informationally (the
        bench_gate policy). The report lists every CURRENT anomaly,
        but the counter/log/trace-event emission fires only on the
        ok -> anomalous TRANSITION of a series (a persistent
        regression scraped every 15 s is one regression, not one per
        scrape — a recovered series re-arms);
        ``watchdog_anomaly_count`` gauges the current state."""
        now = self._clock() if now is None else now
        lo = now - self.window_s
        anomalies: List[dict] = []
        informational: List[dict] = []
        matched = unmatched = 0
        # (key, live value, aggregate tag) per matched series — the
        # two modes differ ONLY in how the live value is computed:
        # history mode (round 23) reads the TRUE window mean from the
        # store's bucket sum/count (a warmup transient no longer hides
        # a regressed window — the satellite window-fix); the deque
        # path below is the round-12 charitable window-best, unchanged
        # byte-for-byte when no store is attached (pinned)
        live_rows: List[tuple] = []
        if self.store is not None:
            with self._lock:
                names = dict(self._store_keys)
            live_series = len(names)
            for name in sorted(names):
                key = names[name]
                base = self._baseline.get(key)
                if base is None:
                    unmatched += 1
                    continue
                stats = self.store.window_stats(name, lo, now)
                if stats is None:
                    continue
                matched += 1
                live_rows.append((key, stats["mean"], "window_mean"))
        else:
            with self._lock:
                live_map = {key: list(q)
                            for key, q in self._live.items()}
            live_series = len(live_map)
            for key, q in live_map.items():
                base = self._baseline.get(key)
                if base is None:
                    unmatched += 1
                    continue
                vals = [v for (t, v) in q if lo <= t <= now]
                if not vals:
                    continue
                matched += 1
                direction = base.get("direction", "higher")
                live = max(vals) if direction == "higher" else min(vals)
                live_rows.append((key, live, None))
        for key, live, aggregate in live_rows:
            base = self._baseline[key]
            direction = base.get("direction", "higher")
            best = float(base["best"])
            if best == 0:
                continue
            if direction == "higher":
                drop = (best - live) / best
            else:
                drop = (live - best) / abs(best)
            if drop <= self.tolerance:
                continue
            platform = key[2]
            row = dict(zip(_KEY_FIELDS, key))
            row.update({
                "baseline_best": best, "live": live,
                "direction": direction,
                "drop_pct": round(100 * drop, 1),
                "gated": platform in self.gated_platforms,
                "window_s": self.window_s,
            })
            if aggregate is not None:
                row["aggregate"] = aggregate
            (anomalies if row["gated"] else informational).append(row)
        # transition detection over the gated set: emit (counter, log,
        # trace event) only for series that were ok at the last check;
        # a recovered series re-arms
        now_flagged = {tuple(r.get(k) for k in _KEY_FIELDS)
                       for r in anomalies}
        with self._lock:
            new_keys = now_flagged - self._flagged
            self._flagged = now_flagged
        self._emit([r for r in anomalies
                    if tuple(r.get(k) for k in _KEY_FIELDS) in new_keys])
        report = {
            "now": now, "window_s": self.window_s,
            "tolerance": self.tolerance,
            "baseline_series": len(self._baseline),
            "live_series": live_series,
            "matched": matched, "unmatched": unmatched,
            "anomalies": anomalies, "informational": informational,
            "ok": not anomalies,
        }
        if self.metrics is not None:
            self.metrics.set_gauge("watchdog_series_matched", matched)
            self.metrics.set_gauge("watchdog_anomaly_count", len(anomalies))
        return report

    def _emit(self, anomalies: List[dict]):
        self.anomalies.extend(anomalies)
        del self.anomalies[:-256]  # bounded, newest kept
        if not anomalies:
            return
        if self.metrics is not None:
            self.metrics.inc("watchdog_anomalies_total", len(anomalies))
        for row in anomalies:
            log.warning(
                "watchdog anomaly: %s [%s, n=%s] live %.4g vs committed "
                "best %.4g (%s-is-better, %s%% worse)",
                row["metric"], row["platform"], row["n"], row["live"],
                row["baseline_best"], row["direction"], row["drop_pct"])
            tr = self.tracer
            if tr is not None and tr.enabled:
                # the series' own "kind" field is renamed: the span
                # model reserves kind= for the span class
                attrs = {("series_kind" if k == "kind" else k): v
                         for k, v in row.items() if v is not None}
                tr.event("watchdog.anomaly", kind="anomaly", **attrs)
            for fn in self._listeners:
                try:
                    fn(row)
                except Exception:
                    if self.metrics is not None:
                        self.metrics.inc("watchdog_listener_errors_total")
                    # log-once-per-listener: a listener that fails on
                    # every anomaly must not drown the log the check
                    # loop is trying to protect
                    if id(fn) not in self._listener_warned:
                        self._listener_warned.add(id(fn))
                        log.exception(
                            "watchdog listener %r failed (counted in "
                            "watchdog_listener_errors_total; further "
                            "failures of this listener log at this "
                            "site only once)", fn)


def _serve_roof_fraction(snap: dict) -> Optional[float]:
    """roof_fraction of the serve.solve roofline row, when a machine
    model is configured (env) and the ledgers know the op."""
    try:
        from .roofline import MachineModel, roofline_report
        if MachineModel.from_env() is None:
            return None
        rep = roofline_report()
        for row in rep["rows"]:
            if row["op"] == "serve.solve" and row["roof_fraction"]:
                return row["roof_fraction"]
    except Exception:
        return None
    return None
