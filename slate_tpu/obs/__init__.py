"""slate_tpu.obs — unified observability layer.

One span model flowing from the simplified-API drivers through the
serving runtime (Session/Batcher/Executor), exported in formats real
tools ingest:

* :mod:`.tracing`    — structured spans (trace/span/parent ids,
  attributes, error status), request-scoped propagation, slow-request
  log; subsumes ``utils.trace.phase`` (feeds the legacy timers map and
  SVG timeline on every span finish).
* :mod:`.export`     — Chrome-trace/Perfetto ``trace_event`` JSON
  (one lane per thread + one per phase class) with a schema validator.
* :mod:`.flops`      — the FLOP ledger: every model-GFLOP formula in
  one module (bench.py, tester.py, and runtime/session.py all import
  from here) plus the process-wide monotone flop counter the drivers
  credit.
* :mod:`.exposition` — Prometheus text rendering of runtime Metrics +
  an opt-in stdlib-only HTTP endpoint (/metrics, /healthz,
  /trace.json).
* :mod:`.merge`      — aligns host spans with ``jax.profiler`` device
  traces via the ``potrf_l{k}_*``/``geqrf_l{k}_*`` named scopes and
  computes the measured lookahead-overlap metric (PERF_HISTORY.md round 7's
  modeled number, measured); round 12 adds the multi-process trace
  combine (``combine_process_traces``).
* :mod:`.slo`        — declarative serving objectives evaluated over
  rolling windows with multi-window burn rates; the ``/slo`` endpoint
  payload (round 12).
* :mod:`.watchdog`   — online regression detection: live serving
  numbers vs the committed ``BASELINE_SERIES.json`` best-priors
  (bench_gate's tolerance policy), anomalies into trace + /metrics.
* :mod:`.aggregate`  — N processes' metric/ledger/trace snapshots
  folded into one fleet view (counters summed exactly, histograms
  merged, gauges host-labeled).
* :mod:`.attribution` — per-(tenant, handle) attribution of every
  counter class (flops/bytes/ICI/seconds/residency/outcomes) on exact
  dyadic grids, EWMA handle heat, and the placement-snapshot schema
  the fleet fold turns into ROADMAP item 1's placement input
  (round 15).
* :mod:`.events` / :mod:`.recorder` — the decision journal, flight
  recorder, and incident capture (round 22): every runtime reflex
  emits one structured :class:`~.events.DecisionEvent` (parity with
  its metric counter pinned per kind), recent spans + gauge samples
  ride bounded always-on rings, and anomaly/breach/breaker/fault
  transitions materialize rate-limited, deduped, crash-safe
  ``slate_tpu.incident.v1`` snapshots (the ``/journal`` +
  ``/incidents`` routes; fleet folds in :mod:`.aggregate`).
* :mod:`.timeseries` / :mod:`.forecast` — the telemetry-history layer
  (round 23): a bounded per-series store (raw rings + 10 s/60 s
  min/max/sum/count downsample tiers, counter-to-rate, hard
  cardinality cap) fed by a ``pump()``-style Session sampler, and
  deterministic trend/seasonality forecasting over it
  (autocorrelation periodicity, seasonal-naive/Holt-Winters with
  confidence bands, ``predicted_hot`` / ``time_to_exhaustion`` — the
  elastic-fleet sensing substrate; ``/history`` + ``/forecast``
  routes; fleet fold in :mod:`.aggregate`).
* :mod:`.numerics`   — numerical-health telemetry (round 16): the
  growth-bound machinery (one source of truth with the tester), the
  Hager/Higham condest loop the Session drives with resident-factor
  solve applies, the deterministic residual-probe sampler, and the
  per-handle healthy/degraded/suspect monitor with counted demotion
  and eviction reflexes.

See DESIGN.md "Observability (round 8)" for the reference mapping
(Trace.hh Block/SVG -> span model + Chrome export; the global timers
map / --timer-level -> Metrics histograms / Prometheus text).
"""

from . import (aggregate, attribution, costs, events, flops, forecast,
               numerics, recorder, roofline, slo, timeseries, watchdog)
from .attribution import AttributionLedger
from .events import DecisionEvent, journal_digest, validate_incident
from .export import chrome_trace, validate_chrome_trace, write_chrome_trace
from .exposition import ObsServer, render_prometheus
from .forecast import Forecaster, forecast_points, validate_forecast
from .timeseries import (SessionSampler, TimeseriesStore,
                         validate_timeseries)
from .merge import combine_process_traces, lookahead_overlap, merge_traces
from .numerics import NumericsConfig, NumericsMonitor
from .recorder import (DecisionJournal, FlightRecorder, IncidentCapture,
                       Recorder)
from .slo import Objective, SloTracker
from .tracing import NOOP_SPAN, Span, Tracer, default_tracer
from .watchdog import Watchdog

__all__ = [
    "AttributionLedger", "DecisionEvent", "DecisionJournal",
    "FlightRecorder", "Forecaster", "IncidentCapture", "NOOP_SPAN",
    "NumericsConfig",
    "NumericsMonitor", "Objective", "ObsServer", "Recorder",
    "SessionSampler", "SloTracker", "Span", "TimeseriesStore", "Tracer",
    "Watchdog", "aggregate", "attribution", "chrome_trace",
    "combine_process_traces",
    "costs", "default_tracer", "events", "flops", "forecast",
    "forecast_points", "journal_digest",
    "lookahead_overlap",
    "merge_traces", "numerics", "recorder", "render_prometheus",
    "roofline", "slo", "timeseries",
    "validate_chrome_trace", "validate_forecast", "validate_incident",
    "validate_timeseries", "watchdog",
    "write_chrome_trace",
]


_trace_state_clean = None


def _jax_eager() -> bool:
    """True when we are executing eagerly (NOT inside a jax trace).
    Driver calls re-executed by ``jax.jit`` tracing (the serving
    Session's compiled factor/solve programs call api.* verbs inside
    jit) must credit NOTHING: the trace runs once per compiled shape,
    not per execution — crediting there would freeze the ledger at
    ~one call per shape and record compile durations as spans. The
    probe resolves lazily so importing obs never imports jax."""
    global _trace_state_clean
    if _trace_state_clean is None:
        try:
            from jax.core import trace_state_clean as tsc
        except ImportError:
            try:
                from jax._src.core import trace_state_clean as tsc
            except ImportError:  # unknown jax: assume eager (pre-existing
                tsc = lambda: True  # noqa: E731 — behavior, never worse)
        _trace_state_clean = tsc
    return _trace_state_clean()


def driver(name: str, flops_value: float = 0.0, **attrs):
    """Driver-entry hook used by api.py: credits the process FLOP
    ledger on every EAGER call (flops_total stays monotone with
    tracing off) and opens an ``api.<name>`` span when the default
    tracer is on. Under a jax trace it is a no-op (see ``_jax_eager``);
    work executed through compiled programs is credited by its caller
    — the serving Session records its executed factor/solve flops as
    ``serve.factor``/``serve.solve`` ledger ops."""
    if not _jax_eager():
        return NOOP_SPAN
    if flops_value:
        flops.LEDGER.record(name, flops_value)
    t = default_tracer()
    if not t.enabled:
        return NOOP_SPAN
    return t.span(f"api.{name}", **attrs)
