"""Resident-factorization solve service.

A ``Session`` owns device-resident factored operators (LU / Cholesky /
QR / banded) keyed by a user handle, so N solve requests against the
same operator pay ONE factorization — the TPU-native generalization of
the reference tester's persistent-matrix + ``*_solve_using_factor``
amortization (include/slate/simplified_api.hh), grown into a serving
component: an HBM-byte-budget LRU cache over the factors, explicit
eviction, refactor-on-miss, AOT compile warmup, and serving metrics.

Layering: the Session only calls the public simplified-API verbs
(``lu_factor``/``lu_solve_using_factor``, ``chol_factor``/..., the new
``qr_factor``/``least_squares_solve_using_factor``), so anything those
verbs learn (method dispatch, precision policy, sharding) is served
automatically. The C API's opaque-handle solves (compat/c_glue.py)
route through a process-wide ``default_session()`` so native callers
share the same cache.

**Mesh-native serving (round 11).** ``Session(mesh=...)`` (or
``register(A, mesh=...)``) makes the service pod-scale: a dense
operator registered against a p×q :class:`~..core.grid.ProcessGrid` is
2D-block placed over the mesh at registration (``TiledMatrix.shard`` —
the ``NamedSharding`` analog of the reference's ``BaseMatrix``
2D-block-cyclic layout), its factor is computed by the existing mesh
drivers (the GSPMD-partitioned blocked loops plus the explicit
``parallel/`` schedules the Options select) and stays **resident as a
sharded array across the mesh** — so aggregate HBM, not one chip's, is
the capacity ceiling. Mesh solves always run as ONE AOT-compiled
sharded program per (op, operand shapes, dtype, mesh): the first touch
of a shape compiles at the `_aot_compile` seam (off the request path
via ``warmup``; on it otherwise, counted in ``aot_compiles``), and
every execution credits the measured collective census — the
``collective_bytes_total`` / ``solve_collective_bytes_total`` counters
move per served solve, not per compile. The LRU budget becomes
**per-chip**: a sharded resident is charged its max-per-shard bytes
and the transient term is the largest analyzed program's per-device
temp+output footprint (XLA's memory analysis describes the per-device
SPMD module), so ``hbm_budget`` bounds what the worst chip holds.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, Hashable, List, Optional, Tuple

import jax
import numpy as np

from .. import api
from ..core.exceptions import SlateError
from ..core.grid import ProcessGrid, as_grid
from ..core.tiled_matrix import TiledMatrix, from_dense
from ..core.types import MatrixKind, Options, DEFAULT_OPTIONS
from ..linalg.band_packed import PackedBand
# model-GFLOP formulas live in the ledger (obs/flops.py) — one home
# shared with bench.py and tester.py instead of a private copy here
from ..obs import flops as _flops_mod
from ..obs.flops import LEDGER as _LEDGER
from ..obs.flops import factor_flops as _ff_raw
from ..obs.flops import solve_flops as _sf_raw
from ..obs import costs as _costs
# tenant/handle attribution (round 15): the grid snappers run
# UNCONDITIONALLY at the metric seams — model-flop counters land on
# the integer grid whether or not a ledger is attached, so enabling
# attribution never changes a global counter and the per-tenant rows
# sum to the globals bit-exactly (obs/attribution.py module docstring)
from ..obs.attribution import (DEFAULT_TENANT, PLACEMENT_SCHEMA,
                               fl_grid as _fl_grid, s_grid as _s_grid,
                               validate_placement_snapshot)
from ..obs.tracing import Tracer, default_tracer, log as _obs_log
# numerical-health telemetry (round 16): growth bounds, the
# Hager-Higham condest loop, the deterministic residual sampler, and
# the per-handle health monitor — jax-free; the Session drives it with
# resident-factor solve applies at its existing program seams
from ..obs import numerics as _num
from ..refine import engine as _refine_engine
from ..refine.policy import PolicyTable, RefinePolicy
from .metrics import Metrics
from .tenancy import as_table as _as_tenant_table


def _factor_flops(op: str, m: int, n: int, band: int = 0) -> float:
    """Model factor flops snapped to the integer grid (obs/attribution:
    exact float accumulation -> the per-tenant conservation invariant
    is bit-exact by arithmetic). <1e-13 relative change vs the raw
    lawn41 formula; every serving counter seam uses this wrapper."""
    return _fl_grid(_ff_raw(op, m, n, band))


def _solve_flops(op: str, m: int, n: int, k: int, band: int = 0) -> float:
    """Model solve flops on the integer grid (see _factor_flops)."""
    return _fl_grid(_sf_raw(op, m, n, k, band))


# operator kinds a Session can keep resident. The *_small family
# (round 10) is the many-small-problems engine: dense [n, n] ARRAY
# operators served through the hand-batched blocked kernels
# (linalg/batched) — the per-request path runs the SAME kernels at
# B=1 that the Batcher's grouped dispatch runs at B=bucket, so the
# batched and per-request paths are bit-identical by construction
# (batch-independent arithmetic, pinned by tests/test_batched.py).
OPS = ("lu", "chol", "qr", "band_lu", "band_chol",
       "lu_small", "chol_small", "eig", "svd")
SMALL_OPS = ("lu_small", "chol_small")
# resident spectral operators (round 19, slate_tpu/spectral/): the
# factor is the staged two-stage decomposition, the "solve" is the
# served matrix-function apply (two analyzed gemms + a diagonal scale)
SPECTRAL_OPS = ("eig", "svd")
# operators the round-16 condest probe covers (the gecondest/pocondest
# driver families; QR serves least-squares — trcondest on R is a
# different estimate — and band factors stay on the eager verbs)
CONDEST_OPS = ("lu", "chol", "lu_small", "chol_small")
# operators the sampled residual probe covers: b − A·x is an error
# signal only where x solves A·x = b (a least-squares minimizer's
# residual is data, not error)
PROBE_OPS = ("lu", "chol")
# operators the round-20 incremental-maintenance verb covers: rank-k
# Cholesky up/downdates (dense + small-engine residents) and QR row
# append/delete (linalg/update.py). Everything else answers a mutation
# with the refactor it always did.
UPDATE_OPS = ("chol", "chol_small", "qr")


def _work_dtype_name(entry) -> str:
    """Canonical working-dtype name of a registered operator (the
    refine/policy vocabulary the numerics thresholds scale by) — as
    the DEVICE computes it: without jax x64, a float64-registered
    small operand truly solves in float32, and scaling the residual
    thresholds by float64's eps would flag every healthy handle
    suspect (found by the obs_dump smoke, which runs without x64)."""
    from ..refine.policy import canonical_dtype_name
    A = entry.A
    dt = A.ab.dtype if isinstance(A, PackedBand) else A.dtype
    return canonical_dtype_name(jax.dtypes.canonicalize_dtype(dt))


def _tree_nbytes(payload, per_chip: bool = False) -> int:
    """Device bytes held by a factor payload (sum over pytree leaves).

    Computed from shape/dtype metadata ONLY: the old
    ``np.asarray(leaf).nbytes`` fallback device-transferred any leaf
    lacking ``.nbytes`` — a full factor copy through the host on the
    cache-accounting path (pinned by test: no ``__array__`` call).

    ``per_chip=True`` (round 11) charges a SHARDED leaf its
    max-per-shard bytes — ``sharding.shard_shape`` is pure metadata,
    and GSPMD shards are even, so the max shard is any shard — which
    is the number the per-chip HBM budget must bound. Unsharded (or
    fully replicated) leaves charge their full bytes on every chip,
    which is exactly what replication costs."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(payload):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            if per_chip:
                sharding = getattr(leaf, "sharding", None)
                shard_shape = getattr(sharding, "shard_shape", None)
                if shard_shape is not None:
                    try:
                        shape = shard_shape(tuple(shape))
                    except Exception:
                        pass  # charge the full (replicated) bytes
            n = 1
            for d in shape:
                n *= int(d)
            total += n * np.dtype(dtype).itemsize
        elif getattr(leaf, "nbytes", None) is not None:
            total += int(leaf.nbytes)
        else:  # python scalar leaf: its device form is one element
            total += np.dtype(type(leaf)).itemsize if isinstance(
                leaf, (int, float, complex)) else 0
    return total


@dataclasses.dataclass
class _Operator:
    """A registered (not necessarily factored) operator."""

    A: Any                   # TiledMatrix or PackedBand
    op: str
    opts: Options
    m: int
    n: int
    band: int = 0            # kl+ku (band ops) for flop accounting
    # serving mesh (round 11): dense operators registered against a
    # multi-device grid are factored/solved as sharded AOT programs
    # and their residents charged per-chip; None = single-device
    grid: Optional[ProcessGrid] = None
    # mixed-precision refinement (round 13, slate_tpu/refine/): the
    # resident factor is computed/stored at policy.factor_dtype and
    # every solve refines to working accuracy; None = full precision.
    # Cleared (with the lo resident evicted) when refinement falls
    # back — the counted, observable non-convergence path.
    refine: Optional[RefinePolicy] = None
    # ‖A‖_inf, computed once at first refined solve (the convergence
    # constant's norm — gesv_mixed.cc:34-43)
    anorm: Optional[float] = None
    # ‖A‖_1, computed once at the first condest probe (round 16 —
    # Hager's estimator reports ‖A⁻¹‖_1, so κ̂₁ needs the 1-norm)
    anorm1: Optional[float] = None
    # attribution tenant (round 15): who this operator belongs to.
    # None = the DEFAULT_TENANT — every existing caller lands there,
    # so single-tenant deployments get the ledger without changes
    tenant: Optional[str] = None
    # incremental-maintenance accrual (round 20): applied-update count
    # and growth-weighted error mass since the last fresh factor — the
    # monitor-less fallback for the refactor-due predicate (a numerics
    # monitor, when attached, keeps the authoritative copy per handle).
    # Reset by every fresh factor insert.
    updates: int = 0
    update_weight: float = 0.0
    # tuned-config provenance (round 21): the tuning-table entry (or
    # shadow-tuner promotion) whose knobs this operator's opts carry —
    # the `tuned_config` span attr / cost_log column. None = defaults.
    tuned: Optional[str] = None


@dataclasses.dataclass
class _Resident:
    """A cached factorization (the HBM the LRU budget governs).

    ``nbytes`` is the BUDGET CHARGE: per-chip bytes (max-per-shard for
    mesh residents — the worst chip's share; equal to the total on a
    single device). ``nbytes_total`` is the aggregate bytes across the
    mesh, kept for the ``resident_bytes_total`` gauge."""

    payload: Tuple           # args for the *_solve_using_factor verb
    info: int
    nbytes: int
    nbytes_total: int = 0

    def __post_init__(self):
        if not self.nbytes_total:
            self.nbytes_total = self.nbytes


class Session:
    """Resident-factorization solve service with an HBM-budget LRU cache.

    ``hbm_budget`` bounds the PER-CHIP device bytes of CACHED FACTORS
    (the registered operators themselves are the caller's inputs and
    are not charged): a mesh resident is charged its max-per-shard
    bytes, a single-device resident its full bytes — identical when
    there is no mesh, so the budget means "what the worst chip holds"
    uniformly. ``None`` means unbounded. Factors are built lazily on
    the first solve (refactor-on-miss) and evicted least-recently-used
    when an insert would exceed the budget; a single factor larger than
    the whole budget is kept (you cannot serve without it) and counted
    in the ``budget_overflows`` metric.

    All public methods are thread-safe; solve dispatch is serialized
    under one lock (the device executes one program at a time anyway —
    the batcher, not thread fan-out, is the throughput lever).
    """

    def __init__(self, hbm_budget: Optional[int] = None,
                 opts: Options = DEFAULT_OPTIONS,
                 metrics: Optional[Metrics] = None,
                 tracer: Optional[Tracer] = None,
                 mesh=None, slo=None,
                 refine_policies: Optional[PolicyTable] = None,
                 faults=None, attribution=None, numerics=None,
                 checkpoint_dir: Optional[str] = None,
                 tenant_policies=None, tuning=None):
        self.hbm_budget = hbm_budget
        # autotuning table (round 21, slate_tpu/tuning/): a
        # TuningTable / loaded doc / path, or True for the committed
        # repo-root TUNING_r01.json. None = disabled — every
        # consultation seam is ONE `tuning is None` check and with no
        # table every solve is bit-identical to an untuned session
        # (pinned). register() resolves each operator's
        # nb/inner_blocking/lookahead through the table by first-match
        # (op, n-bucket, dtype, platform); the resolved provenance
        # rides span attrs and the cost_log as `tuned_config`. A
        # session-held table is also ACTIVATED process-globally for
        # the linalg/batched bucket cache (its programs are
        # process-global, so its tuning seam is too — last activation
        # wins; tuning.activate_table(None) restores defaults).
        from .. import tuning as _tuning_mod
        self.tuning = _tuning_mod.as_table(tuning)
        if self.tuning is not None:
            _tuning_mod.activate_table(self.tuning)
        # tenant isolation (round 18, runtime/tenancy.py): a
        # TenantTable (or {tenant: TenantPolicy} dict) declaring
        # per-tenant HBM sub-budgets (enforced here at the
        # factor-insert seam with per-tenant LRU eviction — tenant A's
        # pressure can NEVER evict tenant B's resident, pinned),
        # in-flight caps and flops/s rates (enforced at
        # Batcher.submit), and fair-share weights (the Batcher's
        # deficit-weighted dispatch). None = disabled: every seam is
        # one is-None check, zero allocation (the round-8 discipline,
        # pinned by test).
        self.tenant_policies = _as_tenant_table(tenant_policies)
        # durable-state directory (round 17): when set, close() flushes
        # a final checkpoint (runtime/checkpoint.py) + placement
        # snapshot there — the artifact the fleet coordinator's
        # failover restores from after this process dies. None = the
        # pre-round-17 behavior (close drops resident state).
        self.checkpoint_dir = checkpoint_dir
        # numerical-health telemetry (round 16): None = disabled —
        # every seam guards with ONE `numerics is None` check and
        # allocates nothing (the round-8 tracer discipline, pinned by
        # test). An obs.numerics.NumericsMonitor tracks per-handle
        # condest / growth / sampled-residual / refine-drift signals
        # into a healthy/degraded/suspect state with counted reflexes
        # (suspect handles are demoted off the refine ladder and lose
        # eviction tie-breaks — never silently).
        self.numerics = numerics
        # tenant/handle attribution (round 15): None = disabled — every
        # seam guards with ONE `attr is None` check and allocates
        # nothing (the round-8 tracer discipline, pinned by test). An
        # obs.attribution.AttributionLedger accounts flops, bytes, ICI
        # bytes, device/queue seconds, HBM residency byte-seconds,
        # cache hits/misses, and request outcomes per (tenant, handle),
        # plus EWMA handle heat — the placement/quota sensing substrate
        self.attribution = attribution
        # deterministic fault injection (round 14): None = disabled —
        # every seam guards with ONE `faults is None` check, so the
        # production hot path pays nothing (the round-8 tracer
        # discipline, pinned by test). A runtime/faults.FaultInjector
        # makes dispatch failures, slow devices, compile stalls, HBM
        # exhaustion, and refine non-convergence reproducible inputs.
        self.faults = faults
        # flight recorder + decision journal (round 22,
        # obs/recorder.py): None = disabled — every reflex seam guards
        # with ONE `recorder is None` check and allocates nothing (the
        # round-8 discipline, pinned by test). enable_recorder() is
        # the opt-in; every counted reflex decision then also lands a
        # structured DecisionEvent (events.KIND_COUNTERS parity,
        # pinned), and anomaly/breach/breaker/fault transitions
        # capture rate-limited incident snapshots.
        self.recorder = None
        # telemetry history (round 23, obs/timeseries.py): None =
        # disabled — the pump seam guards with ONE `timeseries is
        # None` check and allocates nothing (the round-8 discipline,
        # pinned by test). enable_timeseries() attaches the bounded
        # time-series store, the pump()-style sampler (thread-free:
        # Fleet.pump / a chaos driver / a scrape loop calls
        # pump_timeseries on its own thread), and the forecaster
        # behind the /history and /forecast routes.
        self.timeseries = None
        self.forecaster = None
        self._ts_sampler = None
        self.opts = opts
        # mixed-precision policy table (round 13): register(...,
        # refine=True) resolves its RefinePolicy here per
        # (op, n, working dtype); the default table falls back to the
        # one-tier-down dtype ladder (refine/policy.py)
        self.refine_policies = refine_policies or PolicyTable()
        # serving mesh: a ProcessGrid or a jax Mesh with ("p", "q")
        # axes; every dense operator registered without an explicit
        # per-operator mesh is sharded over it (mesh docstring above).
        # With a mesh, hbm_budget bounds PER-CHIP bytes.
        self.grid = as_grid(mesh)
        self.metrics = metrics or Metrics()
        if attribution is not None and attribution.metrics is None:
            attribution.metrics = self.metrics  # heat gauges land here
        if numerics is not None and numerics.metrics is None:
            numerics.metrics = self.metrics  # health gauges land here
        # request-scoped tracing: disabled by default (the shared
        # default tracer starts off) — zero spans, no per-solve cost
        # beyond one enabled-flag check per phase
        self.tracer = tracer or default_tracer()
        # SLO tracking (round 12): None = disabled, zero per-solve cost
        # beyond one attribute check (the round-8 discipline); an
        # obs.slo.SloTracker records request/cache/oom events here and
        # through the Batcher, evaluated at /slo scrape time
        self.slo = slo
        if slo is not None and slo.metrics is None:
            slo.metrics = self.metrics
        if slo is not None and slo.tracer is None:
            slo.tracer = self.tracer
        # per-shape compile observability (Session.warmup + refactor-on-
        # miss): [{op, what, shape, lower_s, compile_s}, ...]
        self.compile_log: List[dict] = []
        # per-shape COST observability (ISSUE 5): one row per AOT-
        # compiled program — model flops, XLA bytes-accessed, arg/out/
        # temp/peak HBM, collective census (obs/costs.py)
        self.cost_log: List[dict] = []
        # (op, what) -> newest model_flops row, maintained as cost_log
        # grows: the shed-ordering read (recompute_cost) runs under
        # the Batcher's queue lock per queued request and must be O(1),
        # not a cost_log scan
        self._cost_index: Dict[Tuple[str, str], float] = {}
        # AOT-key -> ProgramCosts for resident executables; drives the
        # per-execution bytes crediting and the transient-footprint
        # term of the HBM budget (evicted in step with _compiled)
        self._program_costs: Dict[Hashable, _costs.ProgramCosts] = {}
        self._obs_server = None
        self._lock = threading.RLock()
        self._ops: Dict[Hashable, _Operator] = {}
        self._cache: "OrderedDict[Hashable, _Resident]" = OrderedDict()
        # per-(op, opts) jitted solve fns and per-shape AOT executables;
        # both LRU-capped: compiled programs hold device memory, and a
        # long-lived session serving many distinct shapes would
        # otherwise re-grow the unbounded-residency problem the factor
        # budget bounds (evicted entries simply recompile on reuse)
        self._jit: "OrderedDict[Hashable, Any]" = OrderedDict()
        self._compiled: "OrderedDict[Hashable, Any]" = OrderedDict()
        self._jit_cap = 64
        self._compiled_cap = 128
        self._seq = 0

    def enable_slo(self, objectives=None, **kw):
        """Attach an :class:`~..obs.slo.SloTracker` (default
        objectives unless given) bound to this session's metrics and
        tracer; idempotent — a second call returns the running tracker.
        The ``/slo`` route of :meth:`serve_obs` serves its payload."""
        from ..obs.slo import SloTracker
        with self._lock:
            if self.slo is None:
                self.slo = SloTracker(objectives, metrics=self.metrics,
                                      tracer=self.tracer, **kw)
                if self.recorder is not None:
                    # breach transitions are incident triggers (rd 22)
                    self.slo.recorder = self.recorder
            return self.slo

    def enable_attribution(self, halflife_s: float = 300.0, **kw):
        """Attach an :class:`~..obs.attribution.AttributionLedger`
        (heat halflife ``halflife_s``) bound to this session's metrics
        and return it; idempotent — a second call returns the running
        ledger. The ``/tenants`` route of :meth:`serve_obs` serves its
        payload and ``/metrics`` grows the ``tenant_*`` sections."""
        from ..obs.attribution import AttributionLedger
        with self._lock:
            if self.attribution is None:
                self.attribution = AttributionLedger(
                    halflife_s=halflife_s, metrics=self.metrics, **kw)
            return self.attribution

    def enable_numerics(self, config=None, **kw):
        """Attach an :class:`~..obs.numerics.NumericsMonitor` (round
        16) bound to this session's metrics and return it; idempotent
        — a second call returns the running monitor. ``config`` is a
        :class:`~..obs.numerics.NumericsConfig` (or kwargs for one:
        ``sample_fraction=``, thresholds, ...). The ``/numerics``
        route of :meth:`serve_obs` serves its payload and ``/metrics``
        grows the ``handle_health`` gauges."""
        from ..obs.numerics import NumericsMonitor
        with self._lock:
            if self.numerics is None:
                self.numerics = NumericsMonitor(
                    config, metrics=self.metrics, **kw)
            return self.numerics

    def request_tenant(self, handle: Hashable,
                       override: Optional[str] = None) -> str:
        """Resolved tenant of one request: the explicit per-request
        override, else the operator's registered tenant, else the
        DEFAULT_TENANT. Lock-free (the op_meta discipline: called from
        the Batcher's outcome paths, which must never wait on a device
        execution)."""
        if override is not None:
            return str(override)
        entry = self._ops.get(handle)
        t = None if entry is None else entry.tenant
        return DEFAULT_TENANT if t is None else t

    def _attr_evicted(self, handle: Hashable):
        """Caller verified ``self.attribution is not None``. Close the
        handle's residency interval (final byte-second accrual credits
        the same value to the cell and the global counter) and advance
        its heat (decay only — an eviction is not an access)."""
        attr = self.attribution
        inc = attr.end_residency(handle)
        if inc:
            self.metrics.inc("residency_byte_seconds_total", inc)
        attr.touch_eviction(handle)

    def _journal_evict(self, rec, handle, nbytes, reason,
                       entry=None, **inputs):
        """Caller verified ``rec`` (= self.recorder) is not None: ONE
        reason-tagged eviction DecisionEvent — every seam that bumps
        the ``evictions`` counter funnels here, so the journal/counter
        parity per events.KIND_COUNTERS stays exact."""
        if entry is None:
            entry = self._ops.get(handle)
        rec.decision("eviction",
                     op=None if entry is None else entry.op,
                     handle=handle,
                     tenant=None if entry is None else entry.tenant,
                     outcome=reason,
                     inputs=dict(inputs, nbytes=nbytes))

    def enable_faults(self, plan=None, seed: int = 1):
        """Attach a :class:`~.faults.FaultInjector` built from ``plan``
        (default: :func:`~.faults.default_plan` under ``seed``) and
        return it — the chaos runner's entry point. Idempotent in the
        enable_slo sense: a second call replaces the injector (a new
        soak wants fresh counters)."""
        from .faults import FaultInjector, FaultPlan, default_plan
        if plan is None:
            plan = default_plan(seed)
        elif isinstance(plan, dict):
            plan = FaultPlan.from_dict(plan)
        self.faults = FaultInjector(plan)
        if self.recorder is not None:
            # injector firings are incident triggers (round 22)
            self.faults.recorder = self.recorder
        return self.faults

    def enable_recorder(self, incident_dir: Optional[str] = None,
                        host: Optional[str] = None, **kw):
        """Attach an :class:`~..obs.recorder.Recorder` (round 22): the
        decision journal + flight recorder + incident capture, bound
        to this session's metrics and tracer; idempotent — a second
        call returns the running recorder. ``incident_dir`` enables
        crash-safe on-disk incident snapshots (atomic publish);
        ``kw`` forwards ring capacities and rate-limit/dedup windows.
        The ``/journal`` and ``/incidents`` routes of
        :meth:`serve_obs` serve its payloads."""
        from ..obs.recorder import Recorder
        with self._lock:
            if self.recorder is None:
                rec = Recorder(incident_dir=incident_dir, host=host,
                               metrics=self.metrics,
                               tracer=self.tracer, **kw)
                rec.providers.update({
                    "metrics": self.metrics.snapshot,
                    "numerics": self.numerics_payload,
                    "quotas": self.quotas_payload,
                    "placement": self.placement_snapshot,
                    # the newest rows carry the implicated programs'
                    # compile provenance; the full log stays on /costs
                    "cost_log": lambda: list(self.cost_log[-64:]),
                    "tuning": self._tuning_provenance,
                })
                # finished spans feed the flight ring (tracing hook)
                self.tracer.recorder = rec
                if self.faults is not None:
                    self.faults.recorder = rec
                if self.slo is not None:
                    self.slo.recorder = rec
                self.recorder = rec
            return self.recorder

    def enable_timeseries(self, interval_s: float = 1.0,
                          clock=time.time, host: Optional[str] = None,
                          **kw):
        """Attach the telemetry-history layer (round 23): a bounded
        :class:`~..obs.timeseries.TimeseriesStore`, a ``pump()``-style
        :class:`~..obs.timeseries.SessionSampler` throttled to
        ``interval_s`` (drive it with :meth:`pump_timeseries`), and a
        :class:`~..obs.forecast.Forecaster` over the store; idempotent
        — a second call returns the running store. ``clock`` is
        injectable (chaos drills and tests run on a scripted clock —
        no sleeps). ``kw`` forwards ring capacities / tier widths /
        ``max_series``. The ``/history`` and ``/forecast`` routes of
        :meth:`serve_obs` serve the payloads."""
        from ..obs.forecast import Forecaster
        from ..obs.timeseries import SessionSampler, TimeseriesStore
        with self._lock:
            if self.timeseries is None:
                store = TimeseriesStore(host=host, clock=clock, **kw)
                self._ts_sampler = SessionSampler(
                    self, store, interval_s=interval_s)
                self.forecaster = Forecaster(store)
                self.timeseries = store
            return self.timeseries

    def pump_timeseries(self, now: Optional[float] = None,
                        force: bool = False) -> int:
        """One history-sampling pass (round 23): snapshot gauges (at
        their stamped timestamps), counter deltas, per-handle heat,
        and per-tenant burn rates into the store. Thread-free and
        throttled; returns samples recorded (0 when throttled or
        disabled). Disabled (the default) costs ONE is-None check."""
        if self.timeseries is None:
            return 0
        return self._ts_sampler.pump(now=now, force=force)

    def _tuning_provenance(self) -> dict:
        """Incident-capture section: which handles serve under which
        resolved/promoted config right now."""
        with self._lock:
            handles = {repr(h): e.tuned for h, e in self._ops.items()
                       if getattr(e, "tuned", None) is not None}
        return {"table": self.tuning is not None, "handles": handles}

    def _fault(self, site: str):
        """Apply one fault opportunity at ``site`` (caller verified
        ``self.faults is not None``): count what fired, sleep the
        latency-shaped kinds first (a slow-and-then-failing device
        sleeps before failing, like the real thing), then raise for
        ``dispatch_error``. Returns the fired specs so boolean seams
        (hbm, refine.lo_factor) can branch on truthiness."""
        from .faults import TransientDispatchError
        fired = self.faults.fire(site)
        for spec in fired:
            self.metrics.inc("faults_injected_total")
            self.metrics.inc("fault:" + spec.kind)
            if spec.latency_s:
                time.sleep(spec.latency_s)
        for spec in fired:
            if spec.kind == "dispatch_error":
                raise TransientDispatchError(
                    f"injected transient dispatch failure at {site!r}")
        return fired

    def recompute_cost(self, handle: Hashable, ncols: int = 1) -> float:
        """Model flops the fleet pays again if this request is SHED and
        the client retries — the load shedder's cheapest-first ordering
        key. Prefers the round-9 ``cost_log``'s per-program
        ``model_flops`` rows (what the AOT seam actually measured for
        this op); falls back to the ledger formulas for ops never
        compiled through it. A request against a RESIDENT factor costs
        one solve; a non-resident one costs factor + solve — so
        shedding prefers requests whose operators are still hot.
        Lock-free (GIL-atomic dict/list reads, the op_meta discipline):
        the Batcher calls this under its own lock and must never wait
        on a device execution."""
        entry = self._ops.get(handle)
        if entry is None:
            return 0.0
        cost = (self._logged_flops(entry.op, "solve")
                or _solve_flops(entry.op, entry.m, entry.n, max(ncols, 1),
                                entry.band))
        if handle not in self._cache:
            cost += (self._logged_flops(entry.op, "factor")
                     or _factor_flops(entry.op, entry.m, entry.n,
                                      entry.band))
        return cost

    def _logged_flops(self, op: str, what: str) -> float:
        """Newest cost_log model_flops row for (op, what), 0.0 when the
        op never compiled through the AOT seam. O(1): the index is
        maintained as _aot_compile appends rows (GIL-atomic dict read —
        this runs under the Batcher lock on the shed path)."""
        return self._cost_index.get((op, what), 0.0)

    def degrade_class(self, handle: Hashable) -> Optional[str]:
        """Which DEGRADATION_LADDER family a handle's serving path
        belongs to ("mesh" / "mixed" / "dense"), None for unknown
        handles. Grouped small buckets classify themselves (the
        Batcher's _SMALL key). Lock-free, op_meta discipline."""
        entry = self._ops.get(handle)
        if entry is None:
            return None
        if entry.grid is not None:
            return "mesh"
        if entry.refine is not None:
            return "mixed"
        return "dense"

    def demote_to_working_precision(self, handle: Hashable) -> bool:
        """The mixed→working_precision rung of the degradation ladder,
        walked by the Executor's circuit breaker AND (round 16) the
        numerics suspect reflex: deactivate the refine policy and
        evict the low-precision resident so the next solve refactors
        at working precision (the same observable fallback refine
        non-convergence takes — counted separately in
        ``refine_demotions_total``; a numerics-driven demotion
        additionally counts ``health_demotions_total``, so the three
        causes stay distinguishable)."""
        with self._lock:
            entry = self._ops.get(handle)
            if entry is None or entry.refine is None:
                return False
            entry.refine = None
            dropped = self._cache.pop(handle, None)
            if dropped is not None:
                self.metrics.inc("evictions")
                self.metrics.inc("evicted_bytes", dropped.nbytes)
                if self.attribution is not None:
                    self._attr_evicted(handle)
            self.metrics.inc("refine_demotions_total")
            rec = self.recorder
            if rec is not None:
                if dropped is not None:
                    rec.decision("eviction", op=entry.op, handle=handle,
                                 tenant=entry.tenant,
                                 outcome="refine_demotion",
                                 inputs={"nbytes": dropped.nbytes})
                rec.decision("refine_demotion", op=entry.op,
                             handle=handle, tenant=entry.tenant,
                             outcome="working_precision")
            self._update_hbm_gauges()
        _obs_log.warning(
            "degradation ladder: operator %r demoted to working "
            "precision", handle)
        return True

    # -- numerical health (round 16, obs/numerics.py) ----------------------

    def _health_reflex(self, entry: _Operator, handle: Hashable,
                       old: str, new: str):
        """Caller verified ``self.numerics is not None``. The counted
        reflexes on a health-state transition: a handle that turns
        SUSPECT while serving from a low-precision resident is demoted
        off the refine ladder (the round-14
        ``demote_to_working_precision`` rung — ``refine_demotions_total``
        moves, plus ``health_demotions_total`` so a numerics-driven
        demotion is distinguishable from a breaker-driven one). Suspect
        handles also lose eviction tie-breaks (:meth:`_eviction_order`).
        Never silent: the monitor already logged/counted the
        transition."""
        if new == old:
            return
        if new == "suspect" and entry.refine is not None:
            self.metrics.inc("health_demotions_total")
            rec = self.recorder
            if rec is not None:
                _st, condest, growth = \
                    self.numerics.placement_info(handle)
                rec.decision("health_demotion", op=entry.op,
                             handle=handle, tenant=entry.tenant,
                             inputs={"from": old, "to": new,
                                     "condest": condest,
                                     "growth": growth},
                             outcome="suspect")
            _obs_log.warning(
                "numerics reflex: suspect operator %r demoted off the "
                "refine ladder", handle)
            self.demote_to_working_precision(handle)

    def condest(self, handle: Hashable) -> float:
        """Hager-Higham 1-norm condition estimate κ̂₁(A) ≈ ‖A‖₁‖A⁻¹‖₁
        from the RESIDENT factor (factoring on miss) — the serving
        analog of slate::gecondest/pocondest (LAPACK ``?gecon``): a
        handful of extra ``*_solve_using_factor`` applies driven by
        :func:`~..obs.numerics.norm1est`, each executing the SAME
        analyzed AOT solve programs the serving path runs (mesh
        residents included — zero new compiles after :meth:`warmup`),
        credited per execution to the cost/attribution ledgers under
        the ``numerics.condest`` op. Covers lu/chol operators (dense —
        single-device or mesh-sharded — and the *_small engine).
        Records into the attached NumericsMonitor (if any) and runs
        the health reflexes on the resulting transition."""
        with self._lock:
            entry = self._ops.get(handle)
            if entry is None:
                raise SlateError(f"Session: unknown handle {handle!r}")
            if entry.op not in CONDEST_OPS:
                raise SlateError(
                    f"Session.condest: covers {CONDEST_OPS}, not "
                    f"{entry.op!r}")
            nm = self.numerics
            hit = handle in self._cache
            res = self.factor(handle)
            if res.info != 0:
                raise SlateError(
                    f"Session.condest: operator {handle!r} factorization "
                    f"failed (info={res.info})")
            if (not hit and nm is not None
                    and nm.config.condest_on_factor):
                # the factor-on-miss just ran the estimator at its own
                # seam (_numerics_after_factor) — return that estimate
                # instead of paying the probe solves twice for one
                # logical question
                ce = nm.placement_info(handle)[1]
                if ce is not None:
                    return ce
            # a factor-time health reflex may have demoted + refactored
            # (the returned res IS the serving resident either way)
            return self._condest_locked(entry, handle, res)

    def _condest_locked(self, entry: _Operator, handle: Hashable,
                        res: _Resident) -> float:
        """Caller holds the lock; ``res`` is a successful resident."""
        nm = self.numerics
        cfg = nm.config if nm is not None else _num.NumericsConfig()
        n = entry.n
        if entry.anorm1 is None:
            if entry.op in SMALL_OPS:
                a = np.asarray(entry.A)
                entry.anorm1 = float(
                    np.abs(a.astype(np.complex128 if np.iscomplexobj(a)
                                    else np.float64)).sum(axis=0).max())
            else:
                from ..core.types import Norm
                from ..linalg.norms import norm as _norm
                entry.anorm1 = float(_norm(entry.A, Norm.One))
        wd = _work_dtype_name(entry)
        cplx = wd.startswith("complex")
        solve, solve_h = self._condest_applies(entry, handle, res, cplx)
        est, solves = _num.norm1est(solve, solve_h, n, complex_=cplx,
                                    max_iter=cfg.condest_max_iter)
        cond = (float("inf") if est <= 0.0 or entry.anorm1 <= 0.0
                else entry.anorm1 * est)
        if not np.isfinite(cond):
            # the session-level sentinel counter must agree with the
            # per-handle nonfinite field record_condest bumps below
            self.metrics.inc("numerics_nonfinite_total")
        # probe-work crediting: `solves` factor applies of one column
        # each — the model-flop seam every serving counter uses, on a
        # dedicated counter/ledger op so client-attributed solve work
        # stays conserving (numerics probes are system work)
        fl = solves * _solve_flops(entry.op, entry.m, entry.n, 1,
                                   entry.band)
        self.metrics.inc("condest_runs_total")
        self.metrics.inc("condest_solves_total", solves)
        self.metrics.inc("numerics_flops_total", fl)
        self.metrics.inc("flops_total", fl)
        _LEDGER.record("numerics.condest", fl)
        if nm is not None:
            old, new = nm.record_condest(handle, cond)
            self._health_reflex(entry, handle, old, new)
        return cond

    def _condest_applies(self, entry: _Operator, handle: Hashable,
                         res: _Resident, cplx: bool):
        """Caller holds the lock. (x ↦ A⁻¹x, x ↦ A⁻ᴴx) host callables
        over the resident factor for :func:`~..obs.numerics.norm1est`
        (np [n, 1] float64/complex128 in and out).

        Dense operators run the SAME solve programs the serving path
        uses (warmup-compiled AOT executables when shapes match — the
        mesh zero-new-compiles claim; refined residents apply through
        the refine ``start`` program, i.e. cast-down → lo factor apply
        → cast-up, so the estimate describes the factor that actually
        serves). LU adds one conjugate-transpose-solve program
        (``condest_t``), compiled through the analyzed AOT seam.
        Small operators run their B=1 bucket programs
        (accounting-suppressed — the condest seam credits explicitly);
        the lu_small transpose solve runs host-side from a one-time
        factor gather (triangular solves at small n)."""
        op = entry.op
        payload = res.payload
        tenant = entry.tenant

        if op in SMALL_OPS:
            from ..linalg import batched as _batched
            if op == "chol_small":
                lfac = payload[0]

                def apply(x):
                    with _batched.suppress_accounting():
                        y = _batched.potrs_batched(
                            lfac[None], np.ascontiguousarray(x)[None])
                    return np.asarray(jax.block_until_ready(y))[0]

                # A⁻ᴴ = A⁻¹ for an HPD operator (pocondest: one solver)
                return apply, apply
            lu_d, perm_d = payload

            def apply(x):
                with _batched.suppress_accounting():
                    y = _batched.getrs_batched(
                        lu_d[None], perm_d[None],
                        np.ascontiguousarray(x)[None])
                return np.asarray(jax.block_until_ready(y))[0]

            # host conjugate-transpose solve from the gathered factor:
            # a[perm] = L·U (gather semantics, linalg/batched), so
            # A⁻ᴴx = Pᵀ·L⁻ᴴ·U⁻ᴴ·x — scatter rows back through perm
            work = np.complex128 if cplx else np.float64
            lu_h = np.asarray(lu_d).astype(work)
            perm_h = np.asarray(perm_d).astype(np.int64)
            nloc = lu_h.shape[0]
            l_h = np.tril(lu_h, -1) + np.eye(nloc)
            u_h = np.triu(lu_h)

            def apply_h(x):
                w = np.linalg.solve(u_h.conj().T, x)
                v = np.linalg.solve(l_h.conj().T, w)
                y = np.zeros_like(v)
                y[perm_h] = v
                return y

            return apply, apply_h

        # dense lu/chol (single-device, mesh-sharded, or refined)
        def host(X):
            return (X.to_numpy() if isinstance(X, TiledMatrix)
                    else np.asarray(X))

        if entry.refine is not None:
            def fwd(x):
                B = self._wrap_rhs(entry, np.ascontiguousarray(x))
                exe, key = self._refine_exe(entry, handle, "start",
                                            (payload, B))
                X = exe(payload, B)
                self._credit_program(key, "numerics.condest",
                                     tenant=tenant, handle=handle)
                return host(X)
        else:
            solve_fn = self._solve_fn(entry)

            def fwd(x):
                B = self._wrap_rhs(entry, np.ascontiguousarray(x))
                Bw = _at_tile_width(B)
                key = self._aot_key(entry, payload, Bw)
                exe = self._compiled.get(key)
                if exe is None and entry.grid is not None:
                    exe = self._aot_compile("solve", entry, handle,
                                            solve_fn, (payload, Bw),
                                            key=key)
                    self._compiled_put(key, exe)
                    self.metrics.inc("aot_compiles")
                if exe is not None:
                    self._compiled.move_to_end(key)
                    self._credit_program(key, "numerics.condest",
                                         tenant=tenant, handle=handle)
                    return host(_at_width_of(exe(payload, Bw), B))
                return host(_at_width_of(solve_fn(payload, Bw), B))

        if op == "chol":
            # A⁻ᴴ = A⁻¹ (HPD resident) — the pocondest convention
            return fwd, fwd

        def tsolve(x):
            xq = np.conj(x) if cplx else x
            B = self._wrap_rhs(entry, np.ascontiguousarray(xq))
            exe, key = self._condest_texe(entry, handle, payload, B)
            Y = exe(payload, B)
            if key is not None:
                self._credit_program(key, "numerics.condest",
                                     tenant=tenant, handle=handle)
            y = host(Y)
            return np.conj(y) if cplx else y

        return fwd, tsolve

    def _condest_tfn(self, entry: _Operator):
        """The LU conjugate-transpose-solve closure (x ↦ A⁻ᵀx via
        ``getrs(..., trans=True)``; the host wrapper conjugates around
        it for complex dtypes). Refined residents cast the rhs down to
        the factor dtype and the result back up, mirroring the refine
        ``start`` program — the estimate must describe the factor that
        serves."""
        opts = entry.opts
        if entry.refine is not None:
            policy = entry.refine
            work = entry.A.dtype

            def make():
                from ..linalg import elementwise as ew
                from ..linalg.lu import getrs as _getrs
                from ..refine.policy import jax_dtype as _jd
                lo = _jd(policy.factor_dtype)

                def tsolve(payload, B):
                    LU, perm = payload
                    Y = _getrs(LU, perm, ew.copy(B, dtype=lo), opts,
                               trans=True)
                    return ew.copy(Y, dtype=work)
                tsolve.__name__ = "serve_lu_condest_t_refined"
                return tsolve

            return self._jit_cached(
                ("condest_t", entry.op, opts, policy,
                 str(np.dtype(entry.A.dtype))), make)

        def make():
            from ..linalg.lu import getrs as _getrs

            def tsolve(payload, B):
                LU, perm = payload
                return _getrs(LU, perm, B, opts, trans=True)
            tsolve.__name__ = "serve_lu_condest_t"
            return tsolve

        return self._jit_cached(("condest_t", entry.op, opts), make)

    def _condest_texe(self, entry: _Operator, handle: Hashable,
                      payload, B):
        """AOT-compiled ``condest_t`` program for these shapes →
        (exe, key) — always through the analyzed ``_aot_compile`` seam
        (the _refine_exe discipline: per-execution bytes/census
        crediting; warmup precompiles it so a warmed operator's
        condest adds zero compiles)."""
        leaves, treedef = jax.tree_util.tree_flatten((payload, B))
        shapes = tuple((tuple(l.shape), str(l.dtype)) for l in leaves)
        key = ("condest_t", entry.op, entry.opts, entry.refine, treedef,
               shapes)
        exe = self._compiled.get(key)
        if exe is None:
            fn = self._condest_tfn(entry)
            exe = self._aot_compile("condest_t", entry, handle, fn,
                                    (payload, B), key=key)
            self._compiled_put(key, exe)
            self.metrics.inc("aot_compiles")
        else:
            self._compiled.move_to_end(key)
        return exe, key

    def op_meta(self, handle: Hashable) -> Optional[Tuple[str, int]]:
        """Lock-free (op, n) of a registered handle, or None — the
        Batcher/Executor SLO- and stage-attribution read (same
        GIL-atomic dict-read discipline as ``small_group_key``: the
        session lock is held across device executions, and an enqueue
        must never wait on one)."""
        entry = self._ops.get(handle)
        return None if entry is None else (entry.op, entry.n)

    # -- registration ------------------------------------------------------

    def register(self, A, op: str = "auto",
                 handle: Optional[Hashable] = None,
                 opts: Optional[Options] = None,
                 mesh=None, refine=None,
                 tenant: Optional[str] = None) -> Hashable:
        """Register an operator; returns its handle (auto-allocated int
        when not given). ``op``: one of {lu, chol, qr, band_lu,
        band_chol} or "auto" (PackedBand → band_*, Hermitian/Symmetric
        → chol, rectangular → qr, else lu).

        ``mesh`` (a ProcessGrid or ("p", "q") jax Mesh) places THIS
        operator on a grid, overriding the session mesh in BOTH
        directions — an explicit 1×1 grid registers the operator
        single-device on a mesh session. A dense TiledMatrix is
        2D-block sharded over the grid at registration and its factor
        stays mesh-resident (module docstring). An operand that
        already carries a multi-device grid is served mesh-native
        without any mesh argument.

        ``tenant`` (round 15): who this operator belongs to — every
        counter class the attribution ledger accounts (flops, bytes,
        seconds, residency byte-seconds, outcomes) and the operator's
        handle heat attribute here. ``None`` (every existing caller)
        lands on the DEFAULT_TENANT; per-request overrides ride the
        ``tenant=`` kwarg of solve/Batcher.submit/Executor.submit.

        ``refine`` (round 13): a :class:`~..refine.RefinePolicy`, or
        ``True`` to resolve one from the session's policy table per
        (op, n, working dtype). The resident factor is then computed
        AND STORED at ``policy.factor_dtype`` (a bf16-from-f32
        resident charges ~half the budget — ~2× residents per HBM
        byte) and every solve refines to working-precision accuracy
        through the ``refine/`` engine; non-convergence falls back to
        a working-precision refactor, counted in
        ``refine_fallbacks_total``. Covers lu/chol operators (dense —
        single-device or mesh-sharded — and the *_small batched
        engine); GMRES-IR strategy is single-device dense only."""
        if op == "auto":
            op = self._infer_op(A)
        if mesh is not None:
            # explicit per-operator override; as_grid maps a 1×1 grid
            # to None = explicit single-device placement
            grid = as_grid(mesh)
        else:
            grid = self.grid
            if grid is None and isinstance(A, TiledMatrix):
                grid = A.grid if (A.grid is not None
                                  and A.grid.size > 1) else None
        if grid is not None:
            if op not in ("lu", "chol", "qr", "eig", "svd"):
                raise SlateError(
                    f"Session.register: mesh serving covers the dense "
                    f"operator kinds (lu/chol/qr/eig/svd), not {op!r}")
            if not isinstance(A, TiledMatrix):
                raise SlateError(
                    "Session.register: mesh serving requires a "
                    f"TiledMatrix operand, got {type(A).__name__}")
            if A.grid is not grid or A.data.shape[0] % (grid.p * A.nb) \
                    or A.data.shape[1] % (grid.q * A.nb):
                # 2D-block placement over the mesh (NamedSharding; the
                # BaseMatrix tileRank analog — core/grid.py): the
                # registered operand itself is mesh-resident, so the
                # factor program reads sharded inputs
                A = A.shard(grid)
        if op not in OPS:
            raise SlateError(f"Session.register: unknown op {op!r}")
        # operand/op agreement, checked here so a mismatch fails at
        # registration, not on the first request-path solve
        if (op in ("band_lu", "band_chol")) != isinstance(A, PackedBand):
            raise SlateError(
                f"Session.register: op {op!r} requires a "
                f"{'PackedBand' if op.startswith('band') else 'TiledMatrix'}"
                f" operand, got {type(A).__name__}")
        if (op in SMALL_OPS) != (not isinstance(A, PackedBand)
                                 and not hasattr(A, "kind")):
            raise SlateError(
                f"Session.register: op {op!r} requires a "
                f"{'plain dense [n, n] array' if op in SMALL_OPS else 'TiledMatrix'}"
                f" operand, got {type(A).__name__}")
        if isinstance(A, PackedBand):
            m = n = A.n
            band = A.kl + A.ku
        else:
            m, n = A.shape
            band = 0
        if op in SMALL_OPS:
            if m != n:
                raise SlateError(
                    "Session.register: small-problem operators must be "
                    f"square, got {(m, n)}")
            A = np.ascontiguousarray(A)
        if op == "qr" and m < n:
            # gels_using_factor covers only the overdetermined case; the
            # underdetermined minimum-norm path needs LQ factors (gels
            # handles it per call). Reject at registration instead of
            # crashing on the first solve.
            raise SlateError(
                "Session.register: wide (m < n) operators are not "
                "servable via resident QR; use least_squares_solve "
                "per call")
        if op in SPECTRAL_OPS:
            # round 19: resident spectral operators (spectral/) — the
            # staged two-stage decomposition needs a dense TiledMatrix
            # (eig additionally a Hermitian/Symmetric one); wide SVD
            # operands register the transpose (api.svd handles wide
            # per call)
            if not isinstance(A, TiledMatrix):
                raise SlateError(
                    f"Session.register: op {op!r} requires a "
                    f"TiledMatrix operand, got {type(A).__name__}")
            if op == "eig":
                if A.kind not in (MatrixKind.Hermitian,
                                  MatrixKind.Symmetric) or m != n:
                    raise SlateError(
                        "Session.register: op 'eig' requires a square "
                        "Hermitian/Symmetric TiledMatrix operand")
            elif m < n:
                raise SlateError(
                    "Session.register: wide (m < n) operators are not "
                    "servable via resident SVD; register the "
                    "transpose (api.svd handles wide per call)")
        policy = None
        if refine is not None and refine is not False:
            if op not in ("lu", "chol", "lu_small", "chol_small"):
                raise SlateError(
                    f"Session.register: refine covers lu/chol operators "
                    f"(dense or small), not {op!r}")
            wd = A.dtype
            if refine is True:
                # table resolution keys off the dense op family — a
                # small operator follows the same (op, n, dtype) rules.
                # A MATCHED rule whose policy is None is an explicit
                # full-precision carve-out (PolicyTable.add(None, ...)):
                # the operator registers unrefined. Only a class no
                # rule covers falls to the dtype ladder — and only
                # ladder exhaustion (c64) is the error.
                from ..refine.policy import default_factor_dtype
                matched, policy = self.refine_policies.lookup(
                    op.replace("_small", ""), n, wd)
                if not matched:
                    lo = default_factor_dtype(wd)
                    if lo is None:
                        raise SlateError(
                            f"Session.register: no refine policy "
                            f"resolves for (op={op!r}, n={n}, "
                            f"dtype={wd}) — no lower factor precision "
                            "exists on the dtype ladder")
                    policy = RefinePolicy(factor_dtype=lo)
            else:
                policy = refine
            if policy is not None:
                try:
                    policy.validate_for(wd)
                except ValueError as e:
                    raise SlateError(f"Session.register: {e}")
                if policy.strategy == "gmres" and (op in SMALL_OPS
                                                   or grid is not None):
                    raise SlateError(
                        "Session.register: GMRES-IR serving covers "
                        "single-device dense operators; use "
                        "strategy='ir' for mesh or small-problem "
                        "operators")
        eopts = opts or self.opts
        tuned = None
        if self.tuning is not None:
            # round 21: first-match (op, n-bucket, dtype, platform)
            # resolution — matched knobs land in THIS operator's opts
            # (nb -> block_size, inner_blocking, lookahead) before any
            # program is built, so warmup compiles the tuned program
            # and the serve path after warmup is zero new compiles;
            # unmatched operators keep their defaults (the documented
            # fallback). One `tuning is None` check when disabled.
            dt = A.ab.dtype if isinstance(A, PackedBand) else A.dtype
            cfg = self.tuning.resolve(op, n, str(np.dtype(dt)),
                                      jax.default_backend())
            if cfg is not None:
                eopts = cfg.apply(eopts)
                tuned = cfg.label()
        with self._lock:
            if handle is None:
                self._seq += 1
                while self._seq in self._ops:  # skip caller-chosen ints
                    self._seq += 1
                handle = self._seq
            if handle in self._ops:
                raise SlateError(f"Session.register: handle {handle!r} "
                                 "already registered (unregister first)")
            self._ops[handle] = _Operator(
                A, op, eopts, m, n, band, grid=grid,
                refine=policy,
                tenant=None if tenant is None else str(tenant),
                tuned=tuned)
        return handle

    def _resolve_tuned(self, entry: _Operator):
        """The table's TunedConfig for one registered operator (None
        without a table or match) — the shadow tuner's first ladder
        rung and the register-time resolution, one vocabulary."""
        if self.tuning is None:
            return None
        A = entry.A
        dt = A.ab.dtype if isinstance(A, PackedBand) else A.dtype
        return self.tuning.resolve(entry.op, entry.n, str(np.dtype(dt)),
                                   jax.default_backend())

    def tuned_width_quantum(self, handle: Hashable) -> int:
        """The Batcher's rhs-width pad quantum for ``handle`` (round
        21): the table's ``width_quantum`` when one matches, else 1 —
        plain pow2 padding, bit-identical to the untuned tree."""
        if self.tuning is None:
            return 1
        with self._lock:
            entry = self._ops.get(handle)
        if entry is None:
            return 1
        A = entry.A
        dt = A.ab.dtype if isinstance(A, PackedBand) else A.dtype
        return self.tuning.width_quantum(entry.op, entry.n,
                                         str(np.dtype(dt)),
                                         jax.default_backend())

    @staticmethod
    def _infer_op(A) -> str:
        if isinstance(A, PackedBand):
            return "band_chol" if A.hermitian else "band_lu"
        if not hasattr(A, "kind"):
            # plain dense [n, n] array: the small-problem engine (a
            # symmetry-blind default — register op="chol_small"
            # explicitly for Hermitian-positive-definite operators)
            return "lu_small"
        if A.kind in (MatrixKind.Hermitian, MatrixKind.Symmetric,
                      MatrixKind.HermitianBand):
            return "chol"
        if A.shape[0] != A.shape[1]:
            return "qr"
        return "lu"

    def unregister(self, handle: Hashable):
        """Drop an operator and its cached factor (no error if absent)."""
        with self._lock:
            entry = self._ops.pop(handle, None)
            res = self._cache.pop(handle, None)
            if res is not None:
                self.metrics.inc("evictions")
                self.metrics.inc("evicted_bytes", res.nbytes)
                if self.attribution is not None:
                    self._attr_evicted(handle)
                rec = self.recorder
                if rec is not None:
                    self._journal_evict(rec, handle, res.nbytes,
                                        "unregister", entry=entry)
            if self.attribution is not None:
                # the handle can never be accessed again: drop its
                # heat/residency clocks (and gauge) so handle churn
                # cannot leak ledger state — the cells stay (billing
                # history)
                self.attribution.forget_handle(handle)
            if self.numerics is not None:
                # same churn-cardinality discipline for the health row
                # and its handle_health gauge
                self.numerics.forget(handle)
            self._update_hbm_gauges()

    def __contains__(self, handle: Hashable) -> bool:
        with self._lock:
            return handle in self._ops

    def handles(self):
        with self._lock:
            return list(self._ops)

    # -- cache -------------------------------------------------------------

    @property
    def cached_bytes(self) -> int:
        with self._lock:
            return sum(r.nbytes for r in self._cache.values())

    def cached_handles(self):
        """LRU → MRU order."""
        with self._lock:
            return list(self._cache)

    def evict(self, handle: Hashable) -> bool:
        """Explicitly drop a cached factor (operator stays registered)."""
        with self._lock:
            res = self._cache.pop(handle, None)
            if res is not None:
                self.metrics.inc("evictions")
                self.metrics.inc("evicted_bytes", res.nbytes)
                if self.attribution is not None:
                    self._attr_evicted(handle)
                rec = self.recorder
                if rec is not None:
                    self._journal_evict(rec, handle, res.nbytes,
                                        "explicit")
            self._update_hbm_gauges()
        return res is not None

    def clear_cache(self):
        with self._lock:
            n = len(self._cache)
            nbytes = sum(r.nbytes for r in self._cache.values())
            if self.attribution is not None:
                for h in self._cache:
                    self._attr_evicted(h)
            self._cache.clear()
            self._update_hbm_gauges()
        self.metrics.inc("evictions", n)
        self.metrics.inc("evicted_bytes", nbytes)
        rec = self.recorder
        if rec is not None and n:
            # one sweep, one decision: count carries the victim total
            # so journal-count parity vs the ``evictions`` counter holds
            rec.decision("eviction", outcome="clear_cache", count=n,
                         inputs={"nbytes": nbytes})

    def factor(self, handle: Hashable) -> _Resident:
        """Resident factor for ``handle``: cache hit or refactor-on-miss
        (LRU-touch either way, evict-to-budget on insert)."""
        with self._lock:
            entry = self._ops.get(handle)
            if entry is None:
                raise SlateError(f"Session: unknown handle {handle!r}")
            attr = self.attribution
            res = self._cache.get(handle)
            if res is not None:
                self._cache.move_to_end(handle)
                self.metrics.inc("cache_hits")
                if attr is not None:
                    # hit: count + heat advance, and re-touch the
                    # residency clock (accrued byte-seconds credit the
                    # cell and the global counter with the same value)
                    attr.access(entry.tenant, handle, True)
                    inc = attr.touch_residency(entry.tenant, handle,
                                               res.nbytes)
                    if inc:
                        self.metrics.inc("residency_byte_seconds_total",
                                         inc)
                if self.slo is not None:
                    self.slo.record_cache(True)
                return res
            self.metrics.inc("cache_misses")
            if attr is not None:
                attr.access(entry.tenant, handle, False)
            if self.slo is not None:
                self.slo.record_cache(False)
            # attrs built only when tracing is on: the disabled path
            # must not allocate per solve (ISSUE 4 acceptance)
            fattrs = (self._span_attrs(entry, handle)
                      if self.tracer.enabled else {})
            with self.metrics.phase("serve.factor", "factor_latency",
                                    tracer=self.tracer, **fattrs):
                res = self._factor(entry, handle)
                if (self.faults is not None and entry.refine is not None
                        and res.info == 0
                        and self._fault("refine.lo_factor")):
                    # injected singular low-precision operand: the lo
                    # factor "fails", driving the SAME counted
                    # working-precision fallback a real indefinite-
                    # under-rounding operand takes
                    res = _Resident(res.payload, 1, res.nbytes,
                                    res.nbytes_total)
                if res.info != 0 and entry.refine is not None:
                    # the LOW-precision factorization itself failed
                    # (e.g. SPD in f32, indefinite after bf16
                    # rounding): a counted refinement fallback — the
                    # working-precision refactor is the answer path,
                    # never the garbage factor
                    self.metrics.inc("refine_fallbacks_total")
                    _obs_log.warning(
                        "refine fallback: low-precision (%s) factor of "
                        "%r failed (info=%d); refactoring at working "
                        "precision", entry.refine.factor_dtype, handle,
                        res.info)
                    rec = self.recorder
                    if rec is not None:
                        rec.decision(
                            "refine_fallback", op=entry.op,
                            handle=handle, tenant=entry.tenant,
                            outcome="lo_factor_failed",
                            inputs={
                                "info": int(res.info),
                                "factor_dtype":
                                    str(entry.refine.factor_dtype)})
                    if not entry.refine.fallback:
                        raise SlateError(
                            f"Session: low-precision factor of "
                            f"{handle!r} failed (info={res.info}) and "
                            "the refine policy disables fallback")
                    entry.refine = None
                    res = self._factor(entry, handle)
            self.metrics.inc("factors_total")
            fl = _factor_flops(entry.op, entry.m, entry.n, entry.band)
            self.metrics.inc("flops_total", fl)
            self.metrics.inc("factor_flops_total", fl)
            # executed work credits the PROCESS ledger here (the api.*
            # verbs inside the compiled factor program only run at
            # trace time and deliberately credit nothing — obs.driver).
            # Band factors are the exception: _factor runs them through
            # the EAGER api verbs, whose driver hook already credited
            # the ledger — crediting serve.factor too would double-count
            if entry.op not in ("band_lu", "band_chol"):
                _LEDGER.record("serve.factor", fl)
            if attr is not None:
                # the factor work belongs to the operator's tenant;
                # same grid-snapped value as the counters above
                attr.record("factor_flops", entry.tenant, handle, fl)
            self._cache[handle] = res
            # a fresh factor zeroes the incremental-update error
            # accrual (round 20; the numerics monitor resets its own
            # copy in record_factor — this is the monitor-less one)
            entry.updates = 0
            entry.update_weight = 0.0
            if attr is not None:
                # open the residency interval: byte-seconds accrue
                # from this insert until eviction/unregister. A
                # factor-on-miss implies no interval is open (inc=0),
                # but crediting the return keeps the seam conserving
                # by construction like every other residency seam
                inc = attr.touch_residency(entry.tenant, handle,
                                           res.nbytes)
                if inc:
                    self.metrics.inc("residency_byte_seconds_total",
                                     inc)
            self._evict_to_budget(keep=handle)
            if self.tenant_policies is not None:
                # round 18: the tenant's own sub-budget, after the
                # global pass (per-tenant LRU, isolation pinned)
                self._evict_tenant_to_budget(entry.tenant, keep=handle)
            if self.numerics is not None and res.info == 0:
                res = self._numerics_after_factor(entry, handle, res)
            return res

    def _numerics_after_factor(self, entry: _Operator, handle: Hashable,
                               res: _Resident) -> _Resident:
        """Caller holds the lock and verified ``self.numerics``.
        Factor-time health signals on a fresh resident: the realized
        growth bound (host read of the factor; skipped for mesh
        residents — their factor-time signal is the condest, which
        runs sharded) with its NaN/Inf sentinel, then the condest
        probe (config-gated). Returns the SERVING resident: a reflex
        demotion mid-signal evicts the lo factor, so this refactors at
        working precision before returning (bounded recursion — the
        demoted entry has ``refine=None`` and cannot demote again)."""
        nm = self.numerics
        cfg = nm.config
        growth = None
        finite = True
        if (cfg.growth_on_factor and entry.grid is None
                and entry.op in CONDEST_OPS):
            growth = (_num.chol_growth if "chol" in entry.op
                      else _num.lu_growth)(res.payload[0], entry.A)
            if not np.isfinite(growth):
                finite = False
                self.metrics.inc("numerics_nonfinite_total")
        old, new = nm.record_factor(
            handle, entry.op, _work_dtype_name(entry),
            factor_dtype=(None if entry.refine is None
                          else entry.refine.factor_dtype),
            tenant=entry.tenant, growth=growth, finite=finite)
        self._health_reflex(entry, handle, old, new)
        if (cfg.condest_on_factor and entry.op in CONDEST_OPS
                and handle in self._cache):
            self._condest_locked(entry, handle, res)
        if handle not in self._cache:
            # a reflex demoted this handle off the refine ladder and
            # evicted its lo resident: serve from a working-precision
            # refactor, never from the factor the reflex just rejected
            return self.factor(handle)
        return res

    def factor_info(self, handle: Hashable) -> int:
        """info of the resident factor (factoring on miss). A cached
        factor is peeked without counting a hit or touching LRU order,
        so an info-check-then-solve pair costs one cache access."""
        with self._lock:
            res = self._cache.get(handle)
            if res is not None:
                return res.info
            return self.factor(handle).info

    def _factor(self, entry: _Operator, handle: Hashable = None
                ) -> _Resident:
        op, A, opts = entry.op, entry.A, entry.opts
        if op in SPECTRAL_OPS:
            payload = self._factor_spectral(entry, handle)
            payload = jax.block_until_ready(payload)
            # the two-stage pipeline finishes through stedc's D&C,
            # which is direct (no convergence failure mode to report):
            # a spectral resident is always info=0
            return _Resident(payload, 0,
                             _tree_nbytes(payload, per_chip=True),
                             _tree_nbytes(payload))
        if op in SMALL_OPS:
            # the per-request arm of the many-small-problems engine:
            # ONE item through the SAME hand-batched kernels the
            # grouped dispatch uses at B=bucket (linalg/batched's
            # per-bucket program cache compiles/reuses the B=1
            # program) — so a cached factor is bit-identical to the
            # slice a batched factor would have produced
            from ..linalg import batched as _batched
            if entry.refine is not None:
                # the mixed arm: cast+factor in the policy's dtype
                # through the SAME bucket programs the grouped mixed
                # dispatch runs at B=bucket — a cached lo factor is
                # bit-identical to the slice a batched mixed factor
                # would have produced (and charges factor-dtype bytes)
                lo = entry.refine.factor_dtype
                if op == "lu_small":
                    lu, perm, info = _batched.getrf_mixed_batched(
                        A[None], lo)
                    payload = (lu[0], perm[0])
                else:
                    l, info = _batched.potrf_mixed_batched(A[None], lo)
                    payload = (l[0],)
            elif op == "lu_small":
                lu, perm, info = _batched.getrf_batched(A[None])
                payload = (lu[0], perm[0])
            else:
                l, info = _batched.potrf_batched(A[None])
                payload = (l[0],)
            payload = jax.block_until_ready(payload)
            return _Resident(payload, int(info[0]),
                             _tree_nbytes(payload))
        if op in ("band_lu", "band_chol"):
            # band factors stay on the eager verbs (PackedBand pipelines
            # host-side packing the whole-program jit cannot absorb)
            if op == "band_lu":
                LU, perm, info = api.lu_factor(A, opts)
                payload = (LU, perm)
            else:
                L, info = api.chol_factor(A, opts)
                payload = (L,)
        else:
            # dense factors run as ONE compiled program (round 7):
            # warmup() AOT-compiles it per operand shape, so a served
            # operator's first refactor-on-miss skips tracing AND
            # compilation — and the program is the LOOKAHEAD pipeline
            # (entry.opts.lookahead flows into the jitted driver), so
            # served factors compile the lookahead variant ahead of the
            # first request (ISSUE 3 satellite).
            key = self._factor_key(entry)
            exe = self._compiled.get(key)
            if exe is None and (entry.grid is not None
                                or entry.refine is not None):
                # mesh discipline: the factor ALWAYS runs as one
                # analyzed sharded AOT program per shape — the census
                # and per-chip transient accounting need the compiled
                # seam, and warmup() may not have covered this shape
                # (this is the on-request-path compile, counted).
                # Round 13 extends the discipline to REFINED entries:
                # the low-precision factor program is analyzed so its
                # bytes/census credit per execution (ISSUE 10 —
                # "through the AOT seam as analyzed programs")
                exe = self._aot_compile("factor", entry, handle,
                                        self._factor_fn(entry), (A,),
                                        key=key)
                self._compiled_put(key, exe)
                self.metrics.inc("factor_aot_compiles")
            if exe is not None:
                self._compiled.move_to_end(key)
                payload, info = exe(A)
                self._credit_program(key, "serve.factor",
                                     tenant=entry.tenant, handle=handle)
            else:
                payload, info = self._factor_fn(entry)(A)
        payload = jax.block_until_ready(payload)
        return _Resident(payload, int(info),
                         _tree_nbytes(payload, per_chip=True),
                         _tree_nbytes(payload))

    def _factor_spectral(self, entry: _Operator, handle: Hashable):
        """Caller holds the lock. The round-19 spectral factorization:
        run the staged two-stage pipeline (spectral/mesh.py) with every
        DEVICE stage routed through the ``_aot_compile`` seam — each
        stage is a cost-analyzed program whose bytes/collective census
        credit per execution (the mesh-factor discipline of round 11,
        applied per stage because the host stedc round-trip splits the
        pipeline). Returns the resident pytree payload
        (EigFactors/SVDFactors) with the spectrum replicated over the
        operator's grid."""
        from .. import spectral as _spectral

        def stage(name, jfn, args):
            leaves, treedef = jax.tree_util.tree_flatten(args)
            shapes = tuple((tuple(l.shape), str(l.dtype))
                           for l in leaves)
            key = ("spectral", name, entry.op, entry.opts, treedef,
                   shapes)
            exe = self._compiled.get(key)
            if exe is None:
                exe = self._aot_compile(name, entry, handle, jfn, args,
                                        key=key)
                self._compiled_put(key, exe)
                self.metrics.inc("factor_aot_compiles")
            else:
                self._compiled.move_to_end(key)
            self._credit_program(key, "serve.factor",
                                 tenant=entry.tenant, handle=handle)
            return exe(*args)

        if entry.op == "eig":
            lam, V = _spectral.heev_staged(entry.A, entry.opts,
                                           stage=stage)
            if entry.grid is not None:
                lam = jax.device_put(lam, entry.grid.replicated())
            return _spectral.EigFactors(V, lam)
        s, U, V = _spectral.svd_staged(entry.A, entry.opts, stage=stage)
        if entry.grid is not None:
            s = jax.device_put(s, entry.grid.replicated())
        return _spectral.SVDFactors(U, s, V)

    def _credit_program(self, key: Hashable, op: str,
                        waste_fraction: float = 0.0,
                        tenant: Optional[str] = None,
                        handle: Optional[Hashable] = None):
        """One execution of an analyzed AOT program: credit the process
        BYTES ledger (bytes-accessed + modeled collective traffic) and
        the session counters — the per-execution discipline the flop
        ledger already follows (compile-time tracing credits nothing).

        ``waste_fraction`` (round 12) is the padded share of the
        program's columns (the Batcher's pow2 width quantization): that
        share of the bytes/ICI traffic moves to the ``padding.waste``
        ledger op and the ``padding_waste_bytes`` counter instead of
        ``op`` — executed totals preserved, useful-work attribution
        honest. The per-kind collective census stays whole under the
        useful record (instruction counts are structural, not
        column-divisible)."""
        pc = self._program_costs.get(key)
        if pc is None:
            return
        if waste_fraction > 0.0:
            wf = min(max(waste_fraction, 0.0), 1.0)
            ba = pc.bytes_accessed or 0.0
            _costs.BYTES.record(op, ba * (1.0 - wf),
                                pc.collective_bytes * (1.0 - wf),
                                pc.collectives)
            _costs.BYTES.record("padding.waste", ba * wf,
                                pc.collective_bytes * wf)
            if ba:
                self.metrics.inc("padding_waste_bytes", ba * wf)
        else:
            _costs.BYTES.record_costs(op, pc)
        # the session counters (and round-15 attribution cells) take
        # the GRID-SNAPPED program bytes — XLA byte counts are whole
        # numbers anyway, and the snap is what makes the per-tenant
        # conservation sums exact (obs/attribution.py); the process
        # BYTES ledger above keeps the raw analysis values
        attr = self.attribution
        if pc.bytes_accessed:
            ba = _fl_grid(pc.bytes_accessed)
            self.metrics.inc("bytes_accessed_total", ba)
            if attr is not None and handle is not None:
                attr.record("bytes", tenant, handle, ba)
        if pc.collective_bytes:
            cb = _fl_grid(pc.collective_bytes)
            self.metrics.inc("collective_bytes_total", cb)
            if attr is not None and handle is not None:
                attr.record("ici_bytes", tenant, handle, cb)
            # per-verb ICI split (round 11): a capacity planner needs
            # the steady-state (solve) traffic separate from the
            # amortized factor traffic — both move per EXECUTION
            self.metrics.inc(
                ("solve_collective_bytes_total" if op == "serve.solve"
                 else "factor_collective_bytes_total"),
                cb)

    def _jit_cached(self, jkey: Hashable, make):
        """LRU-jit-cache shared by the solve and factor programs. A
        miss means the next call pays tracing (+compilation unless an
        AOT executable covers the shape) on the request path — counted
        so a serving fleet can alarm on jit-cache churn."""
        fn = self._jit.get(jkey)
        if fn is None:
            self.metrics.inc("jit_cache_misses")
            fn = self._jit[jkey] = jax.jit(make())
            while len(self._jit) > self._jit_cap:
                self._jit.popitem(last=False)
        else:
            self._jit.move_to_end(jkey)
        return fn

    def _compiled_put(self, key: Hashable, exe):
        """Insert an AOT executable under the shared cap (its cost
        analysis is dropped in step, so the transient-footprint term of
        the budget only counts programs that can still run)."""
        self._compiled[key] = exe
        while len(self._compiled) > self._compiled_cap:
            old, _ = self._compiled.popitem(last=False)
            self._program_costs.pop(old, None)

    def _factor_fn(self, entry: _Operator):
        if entry.refine is not None:
            # the refine engine's cast+factor program (the policy is
            # part of the key: two operators refined under different
            # factor dtypes never share a closure)
            return self._jit_cached(
                ("factor", entry.op, entry.opts, entry.refine),
                lambda: _refine_engine.make_factor_fn(
                    entry.op, entry.opts, entry.refine))
        return self._jit_cached(
            ("factor", entry.op, entry.opts),
            lambda: _make_factor_fn(entry.op, entry.opts))

    @staticmethod
    def _factor_key(entry: _Operator) -> Hashable:
        leaves, treedef = jax.tree_util.tree_flatten(entry.A)
        shapes = tuple((tuple(l.shape), str(l.dtype)) for l in leaves)
        return ("factor", entry.op, entry.opts, entry.refine, treedef,
                shapes)

    def _largest_transient(self) -> int:
        """Caller holds the lock. Transient HBM (temp scratch + output
        allocation) of the largest resident AOT program — the
        peak-memory truth XLA's memory_analysis reports at the compile
        seam. 0 when no program has been analyzed (XLA:CPU reports 0
        temp bytes: graceful degradation to the round-6 accounting)."""
        return max((pc.transient_bytes
                    for pc in self._program_costs.values()), default=0)

    def _update_hbm_gauges(self):
        """Caller holds the lock. Publish the HBM truth as gauges:
        resident factor bytes (the PER-CHIP charge — max-per-shard for
        mesh residents, the whole factor on a single device), the
        worst-case per-chip peak (factors + largest program transient —
        XLA's memory analysis describes the per-device SPMD module),
        the aggregate bytes across the mesh, and the per-chip headroom
        against the budget."""
        resident = sum(r.nbytes for r in self._cache.values())
        peak = resident + self._largest_transient()
        self.metrics.set_gauge("resident_bytes", resident)
        self.metrics.set_gauge(
            "resident_bytes_total",
            sum(r.nbytes_total for r in self._cache.values()))
        self.metrics.set_gauge("peak_hbm_bytes", peak)
        if self.hbm_budget is not None:
            self.metrics.set_gauge("hbm_headroom", self.hbm_budget - peak)

    def hbm_headroom(self) -> Optional[int]:
        """PER-CHIP budget minus (per-chip resident factor charge +
        largest program's per-device transient); None when the session
        is unbounded."""
        with self._lock:
            if self.hbm_budget is None:
                return None
            return self.hbm_budget - (
                sum(r.nbytes for r in self._cache.values())
                + self._largest_transient())

    def _eviction_order(self):
        """Caller holds the lock. The LRU walk order, except SUSPECT
        handles lose eviction tie-breaks (round 16): a resident the
        numerics monitor distrusts is the cheapest thing to give back
        — its next touch refactors anyway if the operand really
        degraded, and keeping it pins HBM a healthy handle could use.
        LRU order is preserved within each health class; with numerics
        disabled this is exactly ``list(self._cache)`` (one None
        check)."""
        keys = list(self._cache)
        nm = self.numerics
        if nm is None:
            return keys
        sus = [h for h in keys if nm.health(h) == "suspect"]
        if not sus:
            return keys
        smark = set(sus)
        return sus + [h for h in keys if h not in smark]

    def _evict_to_budget(self, keep: Hashable):
        """Caller holds the lock. Drop LRU entries (never ``keep``)
        until resident factors PLUS the largest resident program's
        transient footprint fit the budget (round 9: the budget used to
        be an honor-system sum of factor nbytes that ignored what the
        programs themselves allocate while running)."""
        budget = self.hbm_budget
        if self.faults is not None and self._fault("hbm"):
            # injected HBM exhaustion: for THIS insert the budget
            # collapses to zero — eviction-under-pressure runs for
            # real (everything but `keep` drops; `keep` then counts a
            # budget overflow exactly like a genuinely over-budget
            # factor). An unbounded session degrades the same way.
            budget = 0
        if budget is None:
            self._update_hbm_gauges()
            return
        transient = self._largest_transient()
        used = sum(r.nbytes for r in self._cache.values()) + transient
        for h in self._eviction_order():
            if used <= budget:
                break
            if h == keep:
                continue
            nbytes = self._cache.pop(h).nbytes
            used -= nbytes
            self.metrics.inc("evictions")
            self.metrics.inc("evicted_bytes", nbytes)
            if self.attribution is not None:
                self._attr_evicted(h)
            rec = self.recorder
            if rec is not None:
                self._journal_evict(rec, h, nbytes, "budget",
                                    used=used, budget=budget)
        if used > budget:
            # the kept factor (+ program transient) alone exceeds the
            # budget; serving must continue, but this is OOM risk —
            # record the overflow and warn on the slow-log path
            self.metrics.inc("budget_overflows")
            self.metrics.inc("oom_risk_warnings")
            _obs_log.warning(
                "OOM risk: resident factors + largest program transient "
                "= %d bytes exceed hbm_budget=%d (transient=%d); serving "
                "continues with negative headroom", used, budget,
                transient)
        if self.slo is not None:
            # one budget check = one oom_risk SLO event (good = fits;
            # an injected exhaustion records the bad event it simulates)
            self.slo.record_oom(used <= budget)
        self._update_hbm_gauges()

    # -- per-tenant HBM sub-budgets (round 18, runtime/tenancy.py) ---------

    @staticmethod
    def _tname(tenant) -> str:
        return DEFAULT_TENANT if tenant is None else str(tenant)

    def tenant_resident_bytes(self, tenant=None) -> int:
        """Per-chip resident factor bytes charged to one tenant (the
        sub-budget's numerator). Lock-free (GIL-atomic dict walks over
        immutable fields — the op_meta discipline): scrapes and the
        fleet's migration-source scan must not wait on an in-flight
        solve."""
        t = self._tname(tenant)
        total = 0
        for h, res in list(self._cache.items()):
            e = self._ops.get(h)
            if e is not None and self._tname(e.tenant) == t:
                total += res.nbytes
        return total

    def _evict_tenant_to_budget(self, tenant, keep: Hashable):
        """Caller holds the lock and verified ``self.tenant_policies``.
        The per-tenant HBM sub-budget, enforced at the factor-insert
        seam: when THIS tenant's resident bytes exceed its declared
        ``max_resident_bytes``, evict ITS residents in LRU order
        (never ``keep``, never another tenant's — the isolation pin:
        tenant A's pressure cannot evict tenant B's resident; the
        GLOBAL budget in _evict_to_budget remains the only
        cross-tenant eviction authority). A kept factor alone over the
        sub-budget counts ``tenant_quota_overflows`` — serving
        continues, the tenant is over its declared share, and the
        gauge pair says so."""
        t = self._tname(tenant)
        pol = self.tenant_policies.policy(t)
        sub = None if pol is None else pol.max_resident_bytes
        used = 0
        for h, res in self._cache.items():
            e = self._ops.get(h)
            if e is not None and self._tname(e.tenant) == t:
                used += res.nbytes
        if sub is not None:
            # the SAME walk order the global budget uses
            # (_eviction_order: round-16 suspect residents lose
            # tie-breaks, then LRU), filtered to this tenant — one
            # eviction policy, two budget scopes
            mine = [h for h in self._eviction_order()
                    if (e := self._ops.get(h)) is not None
                    and self._tname(e.tenant) == t]
            for h in mine:
                if used <= sub:
                    break
                if h == keep:
                    continue
                nbytes = self._cache.pop(h).nbytes
                used -= nbytes
                self.metrics.inc("evictions")
                self.metrics.inc("evicted_bytes", nbytes)
                self.metrics.inc("tenant_quota_evictions_total")
                if self.attribution is not None:
                    self._attr_evicted(h)
                # ONE decision, TWO counters (evictions + the tenant
                # quota secondary): outcome "tenant_quota" carries the
                # OUTCOME_COUNTERS parity for the second one
                rec = self.recorder
                if rec is not None:
                    self._journal_evict(rec, h, nbytes, "tenant_quota",
                                        used=used, sub_budget=sub)
            if used > sub:
                self.metrics.inc("tenant_quota_overflows")
                _obs_log.warning(
                    "tenant quota: %r resident bytes %d exceed the "
                    "declared sub-budget %d (the kept factor alone is "
                    "over it); serving continues over-share", t, used,
                    sub)
            self._update_hbm_gauges()
        self.metrics.set_gauge(f"tenant_quota_resident_bytes:{t}", used)
        if sub is not None:
            self.metrics.set_gauge(f"tenant_quota_hbm_headroom:{t}",
                                   sub - used)

    def quotas_payload(self) -> dict:
        """The quota view of the ``/tenants`` route (round 18): the
        declared policy table, each tenant's live resident bytes
        against its sub-budget, and the quota counters.
        ``{"enabled": false}`` without a table."""
        if self.tenant_policies is None:
            return {"enabled": False, "tenants": {}}
        per: Dict[str, dict] = {}
        for h, res in list(self._cache.items()):
            e = self._ops.get(h)
            if e is None:
                continue
            t = self._tname(e.tenant)
            row = per.setdefault(t, {"resident_bytes": 0,
                                     "residents": 0})
            row["resident_bytes"] += res.nbytes
            row["residents"] += 1
        for t in list(per):
            pol = self.tenant_policies.policy(t)
            per[t]["max_resident_bytes"] = (
                None if pol is None else pol.max_resident_bytes)
            per[t]["weight"] = self.tenant_policies.weight(t)
        return {
            "enabled": True,
            "policies": self.tenant_policies.to_dict(),
            "tenants": per,
            "counters": {k: self.metrics.get(k) for k in (
                "quota_rejections_total",
                "tenant_quota_evictions_total",
                "tenant_quota_overflows", "tenant_sheds_total")},
        }

    # -- solve -------------------------------------------------------------

    def _span_attrs(self, entry: _Operator, handle: Hashable) -> dict:
        """Span attributes for one operator: op, shape, dtype, nb,
        lookahead, handle — the vocabulary the ISSUE fixes."""
        A = entry.A
        dtype = A.ab.dtype if isinstance(A, PackedBand) else A.dtype
        attrs = {
            "op": entry.op, "m": entry.m, "n": entry.n,
            "nb": getattr(A, "nb", entry.band),
            "dtype": str(dtype),
            "lookahead": getattr(entry.opts, "lookahead", 0),
            "handle": repr(handle),
        }
        if entry.grid is not None:
            attrs["mesh"] = f"{entry.grid.p}x{entry.grid.q}"
        if entry.refine is not None:
            attrs["factor_dtype"] = entry.refine.factor_dtype
            attrs["refine_strategy"] = entry.refine.strategy
        if entry.tuned is not None:
            # round 21: which tuning-table row (or shadow promotion)
            # configured this operator — attribution joins it per
            # tenant, making tables workload-aware
            attrs["tuned_config"] = entry.tuned
        return attrs

    def solve_matrix(self, handle: Hashable, B: TiledMatrix,
                     served_cols: Optional[int] = None,
                     tenant: Optional[str] = None,
                     spectral_fn: str = "solve",
                     theta: float = 0.0) -> TiledMatrix:
        """Solve with the resident factor; B is a TiledMatrix (dense
        ops) or a padded dense array (band ops). Returns the TiledMatrix
        (or array) solution. Raises on factorization failure (info>0).

        ``served_cols``: how many of B's columns are real client
        requests (default: all). The Batcher's pow2 width padding
        passes the pre-padding count so ``solves_total`` keeps meaning
        "client columns served" — the denominator of every per-solve
        rate — while the flop/bytes ledgers keep crediting the
        EXECUTED width (padding waste is real device work a fleet
        should see)."""
        with self._lock:
            entry = self._ops[handle] if handle in self._ops else None
            if entry is None:
                raise SlateError(f"Session: unknown handle {handle!r}")
            if entry.op in SMALL_OPS:
                raise SlateError(
                    "Session.solve_matrix: small-problem operators take "
                    "plain arrays — use Session.solve")
            # the request's tenant (round 15): explicit override ->
            # operator tenant -> default; resolved only when someone
            # consumes it (the attr/slo disabled path allocates nothing)
            attr = self.attribution
            rt = (self.request_tenant(handle, tenant)
                  if (attr is not None or self.slo is not None) else None)
            hit = handle in self._cache  # before factor() counts it
            res = self.factor(handle)
            if res.info != 0:
                if self.slo is not None:
                    self.slo.record_request(entry.op, entry.n, 0.0,
                                            ok=False, source="solve",
                                            tenant=rt)
                raise SlateError(
                    f"Session: operator {handle!r} factorization failed "
                    f"(info={res.info})")
            # sampled residual probe (round 16): the deterministic
            # sampler decides BEFORE dispatch whether this solve runs
            # the fused solve+residual program instead of the plain
            # one — one extra gemm in-program, one host sync, zero
            # extra programs for unprobed solves. Refined entries skip
            # it (their per-iteration residuals already feed the
            # refine-drift signal). AFTER the info raise on purpose: a
            # failed solve never consumes a decision, on any path —
            # the probe schedule stays a pure function of the
            # SUCCESSFUL request stream (grouped-parity pin).
            nm = self.numerics
            probe = (nm is not None and entry.refine is None
                     and entry.op in PROBE_OPS + SPECTRAL_OPS
                     and nm.sampler.decide())
            k = int(B.shape[1])
            served = k if served_cols is None else int(served_cols)
            tr = self.tracer
            sattrs = (dict(self._span_attrs(entry, handle), k=k,
                           cache_hit=hit) if tr.enabled else {})
            if self.faults is not None:  # the whole disabled-path cost
                self._fault("dispatch")
            with self.metrics.phase("serve.solve", "solve_latency",
                                    tracer=tr, **sattrs) as ph:
                # dispatch (trace/launch) and device-block are split
                # sub-spans so a trace shows where the latency sits —
                # and stage histograms (round 12), so the split is
                # visible in /metrics even with tracing off
                t0 = time.perf_counter()
                pstats = None
                with tr.span("serve.dispatch"):
                    if entry.op in SPECTRAL_OPS:
                        X = self._dispatch_spectral(
                            entry, res, B, handle, spectral_fn, theta,
                            served_cols=served_cols, tenant=rt)
                        if probe:
                            # the spectral residual probe is a SEPARATE
                            # one-gemm program (‖A·v_i − λ_i·v_i‖ on
                            # sampled columns — it reads the resident,
                            # not the request), run alongside the apply
                            pstats = self._spectral_probe(entry, res,
                                                          B, handle)
                    elif probe:
                        X, pstats = self._dispatch_probed(
                            entry, res, B, handle,
                            served_cols=served_cols, tenant=rt)
                    else:
                        X = self._dispatch(entry, res, B, handle,
                                           served_cols=served_cols,
                                           tenant=rt)
                t1 = time.perf_counter()
                with tr.span("serve.block"):
                    X = jax.block_until_ready(X)
                    if pstats is not None:
                        # same program, already executed with X — the
                        # fetch rides the one existing host sync
                        pstats = np.asarray(
                            jax.block_until_ready(pstats))
                t2 = time.perf_counter()
            ex = getattr(ph.span, "trace_id", None)  # exemplar join key
            self.metrics.observe("stage_dispatch", t1 - t0, exemplar=ex)
            self.metrics.observe("stage_device_execute", t2 - t1,
                                 exemplar=ex)
            if attr is not None:
                # device-execute seconds on the dyadic grid — the same
                # snapped value lands in the cell and the global
                ds = _s_grid(t2 - t1)
                self.metrics.inc("device_seconds_total", ds)
                attr.record("device_seconds", rt, handle, ds)
            self.metrics.inc("solves_total", served)
            self.metrics.inc("dispatches_total")
            # padding-waste split (round 12): the Batcher's pow2 width
            # quantization executes k - served REAL zero columns —
            # device work the fleet must see, but not useful work. The
            # solve models are k-linear, so the split is exact:
            # useful + waste = the executed total the old code credited.
            fl = _solve_flops(entry.op, entry.m, entry.n, served,
                              entry.band)
            waste_fl = (_solve_flops(entry.op, entry.m, entry.n,
                                     k - served, entry.band)
                        if k > served else 0.0)
            self.metrics.inc("flops_total", fl + waste_fl)  # executed
            self.metrics.inc("solve_flops_total", fl)       # useful
            # executed work credits the PROCESS ledger here (the api.*
            # verbs inside the compiled solve program only run at trace
            # time and deliberately credit nothing — obs.driver)
            _LEDGER.record("serve.solve", fl)
            if attr is not None:
                attr.record("solve_flops", rt, handle, fl)
            if waste_fl:
                self.metrics.inc("padding_waste_flops", waste_fl)
                self.metrics.set_gauge("width_bucket_efficiency",
                                       served / k)
                _LEDGER.record("padding.waste", waste_fl)
            if self.slo is not None:
                self.slo.record_request(entry.op, entry.n, ph.elapsed,
                                        ok=True, source="solve",
                                        tenant=rt)
            if pstats is not None:
                rnorm, xnorm, bnorm = (float(v) for v in pstats)
                if entry.anorm is None:
                    from ..core.types import Norm
                    from ..linalg.norms import norm as _norm
                    entry.anorm = float(_norm(entry.A, Norm.Inf))
                self._record_rho(
                    entry, handle,
                    _num.scaled_residual(rnorm, xnorm, bnorm,
                                         entry.anorm), served)
            return X

    def _record_rho(self, entry: _Operator, handle: Hashable,
                    rho: float, k: int):
        """Caller holds the lock and verified ``self.numerics``. One
        sampled probe's scaled residual ρ = ‖b−Ax‖/(‖A‖·‖x‖+‖b‖):
        histogram + counter + the probe gemm's model flops (a
        dedicated ``numerics.probe`` ledger op and counter — probe
        work is system work, so the tenant-conserving solve counters
        never move), the ``residual``-kind SLO event, the monitor
        record, and the health reflex on its transition."""
        self.metrics.inc("residual_probes_total")
        if np.isfinite(rho):
            self.metrics.observe("sampled_residual", rho)
        else:
            # count, don't observe: one NaN in the histogram poisons
            # sum/p99 forever and blinds the watchdog series (NaN
            # compares false against any baseline) — the monitor's
            # suspect sentinel is the alarm for this case
            self.metrics.inc("numerics_nonfinite_total")
        fl = _fl_grid(_flops_mod.gemm(entry.n, max(int(k), 1), entry.n))
        self.metrics.inc("numerics_flops_total", fl)
        self.metrics.inc("flops_total", fl)
        _LEDGER.record("numerics.probe", fl)
        if self.slo is not None:
            self.slo.record_residual(rho)
        old, new = self.numerics.record_residual(
            handle, rho, work_dtype=_work_dtype_name(entry))
        self._health_reflex(entry, handle, old, new)

    def _record_small_probe(self, entry: _Operator, handle: Hashable,
                            x: np.ndarray, b2: np.ndarray):
        """Caller holds the lock and verified ``self.numerics``. The
        small-op arm of the sampled probe: the operand is already
        host-resident (the engine's [n, n] array) and n is small by
        definition, so the residual is one host gemm — zero extra
        device programs, bit-identical between the per-request and
        grouped paths (both read the same solution bits, the
        linalg/batched contract — the health-parity pin)."""
        a = np.asarray(entry.A)
        work = np.complex128 if np.iscomplexobj(a) else np.float64
        aw = a.astype(work)
        xw = np.asarray(x).astype(work)
        bw = np.asarray(b2).astype(work)
        if bw.ndim == 1:
            # grouped 1-D rhs items arrive unsqueezed (and their
            # solutions with them); the per-request twin records the
            # (n, 1) view — same bits, same rho
            bw = bw[:, None]
        if xw.ndim == 1:
            xw = xw[:, None]
        r = bw - aw @ xw
        if entry.anorm is None:
            entry.anorm = float(np.abs(aw).sum(axis=1).max())
        rho = _num.scaled_residual(
            float(np.abs(r).max()), float(np.abs(xw).max()),
            float(np.abs(bw).max()), entry.anorm)
        self._record_rho(entry, handle, rho, bw.shape[1])

    def solve(self, handle: Hashable, b,
              served_cols: Optional[int] = None,
              tenant: Optional[str] = None) -> np.ndarray:
        """Array-in/array-out solve (the serving entry point): ``b`` is
        a host/device array of shape (rows,) or (rows, k); returns the
        solution with the matching rank (QR operators return n-row
        least-squares solutions for m-row right-hand sides).
        ``served_cols``: see solve_matrix (Batcher width padding).
        ``tenant``: per-request attribution override (round 15) —
        default is the operator's registered tenant."""
        with self._lock:
            entry = self._ops.get(handle)
            if entry is None:
                raise SlateError(f"Session: unknown handle {handle!r}")
            b = np.asarray(b)
            vector = b.ndim == 1
            b2 = b[:, None] if vector else b
            if entry.op in SMALL_OPS:
                x = self._solve_small(handle, entry, b2, tenant=tenant)
                return x[:, 0] if vector else x
            B = self._wrap_rhs(entry, b2)
            # forward served_cols/tenant only when set: solve_matrix
            # keeps its bare (handle, B) call shape on the common path
            # (test doubles and subclasses depend on it)
            kw = {}
            if served_cols is not None:
                kw["served_cols"] = served_cols
            if tenant is not None:
                kw["tenant"] = tenant
            X = self.solve_matrix(handle, B, **kw)
            x = (_host_crop(X) if isinstance(X, TiledMatrix)
                 else np.asarray(X)[: entry.n])
            return x[:, 0] if vector else x

    # -- the many-small-problems engine (round 10) -------------------------

    def small_group_key(self, handle: Hashable) -> Optional[Tuple]:
        """Grouping key for the Batcher's distinct-operator coalescing:
        (op, n, dtype) for small-problem operators, None otherwise —
        requests whose keys match can be served by ONE batched program
        regardless of which operator each one targets.

        LOCK-FREE on purpose: Batcher.submit calls this on every
        enqueue, and the session lock is held across whole device
        executions (solve/solve_small_batched) — taking it here would
        head-of-line-block enqueues behind in-flight solves, exactly
        the accumulation window batching needs. A bare dict read is
        atomic under the GIL and _Operator entries are immutable after
        register(); a concurrent unregister just yields None (the
        request then falls back to a per-handle bucket and fails with
        unknown-handle at dispatch, same as the per-request path)."""
        entry = self._ops.get(handle)
        if entry is None or entry.op not in SMALL_OPS:
            return None
        if entry.refine is not None:
            # mixed entries group only with same-policy mixed entries
            # (the policy is part of the bucket program's identity);
            # the plain key keeps its 3-tuple shape so existing
            # consumers see no change
            return (entry.op, entry.n, str(np.dtype(entry.A.dtype)),
                    entry.refine)
        return (entry.op, entry.n, str(np.dtype(entry.A.dtype)))

    def _solve_small(self, handle: Hashable, entry: _Operator,
                     b2: np.ndarray,
                     tenant: Optional[str] = None) -> np.ndarray:
        """Caller holds the lock. Per-request arm: the B=1 run of the
        same batched kernels the grouped dispatch uses (the bit-identity
        reference for the Batcher's batched path)."""
        from ..linalg import batched as _batched
        attr = self.attribution
        rt = (self.request_tenant(handle, tenant)
              if (attr is not None or self.slo is not None) else None)
        hit = handle in self._cache
        res = self.factor(handle)
        if res.info != 0:
            if self.slo is not None:
                self.slo.record_request(entry.op, entry.n, 0.0,
                                        ok=False, source="solve",
                                        tenant=rt)
            raise SlateError(
                f"Session: operator {handle!r} factorization failed "
                f"(info={res.info})")
        b2 = np.ascontiguousarray(b2, dtype=np.dtype(entry.A.dtype))
        k = b2.shape[1]
        if self.faults is not None:
            self._fault("dispatch")
        if entry.refine is not None:
            # mixed arm (round 13): one refined B=1 pass through the
            # SAME bucket programs the grouped mixed dispatch runs at
            # B=bucket; non-convergence falls back to the plain path
            # below via a working-precision refactor (counted)
            x = self._solve_small_refined(handle, entry, res, b2,
                                          tenant=rt)
            if x is not None:
                return x
            res = self.factor(handle)  # working-precision refactor
            if res.info != 0:
                raise SlateError(
                    f"Session: operator {handle!r} working-precision "
                    f"fallback factorization failed (info={res.info})")
        tr = self.tracer
        sattrs = (dict(self._span_attrs(entry, handle), k=k,
                       cache_hit=hit) if tr.enabled else {})
        with self.metrics.phase("serve.solve", "solve_latency",
                                tracer=tr, **sattrs) as ph:
            t0 = time.perf_counter()
            with tr.span("serve.dispatch"):
                if entry.op == "lu_small":
                    lu, perm = res.payload
                    x = _batched.getrs_batched(lu[None], perm[None],
                                               b2[None])
                else:
                    x = _batched.potrs_batched(res.payload[0][None],
                                               b2[None])
            t1 = time.perf_counter()
            with tr.span("serve.block"):
                x = jax.block_until_ready(x)
            t2 = time.perf_counter()
        ex = getattr(ph.span, "trace_id", None)
        self.metrics.observe("stage_dispatch", t1 - t0, exemplar=ex)
        self.metrics.observe("stage_device_execute", t2 - t1, exemplar=ex)
        self.metrics.inc("solves_total", k)
        self.metrics.inc("dispatches_total")
        fl = _solve_flops(entry.op, entry.m, entry.n, k, entry.band)
        self.metrics.inc("flops_total", fl)
        self.metrics.inc("solve_flops_total", fl)
        _LEDGER.record("serve.solve", fl)
        if attr is not None:
            attr.record("solve_flops", rt, handle, fl)
            ds = _s_grid(t2 - t1)
            self.metrics.inc("device_seconds_total", ds)
            attr.record("device_seconds", rt, handle, ds)
        if self.slo is not None:
            self.slo.record_request(entry.op, entry.n, ph.elapsed,
                                    ok=True, source="solve", tenant=rt)
        x0 = np.asarray(x[0])
        # sampled probe, per-request small arm: one sampler decision
        # per solve, in request order — the SAME stream the grouped
        # dispatch consumes per item (health-parity pin)
        if (self.numerics is not None and entry.refine is None
                and self.numerics.sampler.decide()):
            self._record_small_probe(entry, handle, x0, b2)
        return x0

    def _solve_small_refined(self, handle: Hashable, entry: _Operator,
                             res: _Resident, b2: np.ndarray,
                             tenant: Optional[str] = None
                             ) -> Optional[np.ndarray]:
        """Caller holds the lock. One refined B=1 solve from the
        resident LOW-precision factor. Returns the solution, or None
        after arming the fallback (refine deactivated, lo resident
        evicted, ``refine_fallbacks_total`` counted) — the caller then
        reruns the plain path against a working-precision refactor."""
        from ..linalg import batched as _batched
        policy = entry.refine
        a = np.asarray(entry.A)
        k = b2.shape[1]
        tr = self.tracer
        sattrs = (dict(self._span_attrs(entry, handle), k=k)
                  if tr.enabled else {})
        with self.metrics.phase("serve.solve", "solve_latency",
                                tracer=tr, **sattrs) as ph:
            t0 = time.perf_counter()
            with tr.span("serve.dispatch"):
                if entry.op == "lu_small":
                    lu, perm = res.payload
                    x, its, conv = _batched.getrs_refined_batched(
                        a[None], lu[None], perm[None], b2[None],
                        max_iters=policy.max_iters, tol=policy.tol)
                else:
                    x, its, conv = _batched.potrs_refined_batched(
                        a[None], res.payload[0][None], b2[None],
                        max_iters=policy.max_iters, tol=policy.tol)
            t1 = time.perf_counter()
            with tr.span("serve.block"):
                x, its, conv = jax.block_until_ready((x, its, conv))
            t2 = time.perf_counter()
        attr = self.attribution
        iters = int(np.asarray(its)[0])
        self.metrics.observe("refine_iterations", float(iters))
        if self.numerics is not None:
            o16, n16 = self.numerics.record_refine(handle, iters)
            self._health_reflex(entry, handle, o16, n16)
        extra = iters * (_flops_mod.gemm(entry.n, k, entry.n)
                         + _solve_flops(entry.op, entry.m, entry.n, k,
                                        entry.band))
        self.metrics.inc("refine_flops_total", extra)
        self.metrics.inc("flops_total", extra)
        _LEDGER.record("serve.refine", extra)
        if attr is not None:
            attr.record("refine_flops", tenant, handle, extra)
        if not bool(np.asarray(conv)[0]):
            self.metrics.inc("refine_fallbacks_total")
            _obs_log.warning(
                "refine fallback: small operator %r did not converge "
                "in %d iterations (factor_dtype=%s)", handle,
                policy.max_iters, policy.factor_dtype)
            rec = self.recorder
            if rec is not None:
                rec.decision("refine_fallback", op=entry.op,
                             handle=handle, tenant=tenant,
                             outcome="not_converged",
                             inputs={"iters": iters,
                                     "max_iters": policy.max_iters})
            if not policy.fallback:
                raise SlateError(
                    f"Session: refined solve of {handle!r} did not "
                    f"converge in {policy.max_iters} iterations and "
                    "the refine policy disables fallback")
            entry.refine = None
            dropped = self._cache.pop(handle, None)
            if dropped is not None:
                self.metrics.inc("evictions")
                self.metrics.inc("evicted_bytes", dropped.nbytes)
                if self.attribution is not None:
                    self._attr_evicted(handle)
                if rec is not None:
                    self._journal_evict(rec, handle, dropped.nbytes,
                                        "refine_fallback", entry=entry)
            return None
        self.metrics.inc("refine_converged_total")
        ex = getattr(ph.span, "trace_id", None)
        self.metrics.observe("stage_dispatch", t1 - t0, exemplar=ex)
        self.metrics.observe("stage_device_execute", t2 - t1,
                             exemplar=ex)
        self.metrics.inc("solves_total", k)
        self.metrics.inc("dispatches_total")
        fl = _solve_flops(entry.op, entry.m, entry.n, k, entry.band)
        self.metrics.inc("flops_total", fl)
        self.metrics.inc("solve_flops_total", fl)
        _LEDGER.record("serve.solve", fl)
        if attr is not None:
            attr.record("solve_flops", tenant, handle, fl)
            ds = _s_grid(t2 - t1)
            self.metrics.inc("device_seconds_total", ds)
            attr.record("device_seconds", tenant, handle, ds)
        if self.slo is not None:
            self.slo.record_request(entry.op, entry.n, ph.elapsed,
                                    ok=True, source="solve",
                                    tenant=tenant)
        return np.asarray(x[0])

    def solve_small_batched(self, handles: List[Hashable], bs: List,
                            tenants: Optional[List] = None
                            ) -> Tuple[np.ndarray, List[int]]:
        """ONE batched pass for a shape bucket of DISTINCT-operator
        small requests (the Batcher's grouped dispatch). Cache-miss
        operators are factored first in one batched factor program and
        the per-item factor slices inserted into the cache (bit-identical
        to the B=1 factors the per-request path would have cached —
        batch-independent kernels); then every request's factor is
        stacked — resident hits and fresh misses alike — and served by
        one batched solve program. Returns ``(xs, infos)``: solutions
        ``[B, rows, k]`` in request order plus per-item factorization
        info — a singular item flags itself, its lane carries the
        garbage, and its neighbors' bits are untouched (per-item
        isolation, pinned by tests/test_batched.py).

        Observability: ``batched_programs`` counts the batched programs
        executed (≤ 2 per bucket: factor for the misses, solve for
        everyone — vs O(B) per-request programs), ``bucket_occupancy``
        records the pow2-bucket fill fraction, and the flop ledger is
        credited B × the per-item serve models."""
        from ..linalg import batched as _batched
        if not handles or len(handles) != len(bs):
            raise SlateError("solve_small_batched: handles and bs must "
                             "be equal-length and nonempty")
        if tenants is not None and len(tenants) != len(handles):
            raise SlateError("solve_small_batched: tenants must match "
                             "handles in length")
        with self._lock:
            entries = []
            for h in handles:
                e = self._ops.get(h)
                if e is None:
                    raise SlateError(f"Session: unknown handle {h!r}")
                if e.op not in SMALL_OPS:
                    raise SlateError(
                        f"solve_small_batched: {h!r} is op {e.op!r}, "
                        "not a small-problem operator")
                entries.append(e)
            op, n = entries[0].op, entries[0].n
            dt = np.dtype(entries[0].A.dtype)
            for e in entries[1:]:
                if e.op != op or e.n != n or np.dtype(e.A.dtype) != dt:
                    raise SlateError(
                        "solve_small_batched: mixed bucket (op/n/dtype "
                        "must agree across the batch)")
            pol = entries[0].refine
            if any(e.refine != pol for e in entries[1:]):
                # a refine fallback deactivated one handle's policy
                # between enqueue (lock-free grouping) and dispatch —
                # rare race; serve the bucket per-request, correctness
                # over coalescing
                return self._serve_small_per_request(handles, bs,
                                                     tenants=tenants)
            bsz = len(handles)
            # round 15: per-item request tenants (override -> operator
            # tenant -> default), resolved once — the grouped dispatch
            # must produce the SAME tenant-labeled tallies B
            # per-request solves would (the satellite-1 parity pin)
            attr = self.attribution
            rts = None
            if attr is not None or self.slo is not None:
                rts = [self.request_tenant(
                    h, None if tenants is None else tenants[i])
                    for i, h in enumerate(handles)]
            tr = self.tracer
            battrs = ({"op": op, "n": n, "batch": bsz, "dtype": str(dt)}
                      if tr.enabled else {})
            programs = 0
            # residency BEFORE factoring: a request against an operator
            # that was already resident counts a cache hit, everything
            # else a miss — the same tallies B per-request solves give
            was_resident = {h: (h in self._cache) for h in set(handles)}
            if self.faults is not None:
                self._fault("dispatch")
            with self.metrics.phase("serve.solve_batched",
                                    "solve_latency", tracer=tr,
                                    **battrs) as ph:
                miss_handles = []
                for h in handles:
                    if not was_resident[h] and h not in miss_handles:
                        miss_handles.append(h)
                if miss_handles:
                    amiss = np.stack([np.asarray(self._ops[h].A)
                                      for h in miss_handles])
                    with tr.span("serve.factor_batched",
                                 batch=len(miss_handles)):
                        if pol is not None and op == "lu_small":
                            lus, perms, infos = \
                                _batched.getrf_mixed_batched(
                                    amiss, pol.factor_dtype)
                            lus, perms, infos = jax.block_until_ready(
                                (lus, perms, infos))
                            payloads = [(lus[i], perms[i])
                                        for i in range(len(miss_handles))]
                        elif pol is not None:
                            ls, infos = _batched.potrf_mixed_batched(
                                amiss, pol.factor_dtype)
                            ls, infos = jax.block_until_ready((ls, infos))
                            payloads = [(ls[i],)
                                        for i in range(len(miss_handles))]
                        elif op == "lu_small":
                            lus, perms, infos = _batched.getrf_batched(
                                amiss)
                            lus, perms, infos = jax.block_until_ready(
                                (lus, perms, infos))
                            payloads = [(lus[i], perms[i])
                                        for i in range(len(miss_handles))]
                        else:
                            ls, infos = _batched.potrf_batched(amiss)
                            ls, infos = jax.block_until_ready((ls, infos))
                            payloads = [(ls[i],)
                                        for i in range(len(miss_handles))]
                    if pol is not None and any(int(v) != 0
                                               for v in np.asarray(infos)):
                        # a LOW-precision batched factor failed (e.g.
                        # SPD goes indefinite under bf16 rounding): do
                        # NOT cache the bad lo residents — serve the
                        # bucket per-request, where Session.factor owns
                        # the counted working-precision fallback (the
                        # per-request parity contract: a recoverable
                        # lo-factor failure must not fail futures or
                        # poison the cache)
                        return self._serve_small_per_request(
                            handles, bs, tenants=tenants)
                    ffl = _factor_flops(op, n, n, 0)
                    for h, payload, inf in zip(miss_handles, payloads,
                                               infos):
                        res_h = _Resident(payload, int(inf),
                                          _tree_nbytes(payload))
                        self._cache[h] = res_h
                        self.metrics.inc("factors_total")
                        self.metrics.inc("flops_total", ffl)
                        self.metrics.inc("factor_flops_total", ffl)
                        _LEDGER.record("serve.factor", ffl)
                        if attr is not None:
                            # factor work belongs to the operator's
                            # tenant (the per-request path's factor()
                            # convention — tenant-labeled parity);
                            # the accrual return conserves the seam
                            # by construction (0 on a true miss)
                            ot = self._ops[h].tenant
                            attr.record("factor_flops", ot, h, ffl)
                            inc = attr.touch_residency(ot, h,
                                                       res_h.nbytes)
                            if inc:
                                self.metrics.inc(
                                    "residency_byte_seconds_total",
                                    inc)
                        self._evict_to_budget(keep=h)
                        if self.tenant_policies is not None:
                            self._evict_tenant_to_budget(
                                self._ops[h].tenant, keep=h)
                    programs += 1
                # per-request residents, in request order (the budget
                # can in principle evict a just-inserted factor while
                # later misses insert; self.factor refactors that item
                # at B=1 — same bits, counted as one more miss).
                # Duplicate handles: only the FIRST request against a
                # cold handle is a miss — its duplicates hit the factor
                # it just inserted, exactly the tallies B sequential
                # per-request solves give (1 miss + B−1 hits).
                res_list = []
                counted_miss = set()
                for h in handles:
                    if was_resident[h] or h in counted_miss:
                        self.metrics.inc("cache_hits")
                        if attr is not None:
                            # same tenant-labeled hit tally (and heat
                            # advance / residency touch) B per-request
                            # solves would record — 1 miss + B−1 hits
                            # per cold duplicate handle, pinned
                            ot = self._ops[h].tenant
                            attr.access(ot, h, True)
                            res_t = self._cache.get(h)
                            if res_t is not None:
                                inc = attr.touch_residency(
                                    ot, h, res_t.nbytes)
                                if inc:
                                    self.metrics.inc(
                                        "residency_byte_seconds_total",
                                        inc)
                        if self.slo is not None:
                            self.slo.record_cache(True)
                        if h in self._cache:
                            self._cache.move_to_end(h)
                    else:
                        self.metrics.inc("cache_misses")
                        if attr is not None:
                            attr.access(self._ops[h].tenant, h, False)
                        if self.slo is not None:
                            self.slo.record_cache(False)
                        counted_miss.add(h)
                    res = self._cache.get(h)
                    if res is None:
                        res = self.factor(h)
                    res_list.append(res)
                infos_req = [r.info for r in res_list]
                import jax.numpy as jnp
                bstack = np.stack([
                    np.ascontiguousarray(np.asarray(b), dtype=dt)
                    for b in bs])
                its = conv = None
                t0 = time.perf_counter()
                with tr.span("serve.dispatch", batch=bsz):
                    if pol is not None:
                        # mixed bucket: ONE batched refined solve over
                        # the stacked LOW-precision residents, per-item
                        # convergence masks (refine/engine); the
                        # working-precision operands feed the residual
                        # gemms
                        astack = np.stack([np.asarray(e.A)
                                           for e in entries])
                        if op == "lu_small":
                            x, its, conv = _batched.getrs_refined_batched(
                                astack,
                                jnp.stack([r.payload[0]
                                           for r in res_list]),
                                jnp.stack([r.payload[1]
                                           for r in res_list]),
                                bstack, max_iters=pol.max_iters,
                                tol=pol.tol)
                        else:
                            x, its, conv = _batched.potrs_refined_batched(
                                astack,
                                jnp.stack([r.payload[0]
                                           for r in res_list]),
                                bstack, max_iters=pol.max_iters,
                                tol=pol.tol)
                    elif op == "lu_small":
                        x = _batched.getrs_batched(
                            jnp.stack([r.payload[0] for r in res_list]),
                            jnp.stack([r.payload[1] for r in res_list]),
                            bstack)
                    else:
                        x = _batched.potrs_batched(
                            jnp.stack([r.payload[0] for r in res_list]),
                            bstack)
                t1 = time.perf_counter()
                with tr.span("serve.block"):
                    x = jax.block_until_ready(x)
                t2 = time.perf_counter()
                programs += 1
                if pol is not None:
                    # np.array (writable copy), not asarray: the
                    # per-item fallback below splices lanes in place
                    x, its, conv = (np.array(x), np.asarray(its),
                                    np.asarray(conv))
                    for i in range(bsz):
                        self.metrics.observe("refine_iterations",
                                             float(its[i]))
                        if self.numerics is not None:
                            # per-item refine drift (round 16): the
                            # grouped mixed bucket records the SAME
                            # per-handle iteration stream B per-request
                            # refined solves would
                            o16, n16 = self.numerics.record_refine(
                                handles[i], int(its[i]))
                            self._health_reflex(entries[i], handles[i],
                                                o16, n16)
                    kk = bstack.shape[2] if bstack.ndim == 3 else 1
                    # per-item refinement flops (iters_i × one step's
                    # residual gemm + factor apply, integer grid), so
                    # the global credit below is EXACTLY the sum of
                    # the tenant-attributed per-item values — the
                    # mixed-lane arm of the satellite-1 parity pin
                    per_step = (_flops_mod.gemm(n, kk, n)
                                + _solve_flops(op, n, n, kk, 0))
                    extra_i = [float(int(its[i])) * per_step
                               for i in range(bsz)]
                    extra = float(sum(extra_i))
                    self.metrics.inc("refine_flops_total", extra)
                    self.metrics.inc("flops_total", extra)
                    _LEDGER.record("serve.refine", extra)
                    if attr is not None:
                        for i in range(bsz):
                            if extra_i[i]:
                                attr.record("refine_flops", rts[i],
                                            handles[i], extra_i[i])
                    self.metrics.inc(
                        "refine_converged_total",
                        int(conv.sum()))
                    for i in range(bsz):
                        if conv[i] or infos_req[i] != 0:
                            continue
                        # per-item fallback: deactivate refinement for
                        # this handle, evict its lo resident, refactor
                        # at working precision, re-solve item i alone —
                        # its bucket neighbors' lanes are untouched
                        h = handles[i]
                        e = self._ops[h]
                        self.metrics.inc("refine_fallbacks_total")
                        _obs_log.warning(
                            "refine fallback: grouped small operator %r "
                            "did not converge in %d iterations", h,
                            pol.max_iters)
                        rec = self.recorder
                        if rec is not None:
                            rec.decision(
                                "refine_fallback", op=e.op, handle=h,
                                tenant=e.tenant,
                                outcome="not_converged",
                                inputs={"max_iters": pol.max_iters,
                                        "grouped": True})
                        if not pol.fallback:
                            raise SlateError(
                                f"Session: refined solve of {h!r} did "
                                "not converge and the refine policy "
                                "disables fallback")
                        if e.refine is not None:
                            e.refine = None
                            dropped = self._cache.pop(h, None)
                            if dropped is not None:
                                self.metrics.inc("evictions")
                                self.metrics.inc("evicted_bytes",
                                                 dropped.nbytes)
                                if self.attribution is not None:
                                    self._attr_evicted(h)
                                if rec is not None:
                                    self._journal_evict(
                                        rec, h, dropped.nbytes,
                                        "refine_fallback", entry=e)
                        res_i = self.factor(h)
                        infos_req[i] = res_i.info
                        if res_i.info != 0:
                            continue
                        if op == "lu_small":
                            lu_i, perm_i = res_i.payload
                            xi = _batched.getrs_batched(
                                lu_i[None], perm_i[None], bstack[i][None])
                        else:
                            xi = _batched.potrs_batched(
                                res_i.payload[0][None], bstack[i][None])
                        x[i] = np.asarray(jax.block_until_ready(xi))[0]
            if self.numerics is not None and pol is None:
                # sampled probe, grouped arm: one sampler decision per
                # SUCCESSFUL item in request order (a failed item's
                # per-request twin raises at the info check before its
                # probe, consuming nothing — so the grouped arm must
                # skip it too or every later decision shifts), the
                # residual from the same host gemm the per-request
                # probe runs on the same solution bits — parity pinned
                xs_np = None
                for i in range(bsz):
                    if infos_req[i] != 0:
                        continue
                    if self.numerics.sampler.decide():
                        if xs_np is None:
                            xs_np = np.asarray(x)
                        self._record_small_probe(entries[i], handles[i],
                                                 xs_np[i], bstack[i])
            ex = getattr(ph.span, "trace_id", None)
            self.metrics.observe("stage_dispatch", t1 - t0, exemplar=ex)
            self.metrics.observe("stage_device_execute", t2 - t1,
                                 exemplar=ex)
            k = bstack.shape[2] if bstack.ndim == 3 else 1
            bucket = _batched.batch_bucket(bsz)
            self.metrics.inc("solves_total", bsz * k)
            self.metrics.inc("dispatches_total")
            self.metrics.inc("batched_programs", programs)
            self.metrics.observe("bucket_occupancy", bsz / bucket)
            per_sfl = _solve_flops(op, n, n, k, 0)
            sfl = bsz * per_sfl
            self.metrics.inc("flops_total", sfl)
            self.metrics.inc("solve_flops_total", sfl)
            _LEDGER.record("serve.solve", sfl)
            if attr is not None:
                # per-item solve flops (global sfl = bsz × per_sfl is
                # exactly their sum on the integer grid) and the
                # batch's device-execute seconds split across items in
                # 2^-20 s grid units — integer division, remainder to
                # the first item, so the per-tenant shares sum
                # BIT-EXACTLY to the global credit
                units = round((t2 - t1) * float(1 << 20))
                share, rem = divmod(int(units), bsz)
                self.metrics.inc("device_seconds_total",
                                 units / float(1 << 20))
                for i in range(bsz):
                    attr.record("solve_flops", rts[i], handles[i],
                                per_sfl)
                    ds_i = (share + (rem if i == 0 else 0)) \
                        / float(1 << 20)
                    if ds_i:
                        attr.record("device_seconds", rts[i],
                                    handles[i], ds_i)
            # padding-waste counters (round 12): the pow2 batch bucket
            # executes bucket − bsz REAL padded lanes (identity
            # operands, zero rhs) in the solve program — and the miss
            # factor program its own bucket's padding. The PROCESS
            # ledger's padding.waste op is credited at the source
            # (linalg/batched pads there); these are the session-level
            # /metrics counters. Exactly 0 at full pow2 occupancy.
            waste_fl = (bucket - bsz) * _solve_flops(op, n, n, k, 0)
            if miss_handles:
                fbucket = _batched.batch_bucket(len(miss_handles))
                waste_fl += ((fbucket - len(miss_handles))
                             * _factor_flops(op, n, n, 0))
            if waste_fl:
                self.metrics.inc("padding_waste_flops", waste_fl)
            self.metrics.set_gauge("batch_bucket_efficiency", bsz / bucket)
            if self.slo is not None:
                for i, inf in enumerate(infos_req):
                    self.slo.record_request(op, n, ph.elapsed,
                                            ok=(inf == 0), source="solve",
                                            tenant=(None if rts is None
                                                    else rts[i]))
            return np.asarray(x), infos_req

    def _serve_small_per_request(self, handles: List[Hashable],
                                 bs: List,
                                 tenants: Optional[List] = None
                                 ) -> Tuple[np.ndarray, List[int]]:
        """Caller holds the lock. Degraded grouped dispatch: each
        request through the per-request path — correctness over
        coalescing, used when the one-program pass is unsafe (a
        stale-policy race after a refine fallback, or a failed
        low-precision batched factor whose lanes must take the
        per-request fallback instead of being cached). Per-item
        isolation: an item whose own solve fails carries its nonzero
        info; neighbors are served normally."""
        xs, infos = [], []
        for i, (h, b) in enumerate(zip(handles, bs)):
            e = self._ops[h]
            b2 = np.ascontiguousarray(np.asarray(b),
                                      dtype=np.dtype(e.A.dtype))
            if b2.ndim == 1:
                b2 = b2[:, None]
            try:
                xs.append(self._solve_small(
                    h, e, b2,
                    tenant=None if tenants is None else tenants[i]))
                infos.append(0)
            except SlateError:
                res = self._cache.get(h)
                infos.append(int(res.info) if res is not None
                             and res.info else 1)
                xs.append(np.zeros_like(b2))
        return np.stack(xs), infos

    def _wrap_rhs(self, entry: _Operator, b2: np.ndarray):
        dtype = (entry.A.dtype if not isinstance(entry.A, PackedBand)
                 else entry.A.ab.dtype)
        b2 = np.ascontiguousarray(b2, dtype=np.dtype(dtype))
        if entry.op in ("band_lu", "band_chol"):
            return jax.numpy.asarray(b2)
        nb = entry.A.nb
        # padded to whole tiles here, on the host: padding on the device
        # compiles a program for every new request width. Mesh operators
        # get a mesh-placed right-hand side (grid=None is the
        # single-device no-op): the solve program then consumes sharded
        # inputs end to end instead of all-gathering at entry
        m, k = b2.shape
        padded = np.zeros((-(-m // nb) * nb, -(-k // nb) * nb), b2.dtype)
        padded[:m, :k] = b2
        return dataclasses.replace(
            from_dense(padded, nb=nb, grid=entry.grid), m=m, n=k)

    def _dispatch(self, entry: _Operator, res: _Resident, B,
                  handle: Hashable = None,
                  served_cols: Optional[int] = None,
                  tenant: Optional[str] = None):
        """Run the solve through a per-(op, opts) jitted function,
        preferring an AOT-compiled executable from warmup() when shapes
        match. opts is part of both cache keys: two operators of the
        same kind registered with different Options (precision, method
        selection) must not share a closure.

        Mesh entries NEVER take the plain-jit fallback: a shape warmup
        missed is AOT-compiled here (one sharded program per (op,
        shapes, dtype, mesh) — the mesh is part of the key via the
        operand treedefs), so every served mesh solve executes an
        analyzed program and credits its collective census."""
        if entry.refine is not None:
            return self._dispatch_refined(entry, res, B, handle,
                                          served_cols=served_cols,
                                          tenant=tenant)
        fn = self._solve_fn(entry)
        Bw = _at_tile_width(B)
        key = self._aot_key(entry, res.payload, Bw)
        exe = self._compiled.get(key)
        if exe is None and entry.grid is not None:
            exe = self._aot_compile("solve", entry, handle, fn,
                                    (res.payload, Bw), key=key)
            self._compiled_put(key, exe)
            self.metrics.inc("aot_compiles")
        if exe is not None:
            self._compiled.move_to_end(key)
            k = int(B.shape[1]) if getattr(B, "shape", None) else 0
            wf = (0.0 if served_cols is None or not k
                  else (k - served_cols) / k)
            self._credit_program(key, "serve.solve", waste_fraction=wf,
                                 tenant=tenant, handle=handle)
            return _at_width_of(exe(res.payload, Bw), B)
        return _at_width_of(fn(res.payload, Bw), B)

    def _solve_fn(self, entry: _Operator):
        return self._jit_cached(
            (entry.op, entry.opts),
            lambda: _make_solve_fn(entry.op, entry.opts))

    # -- sampled residual probe (round 16, obs/numerics.py) ----------------

    def _probe_exe(self, entry: _Operator, handle: Hashable,
                   args: Tuple):
        """AOT-compiled fused solve+residual program for these shapes
        → (exe, key) — the _refine_exe discipline: always analyzed, so
        probed solves credit bytes/census per execution and the budget
        sees the program's transient. Warmup precompiles the
        (m, nrhs) shape; other logical rhs widths compile on their
        first probed use (counted in ``aot_compiles`` — the fused
        norms read the logical extent, so the program is genuinely
        per-width, unlike the plain solve's jit fallback)."""
        leaves, treedef = jax.tree_util.tree_flatten(args)
        shapes = tuple((tuple(l.shape), str(l.dtype)) for l in leaves)
        key = ("probe", entry.op, entry.opts, treedef, shapes)
        exe = self._compiled.get(key)
        if exe is None:
            fn = self._jit_cached(
                ("probe", entry.op, entry.opts),
                lambda: _make_probe_fn(entry.op, entry.opts))
            exe = self._aot_compile("probe", entry, handle, fn, args,
                                    key=key)
            self._compiled_put(key, exe)
            self.metrics.inc("aot_compiles")
        else:
            self._compiled.move_to_end(key)
        return exe, key

    def _dispatch_probed(self, entry: _Operator, res: _Resident, B,
                         handle: Hashable = None,
                         served_cols: Optional[int] = None,
                         tenant: Optional[str] = None):
        """One PROBED dispatch: the serving solve fused with the
        residual gemm and the (‖b−Ax‖, ‖x‖, ‖b‖) max-norm triple in
        ONE program — exactly one gemm more than the plain solve
        program (HLO-pinned by test), executed and credited like every
        other served program. Returns (X, stats)."""
        args = (res.payload, entry.A, B)
        exe, key = self._probe_exe(entry, handle, args)
        k = int(B.shape[1]) if getattr(B, "shape", None) else 0
        wf = (0.0 if served_cols is None or not k
              else (k - served_cols) / k)
        self._credit_program(key, "serve.solve", waste_fraction=wf,
                             tenant=tenant, handle=handle)
        return exe(*args)

    # -- resident spectral serving (round 19, slate_tpu/spectral/) ---------

    @staticmethod
    def _spectral_theta(entry: _Operator, theta) -> np.ndarray:
        """The traced scalar parameter of a served matrix function, at
        a FIXED dtype (the operand's real dtype) so every theta value
        reuses one AOT program — a new shift/ridge/rank never
        recompiles (the zero-new-compiles pin)."""
        rdt = np.zeros((), np.dtype(entry.A.dtype)).real.dtype
        return np.asarray(theta, dtype=rdt)

    def _spectral_apply_exe(self, entry: _Operator, handle: Hashable,
                            fname: str, args: Tuple):
        """AOT-compiled served apply for these shapes → (exe, key).
        ALWAYS through the ``_aot_compile`` seam (the refined-entry
        discipline): every served spectral apply executes an analyzed
        program — exactly two gemms + a diagonal scale (HLO-pinned by
        test) — so bytes/census credit per execution."""
        from .. import spectral as _spectral
        leaves, treedef = jax.tree_util.tree_flatten(args)
        shapes = tuple((tuple(l.shape), str(l.dtype)) for l in leaves)
        key = ("spectral.apply", fname, entry.op, entry.opts, treedef,
               shapes)
        exe = self._compiled.get(key)
        if exe is None:
            fn = self._jit_cached(
                ("spectral.apply", entry.op, fname, entry.opts),
                lambda: _spectral.make_apply_fn(entry.op, fname,
                                                entry.opts))
            exe = self._aot_compile("apply", entry, handle, fn, args,
                                    key=key)
            self._compiled_put(key, exe)
            self.metrics.inc("aot_compiles")
        else:
            self._compiled.move_to_end(key)
        return exe, key

    def _dispatch_spectral(self, entry: _Operator, res: _Resident, B,
                           handle: Hashable = None,
                           fname: str = "solve", theta: float = 0.0,
                           served_cols: Optional[int] = None,
                           tenant: Optional[str] = None):
        """One served spectral apply: X = L·diag(f(spectrum, θ))·Rᴴ·B
        against the resident decomposition."""
        args = (res.payload, B, self._spectral_theta(entry, theta))
        exe, key = self._spectral_apply_exe(entry, handle, fname, args)
        k = int(B.shape[1]) if getattr(B, "shape", None) else 0
        wf = (0.0 if served_cols is None or not k
              else (k - served_cols) / k)
        self._credit_program(key, "serve.solve", waste_fraction=wf,
                             tenant=tenant, handle=handle)
        return exe(*args)

    def _spectral_probe(self, entry: _Operator, res: _Resident, B,
                        handle: Hashable):
        """Caller holds the lock. The sampled spectral residual probe:
        one analyzed single-gemm program computing
        ‖A·v_i − λ_i·v_i‖_max (svd: ‖A·v_i − σ_i·u_i‖_max) over a
        static sample of extreme columns → the stacked max-norm triple
        the shared ρ post-processing consumes."""
        from .. import spectral as _spectral
        args = (res.payload, entry.A)
        leaves, treedef = jax.tree_util.tree_flatten(args)
        shapes = tuple((tuple(l.shape), str(l.dtype)) for l in leaves)
        key = ("spectral.probe", entry.op, entry.opts, treedef, shapes)
        exe = self._compiled.get(key)
        if exe is None:
            fn = self._jit_cached(
                ("spectral.probe", entry.op, entry.opts),
                lambda: _spectral.make_probe_fn(entry.op, entry.opts))
            exe = self._aot_compile("probe", entry, handle, fn, args,
                                    key=key)
            self._compiled_put(key, exe)
            self.metrics.inc("aot_compiles")
        else:
            self._compiled.move_to_end(key)
        self._credit_program(key, "numerics.probe", tenant=entry.tenant,
                             handle=handle)
        return exe(*args)

    def apply(self, handle: Hashable, b, fn: str = "solve",
              theta: float = 0.0, served_cols: Optional[int] = None,
              tenant: Optional[str] = None) -> np.ndarray:
        """Served matrix function of a resident spectral operator:
        x = f(A)·b — solve-with-shift ((A−θI)⁻¹b), psd_project,
        whiten, truncate (see spectral/types.py for the per-op
        catalogs). Array-in/array-out like :meth:`solve`; ``theta`` is
        the function's scalar parameter, traced so any value reuses
        the warmed program. svd note: forward functions (truncate)
        take n-row right-hand sides; inverse-direction functions
        (solve/whiten) take m-row ones."""
        from .. import spectral as _spectral
        with self._lock:
            entry = self._ops.get(handle)
            if entry is None:
                raise SlateError(f"Session: unknown handle {handle!r}")
            if entry.op not in SPECTRAL_OPS:
                raise SlateError(
                    f"Session.apply: operator {handle!r} is "
                    f"{entry.op!r}, not a spectral (eig/svd) resident")
            catalog = _spectral.function_catalog(entry.op)
            if fn not in catalog:
                raise SlateError(
                    f"Session.apply: unknown function {fn!r} for op "
                    f"{entry.op!r}; served functions: "
                    f"{sorted(catalog)}")
            b = np.asarray(b)
            vector = b.ndim == 1
            b2 = b[:, None] if vector else b
            B = self._wrap_rhs(entry, b2)
            kw = {}
            if served_cols is not None:
                kw["served_cols"] = served_cols
            if tenant is not None:
                kw["tenant"] = tenant
            X = self.solve_matrix(handle, B, spectral_fn=fn,
                                  theta=theta, **kw)
            x = X.to_numpy()
            return x[:, 0] if vector else x

    def eigvals(self, handle: Hashable) -> np.ndarray:
        """The resident spectrum: Λ ascending for ``eig`` operators,
        Σ descending for ``svd`` (factoring on miss — a spectrum read
        is a serve and warms the resident like any other)."""
        with self._lock:
            entry = self._ops.get(handle)
            if entry is None:
                raise SlateError(f"Session: unknown handle {handle!r}")
            if entry.op not in SPECTRAL_OPS:
                raise SlateError(
                    f"Session.eigvals: operator {handle!r} is "
                    f"{entry.op!r}, not a spectral (eig/svd) resident")
            res = self.factor(handle)
            if res.info != 0:
                raise SlateError(
                    f"Session: operator {handle!r} factorization "
                    f"failed (info={res.info})")
            p = res.payload
            return np.asarray(p.lam if entry.op == "eig" else p.s)

    # -- mixed-precision refined dispatch (round 13, slate_tpu/refine/) ----

    def _refine_exe(self, entry: _Operator, handle: Hashable, what: str,
                    args: Tuple):
        """AOT-compiled refine ``start``/``step`` program for these
        argument shapes → (exe, key). ALWAYS through the ``_aot_compile``
        seam (like mesh entries): every refined solve executes analyzed
        programs, so bytes/census credit per execution and the budget
        sees the programs' transients."""
        policy = entry.refine
        leaves, treedef = jax.tree_util.tree_flatten(args)
        shapes = tuple((tuple(l.shape), str(l.dtype)) for l in leaves)
        key = (f"refine.{what}", entry.op, entry.opts, policy, treedef,
               shapes)
        exe = self._compiled.get(key)
        if exe is None:
            work = entry.A.dtype
            make = (_refine_engine.make_start_fn if what == "start"
                    else _refine_engine.make_step_fn)
            fn = self._jit_cached(
                (f"refine.{what}", entry.op, entry.opts, policy),
                lambda: make(entry.op, entry.opts, policy, work))
            exe = self._aot_compile(f"refine_{what}", entry, handle, fn,
                                    args, key=key)
            self._compiled_put(key, exe)
            self.metrics.inc("aot_compiles")
        else:
            self._compiled.move_to_end(key)
        return exe, key

    def _dispatch_refined(self, entry: _Operator, res: _Resident, B,
                          handle: Hashable = None,
                          served_cols: Optional[int] = None,
                          tenant: Optional[str] = None):
        """Serve one solve from the LOW-precision resident: initial lo
        solve + the refine engine's convergence loop over analyzed
        start/step programs (classic IR) or the GMRES-IR cycle. Emits
        ``refine.*`` spans nested under the solve span, observes the
        per-solve iteration count, splits the ledger useful-vs-
        refinement (``served_cols`` — the Batcher's pow2 width padding
        — splits the programs' bytes to ``padding.waste`` exactly like
        the plain dispatch), and turns non-convergence into the counted
        fallback: evict the lo resident, refactor at working precision
        through the normal path, re-dispatch — never a wrong answer."""
        policy = entry.refine
        tr = self.tracer
        k = int(B.shape[1])
        wf = (0.0 if served_cols is None or not k
              else (k - int(served_cols)) / k)
        if entry.anorm is None:
            from ..core.types import Norm
            from ..linalg.norms import norm as _norm
            entry.anorm = float(_norm(entry.A, Norm.Inf))
        if policy.strategy == "gmres":
            with tr.span("refine.gmres", max_iters=policy.max_iters):
                X, iters, converged = _refine_engine.gmres_solve(
                    entry.A, B, res.payload, entry.op, policy,
                    entry.opts)
        else:
            start_exe, start_key = self._refine_exe(
                entry, handle, "start", (res.payload, B))
            state = {}

            def start_call(payload, B_):
                with tr.span("refine.start"):
                    X0 = start_exe(payload, B_)
                self._credit_program(start_key, "serve.solve",
                                     waste_fraction=wf,
                                     tenant=tenant, handle=handle)
                return X0

            def step_call(payload, A_, B_, X_):
                exe = state.get("exe")
                if exe is None:
                    exe, skey = self._refine_exe(
                        entry, handle, "step", (payload, A_, B_, X_))
                    state["exe"], state["key"] = exe, skey
                with tr.span("refine.step"):
                    out = exe(payload, A_, B_, X_)
                self._credit_program(state["key"], "serve.refine",
                                     waste_fraction=wf,
                                     tenant=tenant, handle=handle)
                return out

            X, iters, converged = _refine_engine.drive(
                start_call, step_call, res.payload, entry.A, B,
                entry.anorm, policy, entry.A.dtype,
                fault_hook=(None if self.faults is None else
                            (lambda: bool(self._fault(
                                "refine.converge")))))
        self.metrics.observe("refine_iterations", float(iters))
        if self.numerics is not None:
            # refine-iteration drift (round 16): rising iteration
            # counts at fixed tolerance = u_f·κ grew — the
            # conditioning-degradation proxy per handle
            o16, n16 = self.numerics.record_refine(handle, iters)
            self._health_reflex(entry, handle, o16, n16)
        # refinement-overhead model flops: iters residual gemms plus
        # iters factor applies (the useful one-solve model stays on
        # serve.solve — ledger split, ISSUE 10 observability)
        extra = iters * (_flops_mod.gemm(entry.n, k, entry.n)
                         + _solve_flops(entry.op, entry.m, entry.n, k,
                                        entry.band))
        self.metrics.inc("refine_flops_total", extra)
        self.metrics.inc("flops_total", extra)
        _LEDGER.record("serve.refine", extra)
        if self.attribution is not None and extra:
            self.attribution.record("refine_flops", tenant, handle,
                                    extra)
        if converged:
            self.metrics.inc("refine_converged_total")
            return X
        self.metrics.inc("refine_fallbacks_total")
        _obs_log.warning(
            "refine fallback: %r did not converge in %d iterations "
            "(factor_dtype=%s, strategy=%s); refactoring at working "
            "precision", handle, policy.max_iters, policy.factor_dtype,
            policy.strategy)
        rec = self.recorder
        if rec is not None:
            rec.decision("refine_fallback", op=entry.op, handle=handle,
                         tenant=tenant, outcome="not_converged",
                         inputs={"iters": iters,
                                 "max_iters": policy.max_iters,
                                 "strategy": policy.strategy})
        if tr.enabled:
            with tr.span("refine.fallback", handle=repr(handle),
                         iters=iters):
                pass
        if not policy.fallback:
            raise SlateError(
                f"Session: refined solve of {handle!r} did not converge "
                f"in {policy.max_iters} iterations and the refine "
                "policy disables fallback")
        entry.refine = None
        dropped = self._cache.pop(handle, None)
        if dropped is not None:
            self.metrics.inc("evictions")
            self.metrics.inc("evicted_bytes", dropped.nbytes)
            if self.attribution is not None:
                self._attr_evicted(handle)
            if rec is not None:
                self._journal_evict(rec, handle, dropped.nbytes,
                                    "refine_fallback", entry=entry)
        res2 = self.factor(handle)
        if res2.info != 0:
            raise SlateError(
                f"Session: operator {handle!r} working-precision "
                f"fallback factorization failed (info={res2.info})")
        return self._dispatch(entry, res2, B, handle,
                              served_cols=served_cols, tenant=tenant)

    # -- incremental factor maintenance (round 20, linalg/update.py) -------

    def update(self, handle: Hashable, delta=None, *,
               downdate: bool = False, delete=None,
               tenant: Optional[str] = None) -> dict:
        """Serve an operand mutation against the RESIDENT factor at
        O(n²k) instead of paying the O(n³) refactor (round 20,
        linalg/update.py — GGMS C1/C2/Q4, Davis–Hager sweep):

        * ``chol``/``chol_small``: ``delta`` is the (n, k) vector block
          W of A' = A + W·Wᴴ (``downdate=True`` for A − W·Wᴴ; the
          positivity guard degrades a failed downdate to a counted
          refactor of the committed operand — never a wrong factor);
        * ``qr``: ``delta`` is (p, n) rows to APPEND, or ``delete=``
          row indices to remove (incremental for previously appended
          rows; deleting a base row degrades to a counted refactor).

        The mutated operand is committed either way — on every
        degraded path the refactor answers from A', so the caller's
        view of the operator is always the post-mutation one. Ranks
        and appended-row counts are padded to pow2 buckets (zero
        lanes are exactly inert), so a stream of k = 1..16 updates
        compiles O(log k) programs through the same ``_aot_compile``
        census seam as every serving program.

        Returns a result dict: ``applied`` (the incremental path
        served it), ``refactored`` (a counted refactor ran — abort
        fault, failed downdate, base-row delete, or the numerics
        update budget coming due), ``deferred`` (no resident to
        maintain: the mutation committed, the next factor() is a
        plain miss), plus ``info``/``k``/``k_bucket``."""
        with self._lock:
            entry = self._ops.get(handle)
            if entry is None:
                raise SlateError(f"Session: unknown handle {handle!r}")
            if entry.op not in UPDATE_OPS:
                raise SlateError(
                    f"Session.update: operator kind {entry.op!r} has "
                    f"no incremental form (supported: {UPDATE_OPS}); "
                    "re-register the mutated operand instead")
            if entry.grid is not None:
                raise SlateError(
                    "Session.update: mesh residents refactor, they do "
                    "not update (the rotation sweep is sequential in "
                    "columns — no profitable sharding)")
            if entry.op == "qr":
                return self._update_qr(entry, handle, delta, delete,
                                       tenant)
            if delete is not None:
                raise SlateError("Session.update: delete= applies to "
                                 "qr operators only")
            return self._update_chol(entry, handle, delta, downdate,
                                     tenant)

    def _request_tenant_or_none(self, handle: Hashable,
                                tenant: Optional[str]) -> Optional[str]:
        """Caller holds the lock: resolved tenant when attribution
        needs one (the request_tenant rule), else the raw override."""
        if self.attribution is not None:
            return self.request_tenant(handle, tenant)
        return tenant

    def _update_chol(self, entry: _Operator, handle: Hashable, delta,
                     downdate: bool, tenant: Optional[str]) -> dict:
        """Caller holds the lock. Rank-k A' = A ± W·Wᴴ against the
        resident potrf factor: the dense path runs the AOT-compiled
        rotation sweep; the small-engine path runs the B=1 slice of
        the SAME batched sweep the grouped verb uses (bit-identical
        by construction, the round-10 rule)."""
        import jax.numpy as jnp
        from ..linalg import update as _upd
        if delta is None:
            raise SlateError("Session.update: chol update needs delta "
                             "(the (n, k) update-vector block W)")
        small = entry.op == "chol_small"
        wd = np.dtype(entry.A.dtype)
        w = np.asarray(delta)
        if w.ndim == 1:
            w = w[:, None]
        if w.ndim != 2 or w.shape[0] != entry.n:
            raise SlateError(
                f"Session.update: delta must be ({entry.n}, k) update "
                f"vectors, got shape {tuple(w.shape)}")
        w = np.ascontiguousarray(w, dtype=wd)
        k = int(w.shape[1])
        sign = -1 if downdate else 1
        # stage the mutated operand host-side FIRST: whatever happens
        # on the device path (abort fault, failed positivity guard),
        # A' is the committed truth every degraded path answers from
        if small:
            a_cur = np.asarray(entry.A)
            A2 = np.ascontiguousarray(
                a_cur + sign * (w @ w.conj().T), dtype=wd)
            anorm1 = float(np.linalg.norm(a_cur, 1))
        else:
            a_cur = np.asarray(
                entry.A.full_dense())[: entry.n, : entry.n]
            anorm1 = float(np.linalg.norm(a_cur, 1))
            A2 = from_dense(a_cur + sign * (w @ w.conj().T),
                            entry.A.nb, kind=entry.A.kind,
                            uplo=entry.A.uplo)
        self.metrics.inc("updates_total")
        rt = self._request_tenant_or_none(handle, tenant)
        # the fault seam fires BEFORE any resident byte is touched: an
        # injected update_abort models a mid-update failure — the
        # resident is bit-untouched and the committed operand
        # refactors (counted), the chaos exit gate
        if self.faults is not None and self._fault("update"):
            self.metrics.inc("update_aborts_total")
            self._update_commit(entry, A2)
            return self._update_refactor(entry, handle, "abort")
        res = self._cache.get(handle)
        if res is None:
            # nothing resident to maintain: commit the mutation; the
            # next factor() is a plain miss, not a counted refactor
            self._update_commit(entry, A2)
            self.metrics.inc("updates_deferred_total")
            return {"applied": False, "refactored": False,
                    "deferred": True, "info": 0, "op": entry.op,
                    "k": k}
        L = res.payload[0]
        kb = _upd.bucket_k(k)
        ldt = np.dtype(L.dtype)  # factor dtype (lo under refine)
        npad = int(L.shape[-1]) if small else int(L.mt * L.nb)
        wpad = np.zeros((npad, kb), dtype=ldt)
        wpad[: entry.n, :k] = w.astype(ldt)
        if small:
            l2, infos = _upd.chol_update_batched(
                L[None], jnp.asarray(wpad)[None], sign)
            l2 = jax.block_until_ready(l2)
            payload2 = (l2[0],)
            info = int(np.asarray(infos)[0])
        else:
            wdev = jnp.asarray(wpad)
            exe, key = self._update_exe(
                entry, handle,
                "chol_down" if downdate else "chol_up", (L, wdev))
            out, info = exe(L, wdev)
            out = jax.block_until_ready(out)
            payload2 = (out,)
            info = int(info)
            self._credit_program(key, "serve.update", tenant=rt,
                                 handle=handle)
        if downdate and info > 0:
            # the positivity guard fired: A − W·Wᴴ is not (numerically)
            # positive definite along the sweep. The incremental result
            # is discarded; the refactor of the committed operand is
            # the authority — it either succeeds (the guard was
            # rounding-conservative) or reports the indefiniteness
            # itself: detected, never served
            self.metrics.inc("update_downdate_failures_total")
            self._update_commit(entry, A2)
            return self._update_refactor(entry, handle,
                                         "downdate_indefinite")
        self._update_commit(entry, A2)
        return self._update_finish(
            entry, handle, payload2, rt, kb, k,
            float(np.linalg.norm(w, 1)) ** 2, anorm1)

    def _update_qr(self, entry: _Operator, handle: Hashable, rows,
                   delete, tenant: Optional[str]) -> dict:
        """Caller holds the lock. QR row maintenance (GGMS Q4): append
        (``rows`` = the (p, n) new rows) or delete (``delete`` = row
        indices). The resident base factors are never touched —
        appends rebuild the (w, tau, r) append block from the full
        appended stack against the resident R (O(n²·P), not O(mn²));
        deleting a BASE row has no incremental form and degrades to a
        counted refactor of the pruned operand."""
        import jax.numpy as jnp
        from ..linalg import update as _upd
        if (rows is None) == (delete is None):
            raise SlateError(
                "Session.update(qr): exactly one of delta (rows to "
                "append) or delete= (row indices) per call")
        wd = np.dtype(entry.A.dtype)
        a_cur = np.asarray(entry.A.to_dense())  # logical (m, n)
        res = self._cache.get(handle)
        base_m = res.payload[0].m if res is not None else None
        idx = None
        if rows is not None:
            u = np.asarray(rows)
            if u.ndim == 1:
                u = u[None, :]
            if u.ndim != 2 or u.shape[1] != entry.n:
                raise SlateError(
                    f"Session.update(qr): delta must be (p, {entry.n})"
                    f" rows to append, got shape {tuple(u.shape)}")
            u = np.ascontiguousarray(u, dtype=wd)
            k_live = int(u.shape[0])
            a_new = np.vstack([a_cur, u])
            m_new = entry.m + k_live
            wn1_sq = float(np.linalg.norm(u, 1)) ** 2
            base_delete = False
        else:
            idx = np.unique(np.atleast_1d(
                np.asarray(delete, dtype=np.int64)))
            if idx.size == 0:
                raise SlateError("Session.update(qr): delete= is empty")
            if int(idx[0]) < 0 or int(idx[-1]) >= entry.m:
                raise SlateError(
                    f"Session.update(qr): delete= indices out of range "
                    f"for {entry.m} rows")
            k_live = int(idx.size)
            a_new = np.delete(a_cur, idx, axis=0)
            m_new = entry.m - k_live
            if m_new < entry.n:
                raise SlateError(
                    "Session.update(qr): delete would leave an "
                    f"underdetermined operator ({m_new} rows < "
                    f"{entry.n} cols)")
            wn1_sq = float(np.linalg.norm(a_cur[idx], 1)) ** 2
            base_delete = res is None or bool((idx < base_m).any())
        A2 = from_dense(a_new, entry.A.nb)
        anorm1 = float(np.linalg.norm(a_cur, 1))
        self.metrics.inc("updates_total")
        rt = self._request_tenant_or_none(handle, tenant)
        if self.faults is not None and self._fault("update"):
            self.metrics.inc("update_aborts_total")
            self._update_commit(entry, A2, m=m_new)
            return self._update_refactor(entry, handle, "abort")
        if res is None:
            self._update_commit(entry, A2, m=m_new)
            self.metrics.inc("updates_deferred_total")
            return {"applied": False, "refactored": False,
                    "deferred": True, "info": 0, "op": "qr",
                    "k": k_live}
        if base_delete:
            # no incremental form for base-row removal: the pruned
            # operand commits and a counted refactor answers
            self._update_commit(entry, A2, m=m_new)
            return self._update_refactor(entry, handle, "base_delete")
        base = res.payload[0]
        # rows already appended on top of the base factors, recovered
        # from the resident payload itself (cols beyond n and rows
        # beyond the live count are zero padding) — survives
        # checkpoint/restore with no side table
        prev = (np.asarray(res.payload[1])[: entry.m - base.m,
                                           : entry.n]
                if len(res.payload) > 1
                else np.zeros((0, entry.n), dtype=wd))
        if rows is not None:
            u_all = np.vstack([prev.astype(wd, copy=False), u])
        else:
            u_all = np.delete(prev, idx - base.m, axis=0)
        p_all = int(u_all.shape[0])
        self._update_commit(entry, A2, m=m_new)
        if p_all == 0:
            # every appended row deleted: the resident base factors
            # alone are exactly the factorization of the pruned
            # operand — zero device work
            return self._update_finish(entry, handle, (base,), rt, 0,
                                       k_live, wn1_sq, anorm1)
        P = _upd.bucket_k(p_all)
        npad = int(base.vr.shape[1])
        ldt = np.dtype(base.vr.dtype)
        upad = np.zeros((P, npad), dtype=ldt)
        upad[:p_all, : entry.n] = u_all.astype(ldt, copy=False)
        udev = jnp.asarray(upad)
        exe, key = self._update_exe(entry, handle, "qr_append",
                                    (base, udev))
        w_, tau_, r_ = jax.block_until_ready(exe(base, udev))
        self._credit_program(key, "serve.update", tenant=rt,
                             handle=handle)
        return self._update_finish(entry, handle,
                                   (base, udev, w_, tau_, r_), rt, P,
                                   k_live, wn1_sq, anorm1)

    def update_small_batched(self, handles, deltas,
                             downdate: bool = False,
                             tenant: Optional[str] = None) -> list:
        """Grouped incremental maintenance for the many-small-problems
        engine (Kalman-filter/RLS fleets): one bucketed program
        up/downdates B chol_small residents at once, through the same
        per-(B-bucket, n, k-bucket, dtype) program cache as the
        batched solve engine, with per-item info isolation (a failed
        downdate degrades THAT item to a counted refactor; the rest
        commit). Cold handles are factored on miss first (a plain
        miss, then updated). Ranks may differ per item — zero pad
        columns are exactly inert, so the group shares one program at
        the max rank's bucket. Returns one result dict per handle."""
        import jax.numpy as jnp
        from ..linalg import update as _upd
        handles = list(handles)
        deltas = list(deltas)
        if len(handles) != len(deltas):
            raise SlateError("Session.update_small_batched: handles "
                             "and deltas length mismatch")
        if not handles:
            return []
        sign = -1 if downdate else 1
        with self._lock:
            entries = []
            for h in handles:
                e = self._ops.get(h)
                if e is None:
                    raise SlateError(f"Session: unknown handle {h!r}")
                if e.op != "chol_small":
                    raise SlateError(
                        "Session.update_small_batched: chol_small "
                        f"operators only (got {e.op!r} for {h!r})")
                entries.append(e)
            keys = {self.small_group_key(h) for h in handles}
            if len(keys) != 1:
                raise SlateError(
                    "Session.update_small_batched: one (op, n, dtype"
                    "[, refine]) group per call, got "
                    f"{sorted(map(str, keys))}")
            n = entries[0].n
            wd = np.dtype(entries[0].A.dtype)
            ws = []
            for e, d in zip(entries, deltas):
                w = np.asarray(d)
                if w.ndim == 1:
                    w = w[:, None]
                if w.ndim != 2 or w.shape[0] != n:
                    raise SlateError(
                        f"Session.update_small_batched: each delta "
                        f"must be ({n}, k) vectors, got "
                        f"{tuple(w.shape)}")
                ws.append(np.ascontiguousarray(w, dtype=wd))
            kb = _upd.bucket_k(max(w.shape[1] for w in ws))
            residents = [self.factor(h) for h in handles]
            for h, r in zip(handles, residents):
                if r.info != 0:
                    raise SlateError(
                        f"Session: operator {h!r} factorization "
                        f"failed (info={r.info})")
            a_curs = [np.asarray(e.A) for e in entries]
            a2s = [np.ascontiguousarray(
                a + sign * (w @ w.conj().T), dtype=wd)
                for a, w in zip(a_curs, ws)]
            an1s = [float(np.linalg.norm(a, 1)) for a in a_curs]
            B = len(handles)
            self.metrics.inc("updates_total", B)
            if self.faults is not None and self._fault("update"):
                self.metrics.inc("update_aborts_total", B)
                outs = []
                for h, e, a2 in zip(handles, entries, a2s):
                    self._update_commit(e, a2)
                    outs.append(self._update_refactor(e, h, "abort"))
                return outs
            ldt = np.dtype(residents[0].payload[0].dtype)
            npad = int(residents[0].payload[0].shape[-1])
            wpad = np.zeros((B, npad, kb), dtype=ldt)
            for i, w in enumerate(ws):
                wpad[i, :n, : w.shape[1]] = w.astype(ldt)
            ls = jnp.stack([r.payload[0] for r in residents])
            l2, infos = _upd.chol_update_batched(
                ls, jnp.asarray(wpad), sign, live_batch=B)
            l2 = jax.block_until_ready(l2)
            infos = np.asarray(infos)[:B]
            outs = []
            for i, (h, e) in enumerate(zip(handles, entries)):
                self._update_commit(e, a2s[i])
                if downdate and int(infos[i]) > 0:
                    self.metrics.inc("update_downdate_failures_total")
                    outs.append(self._update_refactor(
                        e, h, "downdate_indefinite"))
                    continue
                outs.append(self._update_finish(
                    e, h, (l2[i],),
                    self._request_tenant_or_none(h, tenant), kb,
                    int(ws[i].shape[1]),
                    float(np.linalg.norm(ws[i], 1)) ** 2, an1s[i]))
            return outs

    def _warm_update(self, entry: _Operator, handle: Hashable, res,
                     update_k: int, nrhs: int):
        """Caller holds the lock (warmup's round-20 arm). Compile-only
        — no program executes, nothing is maintained: chol gets both
        sweep signs at the rank bucket; qr gets the append program at
        ``bucket_k(update_k)`` PLUS the appended-payload solve for
        exactly ``update_k`` appended rows at this nrhs."""
        import jax.numpy as jnp
        from ..linalg import update as _upd
        kb = _upd.bucket_k(update_k)
        if entry.op == "chol":
            L0 = res.payload[0]
            w0 = jnp.zeros((int(L0.mt * L0.nb), kb), dtype=L0.dtype)
            self._update_exe(entry, handle, "chol_up", (L0, w0))
            self._update_exe(entry, handle, "chol_down", (L0, w0))
            return
        base = res.payload[0]
        npad = int(base.vr.shape[1])
        dt = base.vr.dtype
        u0 = jnp.zeros((kb, npad), dtype=dt)
        self._update_exe(entry, handle, "qr_append", (base, u0))
        pay5 = (base, u0, jnp.zeros((kb, npad), dtype=dt),
                jnp.zeros((npad,), dtype=dt),
                jnp.zeros((npad, npad), dtype=dt))
        B = self._wrap_rhs(entry, np.zeros(
            (entry.m + int(update_k), nrhs), np.dtype(entry.A.dtype)))
        B = _at_tile_width(B)
        skey = self._aot_key(entry, pay5, B)
        if skey not in self._compiled:
            fn = self._solve_fn(entry)
            self._compiled_put(
                skey, self._aot_compile("solve", entry, handle, fn,
                                        (pay5, B), key=skey))
            self.metrics.inc("aot_compiles")

    def _update_exe(self, entry: _Operator, handle: Hashable,
                    kind: str, args: Tuple):
        """AOT executable for one maintenance program — the _probe_exe
        discipline: cached per (kind, op, opts, treedef, shapes) so a
        k-bucketed update stream pays O(log k) compiles (counted in
        ``aot_compiles``/``update_aot_compiles``), every program
        analyzed so executions credit the bytes ledger and the budget
        sees the transient. Returns ``(exe, key)``."""
        leaves, treedef = jax.tree_util.tree_flatten(args)
        shapes = tuple((tuple(l.shape), str(l.dtype)) for l in leaves)
        key = ("update", kind, entry.op, entry.opts, treedef, shapes)
        exe = self._compiled.get(key)
        if exe is None:
            from ..linalg import update as _upd
            opts = entry.opts
            if kind == "qr_append":
                def make():
                    return lambda qr, u: _upd.qr_append_factor(qr, u)
            else:
                sign = 1 if kind == "chol_up" else -1

                def make():
                    return lambda L, w: _upd.chol_update_factor(
                        L, w, sign, opts)
            fn = self._jit_cached(("update", kind, entry.op,
                                   entry.opts), make)
            exe = self._aot_compile("update", entry, handle, fn, args,
                                    key=key)
            self._compiled_put(key, exe)
            self.metrics.inc("aot_compiles")
            self.metrics.inc("update_aot_compiles")
        else:
            self._compiled.move_to_end(key)
        return exe, key

    def _update_commit(self, entry: _Operator, A2,
                       m: Optional[int] = None):
        """Caller holds the lock: the mutated operand becomes the
        operator's truth. Cached norms are stale — dropped, refreshed
        lazily by the next refined solve / condest probe."""
        entry.A = A2
        if m is not None:
            entry.m = m
        entry.anorm = None
        entry.anorm1 = None

    def _update_evict(self, handle: Hashable):
        """Caller holds the lock: drop the resident (counted eviction,
        residency interval closed) ahead of a degrade-to-refactor."""
        res = self._cache.pop(handle, None)
        if res is None:
            return
        self.metrics.inc("evictions")
        self.metrics.inc("evicted_bytes", res.nbytes)
        if self.attribution is not None:
            self._attr_evicted(handle)
        rec = self.recorder
        if rec is not None:
            self._journal_evict(rec, handle, res.nbytes, "update")
        self._update_hbm_gauges()

    def _update_refactor(self, entry: _Operator, handle: Hashable,
                         reason: str, applied: bool = False) -> dict:
        """Caller holds the lock, mutated operand committed. The
        counted degrade path every update failure funnels through:
        evict the (stale or discarded) resident and refactor A' —
        which either serves correctly or reports its own info, never
        a wrong answer from a half-maintained factor."""
        self.metrics.inc("update_refactors_total")
        rec = self.recorder
        if rec is not None:
            # outcome carries the degrade reason; reason "budget" is
            # the OUTCOME_COUNTERS slice that mirrors
            # update_budget_refactors_total (one decision, two counters)
            rec.decision("update_refactor", op=entry.op, handle=handle,
                         tenant=entry.tenant, outcome=reason,
                         inputs={"applied": applied})
        self._update_evict(handle)
        res = self.factor(handle)
        return {"applied": applied, "refactored": True,
                "reason": reason, "info": int(res.info),
                "op": entry.op}

    def _update_finish(self, entry: _Operator, handle: Hashable,
                       payload2: Tuple, rt: Optional[str], kb: int,
                       k: int, wnorm1_sq: float,
                       anorm1: float) -> dict:
        """Caller holds the lock, operand committed. Install the
        maintained resident, credit the executed-bucket update flops
        (counters + process ledger + attribution cell, all
        grid-snapped — the conservation discipline), then run the
        numerics accrual: if the accumulated update error mass crosses
        the budget, the just-served resident refactors NOW (counted),
        off the next request's path."""
        res2 = _Resident(payload2, 0,
                         _tree_nbytes(payload2, per_chip=True),
                         _tree_nbytes(payload2))
        self._cache[handle] = res2
        self._cache.move_to_end(handle)
        fl = 0.0
        if kb:
            fl = _fl_grid(_flops_mod.update_flops(
                entry.op, entry.m, entry.n, kb))
            self.metrics.inc("flops_total", fl)
            self.metrics.inc("update_flops_total", fl)
            _LEDGER.record("serve.update", fl)
        attr = self.attribution
        if attr is not None:
            if fl:
                attr.record("update_flops", rt, handle, fl)
            inc = attr.touch_residency(entry.tenant, handle,
                                       res2.nbytes)
            if inc:
                self.metrics.inc("residency_byte_seconds_total", inc)
        self._update_hbm_gauges()
        self._evict_to_budget(keep=handle)
        if self.tenant_policies is not None:
            self._evict_tenant_to_budget(entry.tenant, keep=handle)
        refactored = self._update_health(entry, handle, k, wnorm1_sq,
                                         anorm1)
        out = {"applied": True, "refactored": bool(refactored),
               "info": 0, "op": entry.op, "k": k, "k_bucket": kb}
        if refactored:
            out["reason"] = "update_budget"
        return out

    def _update_health(self, entry: _Operator, handle: Hashable,
                       k: int, wnorm1_sq: float,
                       anorm1: float) -> bool:
        """Caller holds the lock, maintained resident installed.
        Accrue the update's growth-weighted error mass and consult the
        refactor-due predicate (obs/numerics.py — ONE source of truth:
        the monitor keeps the authoritative per-handle copy when
        attached, the operator entry carries the monitor-less
        fallback). Returns True when the budget came due and a counted
        refactor replaced the accumulated-error resident."""
        weight = _num.update_weight(k, wnorm1_sq, anorm1)
        nm = self.numerics
        if nm is not None:
            old, new = nm.record_update(handle, k, weight)
            self._health_reflex(entry, handle, old, new)
            due = nm.update_due(handle)
        else:
            entry.updates += 1
            entry.update_weight += weight
            due = _num.update_refactor_due(entry.updates,
                                           entry.update_weight,
                                           _num.DEFAULT_UPDATE_BUDGET)
        if not due:
            return False
        self.metrics.inc("update_budget_refactors_total")
        self._update_refactor(entry, handle, "budget", applied=True)
        return True

    @staticmethod
    def _aot_key(entry: _Operator, payload, B) -> Hashable:
        leaves, treedef = jax.tree_util.tree_flatten((payload, B))
        shapes = tuple((tuple(l.shape), str(l.dtype)) for l in leaves)
        return (entry.op, entry.opts, treedef, shapes)

    # -- AOT warmup --------------------------------------------------------

    def warmup(self, handle: Hashable, nrhs: int = 1,
               update_k: Optional[int] = None):
        """Ahead-of-time path: AOT-compile the whole-factor program
        (dense operators; the lookahead-pipeline driver — round 7),
        factor ``handle`` through it now (off the request path), and
        ``jit(...).lower(...).compile()`` the solve for an
        (rows, nrhs) right-hand side, caching the executables so
        request-time refactors AND solves skip tracing and
        compilation. Dense right-hand sides are tile-padded, so one
        warmup at nrhs=1 covers every bucket width up to the
        operator's nb.

        ``update_k`` (round 20): additionally precompile the
        incremental-maintenance programs at ``bucket_k(update_k)`` —
        both chol sweep signs (zero update vectors are exactly inert,
        so one warm covers every live rank in the bucket), or the QR
        append program plus the appended-payload solve for EXACTLY
        ``update_k`` appended rows (the appended solve's rhs height is
        m + p, so each append count is its own program). After this, a
        served update at the bucket is zero new compiles (the
        acceptance pin)."""
        with self._lock:
            entry = self._ops.get(handle)
            if entry is None:
                raise SlateError(f"Session: unknown handle {handle!r}")
            if entry.op in SMALL_OPS:
                # small ops compile through linalg/batched's own
                # per-bucket program cache: factor now (real work — the
                # cached factor serves requests, so it IS credited) and
                # run one zero-rhs solve so the B=1 solve bucket program
                # exists before the first request; the probe solve is
                # fake traffic and its ledger crediting is suppressed
                from ..linalg import batched as _batched
                res = self.factor(handle)
                if res.info == 0:
                    b0 = np.zeros((entry.n, nrhs),
                                  dtype=np.dtype(entry.A.dtype))
                    with _batched.suppress_accounting():
                        if entry.refine is not None:
                            a0 = np.asarray(entry.A)
                            pol = entry.refine
                            if entry.op == "lu_small":
                                lu, perm = res.payload
                                _batched.getrs_refined_batched(
                                    a0[None], lu[None], perm[None],
                                    b0[None], max_iters=pol.max_iters,
                                    tol=pol.tol)
                            else:
                                _batched.potrs_refined_batched(
                                    a0[None], res.payload[0][None],
                                    b0[None], max_iters=pol.max_iters,
                                    tol=pol.tol)
                        elif entry.op == "lu_small":
                            lu, perm = res.payload
                            _batched.getrs_batched(lu[None], perm[None],
                                                   b0[None])
                        else:
                            _batched.potrs_batched(res.payload[0][None],
                                                   b0[None])
                if (update_k is not None and res.info == 0
                        and entry.op == "chol_small"):
                    # populate the batched sweep's bucket programs at
                    # this rank bucket (zero W is exactly inert, so
                    # running it maintains nothing); suppressed — fake
                    # traffic credits no bytes
                    import jax.numpy as jnp
                    from ..linalg import update as _upd
                    kb = _upd.bucket_k(update_k)
                    L0 = res.payload[0]
                    w0 = jnp.zeros((1, int(L0.shape[-1]), kb),
                                   dtype=L0.dtype)
                    with _batched.suppress_accounting():
                        _upd.chol_update_batched(L0[None], w0, 1)
                        _upd.chol_update_batched(L0[None], w0, -1)
                return
            if entry.op in SPECTRAL_OPS:
                # round 19: factoring runs every pipeline stage through
                # the _aot_compile seam (the stage hook in
                # _factor_spectral), so the factor call below IS the
                # stage warmup; then AOT-compile the served apply for
                # EVERY catalog function at this rhs width (θ is a
                # traced scalar — warmed once, any value serves), plus
                # the sampled residual-probe program when the numerics
                # monitor is on. After this, a served apply is zero
                # new compiles (the acceptance pin).
                from .. import spectral as _spectral
                res = self.factor(handle)
                catalog = _spectral.function_catalog(entry.op)
                wd = np.dtype(entry.A.dtype)
                for fname, (_wf, forward) in catalog.items():
                    rows = (entry.n if entry.op == "eig"
                            else (entry.n if forward else entry.m))
                    B = self._wrap_rhs(entry,
                                       np.zeros((rows, nrhs), wd))
                    self._spectral_apply_exe(
                        entry, handle, fname,
                        (res.payload, B,
                         self._spectral_theta(entry, 0.0)))
                if self.numerics is not None:
                    args = (res.payload, entry.A)
                    leaves, treedef = jax.tree_util.tree_flatten(args)
                    shapes = tuple((tuple(l.shape), str(l.dtype))
                                   for l in leaves)
                    pkey = ("spectral.probe", entry.op, entry.opts,
                            treedef, shapes)
                    if pkey not in self._compiled:
                        fn = self._jit_cached(
                            ("spectral.probe", entry.op, entry.opts),
                            lambda: _spectral.make_probe_fn(
                                entry.op, entry.opts))
                        self._compiled_put(
                            pkey, self._aot_compile(
                                "probe", entry, handle, fn, args,
                                key=pkey))
                        self.metrics.inc("aot_compiles")
                return
            if entry.op in ("lu", "chol", "qr"):
                fkey = self._factor_key(entry)
                if fkey not in self._compiled:
                    ffn = self._factor_fn(entry)
                    self._compiled_put(
                        fkey, self._aot_compile(
                            "factor", entry, handle, ffn, (entry.A,),
                            key=fkey))
                    self.metrics.inc("factor_aot_compiles")
            res = self.factor(handle)
            if (update_k is not None and res.info == 0
                    and entry.op in ("chol", "qr")
                    and entry.grid is None):
                self._warm_update(entry, handle, res, update_k, nrhs)
            B = self._wrap_rhs(
                entry, np.zeros((entry.m, nrhs)))
            if entry.refine is not None:
                if entry.refine.strategy == "gmres":
                    # the GMRES-IR cycle jit-caches itself
                    # (linalg/gmres._fgmres_cycle); factoring above was
                    # the warmup
                    return
                # refined entries serve through the start/step
                # programs: compile both off the request path (the
                # start's probe output supplies the step's X shapes;
                # its execution credits nothing — only the explicit
                # _credit_program calls on the serving path do)
                start_exe, _ = self._refine_exe(entry, handle, "start",
                                                (res.payload, B))
                X0 = start_exe(res.payload, B)
                self._refine_exe(entry, handle, "step",
                                 (res.payload, entry.A, B, X0))
                if self.numerics is not None and entry.op == "lu":
                    # the condest conjugate-transpose program at the
                    # (n, 1) probe shape (nrhs=1 warmup covers it) —
                    # so a warmed refined LU's condest adds no
                    # request-path compiles
                    self._condest_texe(entry, handle, res.payload, B)
                return
            Bw = _at_tile_width(B)
            key = self._aot_key(entry, res.payload, Bw)
            if key not in self._compiled:
                fn = self._solve_fn(entry)
                self._compiled_put(
                    key, self._aot_compile("solve", entry, handle, fn,
                                           (res.payload, Bw), key=key))
                self.metrics.inc("aot_compiles")
            if self.numerics is not None:
                # round 16: precompile the numerics programs off the
                # request path — the fused solve+residual probe at
                # THIS nrhs (the probe's fused norms read the logical
                # width, so other widths compile, counted, on first
                # probed use) and LU's condest transpose solve.
                # Condest's forward applies reuse the solve executable
                # compiled above (same shapes), so a warmed operator's
                # condest adds ZERO compiles (mesh acceptance pin).
                if entry.op in PROBE_OPS:
                    self._probe_exe(entry, handle,
                                    (res.payload, entry.A, B))
                if entry.op == "lu":
                    self._condest_texe(entry, handle, res.payload, B)

    def _aot_compile(self, what: str, entry: _Operator, handle: Hashable,
                     fn, args: Tuple, key: Optional[Hashable] = None):
        """``jit(...).lower(...).compile()`` with compile-time
        observability: the trace+lower and compile stages are timed
        separately into ``warmup_lower_latency`` /
        ``warmup_compile_latency`` histograms and appended per shape to
        ``Session.compile_log`` — the numbers a serving fleet needs to
        budget warmup and alarm on recompiles.

        Round 9: the same seam harvests XLA's cost/memory analyses
        (obs/costs.py) into ``Session.cost_log`` — per shape: model
        flops, bytes-accessed, argument/output/temp/peak HBM, and the
        collective census — and keeps the ProgramCosts keyed under the
        executable's cache key so every execution credits the bytes
        ledger and the budget accounts the program's transient HBM."""
        if self.faults is not None:
            self._fault("compile")  # compile_stall: injected latency
        with self.metrics.phase("serve.warmup", tracer=self.tracer,
                                stage=what,
                                **self._span_attrs(entry, handle)):
            t0 = time.perf_counter()
            lowered = fn.lower(*args)
            t1 = time.perf_counter()
            exe = lowered.compile()
            t2 = time.perf_counter()
        self.metrics.observe("warmup_lower_latency", t1 - t0)
        self.metrics.observe("warmup_compile_latency", t2 - t1)
        leaves = jax.tree_util.tree_leaves(args)
        shapes = [tuple(getattr(l, "shape", ())) for l in leaves]
        self.compile_log.append({
            "op": entry.op, "what": what, "shape": shapes,
            "lower_s": t1 - t0, "compile_s": t2 - t1,
        })
        pc = _costs.program_costs(exe)
        if key is not None:
            self._program_costs[key] = pc
        # rhs width of the program (last array arg; the spectral apply
        # carries a trailing scalar θ, so its rhs is one slot earlier)
        wshape = (shapes[-2] if what == "apply" and len(shapes) >= 2
                  else shapes[-1] if shapes else ())
        kk = wshape[1] if len(wshape) > 1 else 1
        if what == "factor":
            model_fl = _factor_flops(entry.op, entry.m, entry.n,
                                     entry.band)
        elif what.startswith("spectral."):
            # one staged spectral program: the stage's own dominant
            # term (obs/flops.py SPECTRAL_STAGE_MODELS), snapped to
            # the counter grid like every other model numerator
            model_fl = _fl_grid(_flops_mod.spectral_stage_flops(
                what, entry.m, entry.n,
                getattr(entry.A, "nb", entry.band) or 1))
        elif what == "refine_step":
            # one refinement step: the working-precision residual gemm
            # plus one low-precision factor apply
            model_fl = (_flops_mod.gemm(entry.n, kk, entry.n)
                        + _solve_flops(entry.op, entry.m, entry.n, kk,
                                       entry.band))
        elif what == "update":
            # round 20: one incremental-maintenance program. The rank
            # operand is the LAST arg — (npad, kb) vectors for chol
            # (rank = cols), (P, npad) appended rows for qr (rank =
            # rows) — and the model charges the executed bucket
            model_fl = _fl_grid(_flops_mod.update_flops(
                entry.op, entry.m, entry.n,
                (wshape[0] if entry.op == "qr" else kk)
                if wshape else 1))
        else:
            model_fl = _solve_flops(entry.op, entry.m, entry.n, kk,
                                    entry.band)
        self.cost_log.append({
            "op": entry.op, "what": what, "shape": shapes,
            "model_flops": model_fl, "tuned_config": entry.tuned,
            **pc.to_dict(),
        })
        self._cost_index[(entry.op, what)] = float(model_fl or 0.0)
        self._update_hbm_gauges()
        return exe

    # -- placement snapshot (round 15: the fleet-fold placement input) -----

    def placement_snapshot(self, host: Optional[str] = None) -> dict:
        """One schema-validated row per RESIDENT factor — {host,
        tenant, handle, op, n, dtype, bytes_per_chip, heat,
        last_access} — the per-process half of the fleet placement
        input (``obs.aggregate.merge_placement_snapshots`` folds N of
        these into the row set ROADMAP item 1's cache tier and quota
        scheduler consume). ``bytes_per_chip`` is the resident's
        PER-CHIP budget charge (max-per-shard for mesh residents — the
        round-11 convention); heat/last_access come from the
        attribution ledger (0.0/null without one). The producer
        validates its own output against the committed schema
        (obs.attribution.validate_placement_snapshot) so a drifted row
        shape fails HERE, not in a consumer three hops away."""
        if host is None:
            import os as _os
            import socket as _socket
            host = f"{_socket.gethostname()}:{_os.getpid()}"
        attr = self.attribution
        # LOCK-FREE on purpose (the op_meta/small_group_key
        # discipline): the session lock is held across whole device
        # executions, and a /tenants scrape must not stall behind an
        # in-flight solve. list(dict.items()) is one GIL-atomic C
        # call, _Resident/_Operator fields are immutable after
        # insert, and a raced unregister just skips its row — a
        # scrape reads the cache as of one instant, which is all a
        # snapshot ever promises.
        if attr is not None:
            # bring residency byte-seconds current so the snapshot
            # and the counters describe the same instant (the ledger
            # has its own lock)
            inc = attr.accrue_residency()
            if inc:
                self.metrics.inc("residency_byte_seconds_total", inc)
        heat_rows = attr.heat_rows() if attr is not None else {}
        nm = self.numerics
        rows = []
        for h, res in list(self._cache.items()):
            entry = self._ops.get(h)
            if entry is None:
                continue  # unregister raced the scrape
            A = entry.A
            dtype = (A.ab.dtype if isinstance(A, PackedBand)
                     else A.dtype)
            hr = repr(h)
            heat, last = heat_rows.get(hr, (0.0, None))
            # round-16 health columns: a placement policy must see
            # what the numerics monitor sees (a hot-but-suspect
            # resident is a replication candidate NOBODY should copy);
            # null without a monitor — the disabled-path row shape
            health, ce, gr = (nm.placement_info(h) if nm is not None
                              else (None, None, None))
            rows.append({
                "host": host,
                "tenant": self.request_tenant(h),
                "handle": hr,
                "op": entry.op,
                "n": int(entry.n),
                "dtype": str(dtype),
                "bytes_per_chip": int(res.nbytes),
                "heat": heat,
                "last_access": last,
                "health": health,
                "condest": ce,
                "growth": gr,
            })
        doc = {
            "schema": PLACEMENT_SCHEMA,
            "host": host,
            "generated_at": time.time(),
            "rows": rows,
        }
        errs = validate_placement_snapshot(doc)
        if errs:
            raise SlateError(
                f"Session.placement_snapshot: schema self-check failed "
                f"({errs[:3]})")
        return doc

    def tenants_payload(self) -> dict:
        """The ``/tenants`` route payload: the attribution ledger's
        per-(tenant, handle) cells + tenant/global totals (residency
        accrued to now via the placement pass) and the placement
        snapshot. ``{"enabled": false}`` without a ledger."""
        if self.attribution is None:
            return {"enabled": False, "tenants": {},
                    "quotas": self.quotas_payload()}
        placement = self.placement_snapshot()  # accrues residency
        payload = self.attribution.snapshot()
        payload["enabled"] = True
        payload["placement"] = placement
        # round 18: the quota view rides the same route (policies,
        # per-tenant resident bytes vs sub-budget, quota counters)
        payload["quotas"] = self.quotas_payload()
        return payload

    def numerics_payload(self) -> dict:
        """The ``/numerics`` route payload: the monitor's per-handle
        signal rows + state histogram + config, plus the session's
        probe counters. ``{"enabled": false}`` without a monitor."""
        if self.numerics is None:
            return {"enabled": False, "handles": {}}
        payload = self.numerics.snapshot()
        payload["enabled"] = True
        payload["counters"] = {k: self.metrics.get(k) for k in (
            "condest_runs_total", "condest_solves_total",
            "residual_probes_total", "numerics_flops_total",
            "numerics_nonfinite_total", "health_transitions_total",
            "health_demotions_total", "refine_demotions_total")}
        return payload

    # -- checkpoint/restore (round 17: runtime/checkpoint.py) --------------

    def checkpoint(self, path: str, only: Optional[List[Hashable]] = None,
                   host: Optional[str] = None) -> dict:
        """Write this session's RESIDENT state (factor trees + full
        operator metadata, per-blob checksums) to checkpoint directory
        ``path`` — the durable artifact :meth:`restore` warm-restarts
        from without refactoring. ``only`` filters to a handle subset
        (the fleet's replication transfer). Returns the manifest
        (schema ``slate_tpu.checkpoint.v1``, producer-validated)."""
        from .checkpoint import save_session
        return save_session(self, path, only=only, host=host)

    def restore(self, path: str,
                only: Optional[List[Hashable]] = None,
                manifest: Optional[dict] = None) -> dict:
        """Warm-restart from a checkpoint directory: re-register each
        record's operator and re-insert its factor WITHOUT refactoring
        — a restored handle's solve is bit-identical to the
        pre-checkpoint resident's (dense/small/refined entries; mesh
        residents re-shard onto the current grid, round-11 rule).
        Heat/health/tenant carry over when the matching obs components
        are attached. A payload whose checksum fails degrades to
        refactor-on-miss, counted in ``restore_corrupt_total`` — never
        a wrong answer. Returns the restore summary. ``manifest``: an
        already-loaded manifest for ``path`` (skips the re-parse — the
        fleet's per-handle failover restores)."""
        from .checkpoint import restore_session
        return restore_session(self, path, only=only, manifest=manifest)

    def close(self):
        """Orderly shutdown: when a ``checkpoint_dir`` is configured,
        flush a final checkpoint plus a placement snapshot there (the
        state a fleet failover needs to recover this process's
        residents — before round 17, close dropped both on the floor),
        then stop the observability endpoint. Idempotent."""
        if self.checkpoint_dir is not None:
            import json as _json
            os.makedirs(self.checkpoint_dir, exist_ok=True)
            self.checkpoint(os.path.join(self.checkpoint_dir,
                                         "checkpoint"))
            doc = self.placement_snapshot()
            tmp = os.path.join(self.checkpoint_dir, "placement.json.tmp")
            with open(tmp, "w") as f:
                _json.dump(doc, f, indent=2, sort_keys=True)
                f.write("\n")
            os.replace(tmp, os.path.join(self.checkpoint_dir,
                                         "placement.json"))
        self.close_obs()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- observability endpoint --------------------------------------------

    def serve_obs(self, host: str = "127.0.0.1", port: int = 0):
        """Opt-in observability HTTP endpoint for THIS session
        (stdlib-only): /metrics (Prometheus text, plus the tenant_*
        sections once ``enable_attribution`` ran), /healthz,
        /trace.json (Chrome trace of the session's tracer), /slo
        (burn-rate payload once ``enable_slo`` ran), /tenants (the
        attribution + placement payload) — every provider is a
        getter, so enabling AFTER serve_obs still works. Returns
        the ObsServer (``.url()`` gives the scrape target); idempotent
        — a second call returns the running server."""
        with self._lock:
            if self._obs_server is None:
                from ..obs.exposition import ObsServer
                self._obs_server = ObsServer(
                    self.metrics, tracer=self.tracer,
                    host=host, port=port,
                    slo=lambda: self.slo,
                    tenants=lambda: self.tenants_payload(),
                    attribution=lambda: self.attribution,
                    numerics=lambda: self.numerics_payload(),
                    quotas=lambda: self.quotas_payload(),
                    recorder=lambda: self.recorder,
                    history=lambda: self.timeseries,
                    forecast=lambda: self.forecaster)
            return self._obs_server

    def close_obs(self):
        """Shut down the observability endpoint, if started."""
        with self._lock:
            srv, self._obs_server = self._obs_server, None
        if srv is not None:
            srv.close()


def _make_factor_fn(op: str, opts: Options):
    """The dense factor verb as an A -> (payload, info) function — one
    whole-program jit per (op, opts). opts carries the round-7
    ``lookahead`` pipeline flag into the compiled driver."""
    import jax.numpy as jnp

    if op == "lu":
        def factor(A):
            LU, perm, info = api.lu_factor(A, opts)
            return (LU, perm), info
    elif op == "chol":
        def factor(A):
            L, info = api.chol_factor(A, opts)
            return (L,), info
    else:
        def factor(A):
            return (api.qr_factor(A, opts),), jnp.zeros((), jnp.int32)
    factor.__name__ = f"serve_{op}_factor"
    return factor


def _make_probe_fn(op: str, opts: Options):
    """The fused solve+residual program (round 16): the op's
    *_solve_using_factor verb PLUS one residual gemm (``api.multiply``
    — hemm for Hermitian operands, gemm otherwise; under GSPMD a
    sharded A partitions it with its collectives, so mesh probes stay
    sharded end to end) and the stacked (‖b−Ax‖_max, ‖x‖_max,
    ‖b‖_max) triple — so the host convergence read costs the one sync
    the solve already pays (the refine-engine norm discipline)."""
    import jax.numpy as jnp
    solve = _make_solve_fn(op, opts)

    def probe(payload, A, B):
        X = solve(payload, B)
        R = api.multiply(-1.0, A, X, 1.0, B, opts)
        stats = jnp.stack([
            jnp.max(jnp.abs(R.dense_canonical())),
            jnp.max(jnp.abs(X.dense_canonical())),
            jnp.max(jnp.abs(B.dense_canonical())),
        ])
        return X, stats

    probe.__name__ = f"serve_{op}_probe"
    return probe


def _at_tile_width(B):
    """B with its logical width raised to its tile-padded storage width
    (the same data): every request width up to a tile then runs one
    solve program, the one warmup compiled, since the logical width
    is part of the program's key. The padded columns are zero."""
    if not isinstance(B, TiledMatrix) or B.n == B.data.shape[1]:
        return B
    return dataclasses.replace(B, n=int(B.data.shape[1]))


def _at_width_of(X, B):
    """The solve's result back at B's logical width."""
    if not isinstance(X, TiledMatrix) or X.n == B.n:
        return X
    return dataclasses.replace(X, n=B.n)


def _host_crop(X: TiledMatrix) -> np.ndarray:
    """X.to_numpy() cropped on the host: slicing on the device compiles
    a program for every new request width."""
    mm, nn = X.shape
    return np.asarray(X.dense())[:mm, :nn]


def _make_solve_fn(op: str, opts: Options):
    """The *_solve_using_factor verb as a (payload, B) -> X function —
    one jit per op kind; jax's cache keys the rest off shapes/treedefs."""
    if op in ("lu", "band_lu"):
        def solve(payload, B):
            LU, perm = payload
            return api.lu_solve_using_factor(LU, perm, B, opts)
    elif op in ("chol", "band_chol"):
        def solve(payload, B):
            return api.chol_solve_using_factor(payload[0], B, opts)
    else:
        def solve(payload, B):
            if len(payload) > 1:
                # round 20: an appended-rows QR resident carries the
                # 5-tuple (base, u, w, tau, r) — python-level arity
                # branch: jit keys on the treedef, so each payload
                # shape traces its own program, never a mixed one
                from ..linalg import update as _upd
                return _upd.appended_gels(payload, B, opts)
            return api.least_squares_solve_using_factor(payload[0], B, opts)
    solve.__name__ = f"serve_{op}_solve"
    return solve


# -- process-wide session shared with the C API ----------------------------

_DEFAULT: Optional[Session] = None
_DEFAULT_LOCK = threading.Lock()

# resident-factor budget for the shared session: without a bound, every
# handle a long-lived native caller ever solves against would pin its
# factor in HBM forever. 4 GiB default (a quarter of a v5e chip's HBM),
# overridable in bytes via the env var.
_DEFAULT_BUDGET_ENV = "SLATE_TPU_SERVE_HBM_BUDGET"
_DEFAULT_BUDGET = 4 << 30


def default_session() -> Session:
    """The process-wide Session. The C-API opaque-handle solve verbs
    (compat/c_glue.py) and in-process Python callers share this one
    instance, so a factorization paid by either side serves both. Its
    factor cache is bounded (see _DEFAULT_BUDGET / the
    SLATE_TPU_SERVE_HBM_BUDGET env var)."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            import os
            budget = int(os.environ.get(_DEFAULT_BUDGET_ENV,
                                        _DEFAULT_BUDGET))
            _DEFAULT = Session(hbm_budget=budget)
        return _DEFAULT
