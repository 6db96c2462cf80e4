"""Shape-bucketing request batcher.

N callers each asking for one right-hand side against the same resident
operator should cost ONE kernel launch, not N: requests are bucketed by
(handle, single-RHS shape, dtype), column-stacked into one (n, K)
right-hand side, solved once through the Session, and split back —
every *_solve_using_factor verb is column-independent, and dense
right-hand sides are tile-padded to the operator's nb, so a K≤nb batch
runs the SAME padded shape (hence the same compiled executable) as a
single request and returns bit-identical per-request results.

**Distinct-operator grouping (round 10).** Small-problem operators
(``Session`` op kinds ``lu_small``/``chol_small``) are additionally
grouped ACROSS handles: every request whose operator shares
(op, n, dtype) and whose rhs shares a shape lands in one bucket
regardless of which operator it targets, and the bucket dispatches as
ONE batched program pass (``Session.solve_small_batched`` — batched
factor for the cache misses, one batched solve over the stacked
resident factors) instead of B per-request programs. Results are
bit-identical to per-request dispatch because the batched kernels'
arithmetic is batch-independent (linalg/batched); a singular item
fails ITS future with the per-item info and leaves its bucket
neighbors' solutions untouched.

A bucket dispatches when it reaches ``max_batch`` or when its oldest
request has waited ``max_wait`` seconds (the serving deadline knob:
latency floor vs launch amortization). The Batcher itself owns no
thread — the Executor drives ``pop_ready``/``run``; ``flush`` exists
for synchronous callers and tests.

**Tenant isolation (round 18).** With a
:class:`~.tenancy.TenantTable` attached (its own ``tenant_policies=``
or the Session's), ``submit`` enforces per-tenant quotas at the door
(in-flight cap, optional flops/s rate — a counted
:class:`~.faults.QuotaExceeded`, never a silent drop) and
``pop_ready`` replaces FIFO bucket order with deficit-weighted
round-robin over per-tenant ready buckets (same buckets, same
programs, different ORDER — bit-parity pinned; the starvation bound
is the :class:`~.tenancy.DeficitScheduler` docstring's hand-pinned
argument). Tenant-scoped SLO objectives shed the burning tenant's own
cheapest requests first (:meth:`maybe_shed`). ``None`` (the default)
is the pre-round-18 behavior: one is-None check per seam, zero
allocation.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import Future, InvalidStateError
from typing import Dict, Hashable, List, Optional, Tuple

import numpy as np

from ..core.exceptions import SlateError
from ..obs.attribution import s_grid as _s_grid
from ..obs.tracing import NOOP_SPAN as _NOOP_SPAN
from .faults import DeadlineExceeded, QuotaExceeded, RequestShed
from .session import Session
from .tenancy import DeficitScheduler, TokenBucket, as_table


@dataclasses.dataclass
class _Request:
    b: np.ndarray          # always 2-D (rows, 1..k) column block
    vector: bool           # original rank (reshape on completion)
    future: Future
    t_submit: float
    # the operator this request targets (small-problem grouped buckets
    # hold requests against DISTINCT handles; same-operator buckets
    # carry it in the key too)
    handle: Hashable = None
    # obs span, opened at dispatch (parent: the batch span) and closed
    # at future resolution; None while tracing is off or pre-dispatch
    span: object = None
    # absolute monotonic deadline (round 14): past it the request
    # FAILS FAST (DeadlineExceeded, counted, span-annotated) instead
    # of occupying a batch lane; None = no deadline
    deadline: Optional[float] = None
    # explicit per-request tenant override (round 15): None = the
    # operator's registered tenant (resolved lazily at the attribution
    # seams — the disabled path never resolves). An explicit tenant
    # joins the bucket key, so one dispatched bucket is one tenant and
    # the Session-side work attribution stays exact.
    tenant: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class ShedPolicy:
    """Admission-control + load-shedding knobs (round 14 reflexes).

    ``max_queue_depth`` is the ADMISSION bound: a submit that would
    push the queue past it is turned away at the door (its future
    fails immediately with :class:`RequestShed`; the enqueue never
    happens). The overload triggers govern SHEDDING of already-queued
    requests: ``max_age_s`` fires when ``oldest_request_age_s``
    (cancelled requests excluded) exceeds it, ``burn_threshold`` when
    the SLO tracker's worst short-window burn rate does (checked at
    most every ``check_interval_s`` — burn evaluation walks event
    windows and must not run per wakeup). A shed event drops
    ``shed_fraction`` of the queue, CHEAPEST-TO-RECOMPUTE FIRST
    (``Session.recompute_cost`` — the round-9 cost-log ordering:
    resident-factor solves are cheap to retry, cold factor+solve
    requests are not), never below ``min_queue_depth``.

    ``None`` fields disable their trigger; a Batcher with no policy
    pays one is-None check per seam (the round-8 discipline)."""

    max_queue_depth: Optional[int] = None
    max_age_s: Optional[float] = None
    burn_threshold: Optional[float] = None
    shed_fraction: float = 0.5
    min_queue_depth: int = 1
    check_interval_s: float = 0.05

    def __post_init__(self):
        if not (0.0 < self.shed_fraction <= 1.0):
            raise ValueError("ShedPolicy: shed_fraction must be in "
                             f"(0, 1], got {self.shed_fraction}")


BucketKey = Tuple[Hashable, Tuple[int, ...], str]

# first element of a grouped small-problem bucket key — a private
# sentinel, so no user handle (which may be any hashable, including
# the string "small") can collide with it
_SMALL = object()


class Batcher:
    """Coalesces same-operator/same-shape solve requests (see module
    docstring). Thread-safe; dispatch runs on the caller of ``run``."""

    def __init__(self, session: Session, max_batch: int = 32,
                 max_wait: float = 2e-3, pad_widths: bool = False,
                 shed_policy: Optional[ShedPolicy] = None,
                 tenant_policies=None, clock=time.monotonic):
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self.session = session
        self.max_batch = max_batch
        self.max_wait = max_wait
        # admission control + load shedding (round 14): None = off,
        # one is-None check per submit / worker wakeup
        self.shed_policy = shed_policy
        self._last_burn_check = 0.0
        # tenant isolation (round 18, runtime/tenancy.py): quotas at
        # the submit seam (in-flight cap / flops-rate -> counted
        # QuotaExceeded, never a silent drop) and deficit-weighted
        # round-robin dispatch order in pop_ready. Defaults to the
        # SESSION's table so one declaration covers both seams; None =
        # the pre-round-18 FIFO behavior, one is-None check per seam,
        # zero allocation (the round-8 discipline, pinned by test)
        self.tenants = (as_table(tenant_policies)
                        if tenant_policies is not None
                        else getattr(session, "tenant_policies", None))
        self._clock = clock
        if self.tenants is not None:
            self._sched = DeficitScheduler(self.tenants)
            self._deficit_gauges: set = set()
            self._tenant_inflight: Dict[str, int] = {}
            # LRU-capped (tenant strings are client input — arbitrary
            # cardinality must not leak memory; a pruned tenant's
            # bucket restarts full, which is the permissive-but-
            # bounded direction)
            from collections import OrderedDict as _OD
            self._tenant_tokens: "_OD[str, TokenBucket]" = _OD()
            self._tenant_tokens_cap = 1024
        else:
            self._sched = None
        # pow2 width quantization (round 11): pad the stacked
        # right-hand side out to the next power of two with zero
        # columns before dispatch, so a varying coalesced width lowers
        # to O(log max_batch) distinct solve programs instead of one
        # per width — the knob that keeps a MESH session's expensive
        # sharded AOT compiles bounded. Per-request results are
        # untouched: every *_solve_using_factor verb is
        # column-independent, so the extra zero columns never feed the
        # real ones (and they are sliced off before futures resolve).
        self.pad_widths = pad_widths
        self._lock = threading.Lock()
        self._buckets: Dict[BucketKey, List[_Request]] = {}
        # incrementally-maintained backpressure state (round 12): the
        # submit hot path publishes gauges from these two counters
        # instead of scanning every bucket while holding the lock;
        # pop_ready recomputes them exactly from the queue
        self._depth = 0
        self._max_backlog = 0
        self._oldest: Optional[float] = None  # head submit time

    # -- submission --------------------------------------------------------

    def submit(self, handle: Hashable, b, timeout_s: Optional[float]
               = None, tenant: Optional[str] = None) -> Future:
        """Enqueue one solve request; resolves to the solution array
        with the same rank as ``b``. Small-problem operators are
        grouped across handles (module docstring): their bucket key is
        (op, n, dtype, rhs-shape), not the handle.

        ``timeout_s`` (round 14): a per-request deadline carried from
        here through bucket formation to dispatch — once it passes the
        future fails fast with :class:`~.faults.DeadlineExceeded`
        (counted in ``deadline_expired_total``) instead of occupying a
        batch lane. With a :class:`ShedPolicy` admission bound, a
        submit against a full queue returns an ALREADY-FAILED future
        (:class:`~.faults.RequestShed`; ``admission_rejected_total``)
        without enqueueing.

        ``tenant`` (round 15): per-request attribution override. An
        EXPLICIT tenant joins the bucket key (requests with different
        explicit tenants never coalesce — one dispatched program is
        one tenant's work, which keeps the attribution exact and is
        the grain the item-1 weighted-fair scheduler will schedule
        at); ``None`` — every existing caller — keeps today's keys
        byte-identical and attributes to the operator's registered
        tenant."""
        req, rejection = self.submit_deferred(handle, b,
                                              timeout_s=timeout_s,
                                              tenant=tenant)
        if rejection is not None:
            self.reject_admission(req, rejection)
        return req.future

    def submit_deferred(self, handle: Hashable, b,
                        timeout_s: Optional[float] = None,
                        tenant: Optional[str] = None
                        ) -> Tuple[_Request, Optional[Exception]]:
        """The enqueue half of :meth:`submit`: returns ``(request,
        rejection)`` WITHOUT resolving an admission-rejected future —
        for callers that hold their own lock across the enqueue (the
        Executor's shutdown-atomic submit) and must run
        :meth:`reject_admission` after releasing it: resolving a
        future runs client done-callbacks, and a callback that
        re-enters the Executor would deadlock on its non-reentrant
        lock."""
        b = np.asarray(b)
        vector = b.ndim == 1
        b2 = b[:, None] if vector else b
        skey = self.session.small_group_key(handle)
        # an explicit tenant splits the bucket (one program = one
        # tenant); spliced BEFORE the (shape, dtype) tail so grouped
        # dispatch keeps reading op=key[1], n=key[2], shape=key[-2],
        # dtype=key[-1] — and None (every existing caller) keeps the
        # key tuples byte-identical to round 14
        tsplit = () if tenant is None else (str(tenant),)
        if skey is not None:
            if not tsplit and self.tenants is not None:
                # round 18: with a tenant table attached, implicit-
                # tenant SMALL groups split by the OPERATOR tenant too
                # — otherwise two tenants' same-(op, n, dtype)
                # operators would coalesce into one bucket and the
                # aggressor's backlog would ride the victim's weight
                # through the DRR scheduler (review finding, pinned).
                # Per-handle dense buckets are single-operator-tenant
                # by construction; without a table the keys stay
                # byte-identical to round 14 (the round-15 pin).
                tsplit = (self.session.request_tenant(handle, None),)
            key: BucketKey = (_SMALL,) + skey + tsplit + (
                tuple(b2.shape), str(b2.dtype))
        else:
            key = (handle,) + tsplit + (tuple(b2.shape), str(b2.dtype))
        req = _Request(b2, vector, Future(), time.monotonic(),
                       handle=handle,
                       tenant=None if tenant is None else str(tenant))
        if timeout_s is not None:
            req.deadline = req.t_submit + timeout_s
        self.session.metrics.inc("requests_total")
        pol = self.shed_policy
        table = self.tenants
        rt = tpol = None
        with self._lock:
            if table is not None:
                # tenant quota gate (round 18): the tenant's OWN
                # limits, checked before the global admission bound —
                # a QuotaExceeded is counted (quota_rejections_total +
                # the quota_rejected outcome) by reject_admission,
                # never a silent drop
                rt = self.session.request_tenant(handle, req.tenant)
                tpol = table.policy(rt)
                if tpol is not None:
                    if (tpol.max_in_flight is not None
                            and self._tenant_inflight.get(rt, 0)
                            >= tpol.max_in_flight):
                        return req, QuotaExceeded(
                            f"tenant {rt!r} is over its in-flight cap "
                            f"({tpol.max_in_flight}); retry with "
                            "backoff — other tenants are unaffected")
            if (pol is not None and pol.max_queue_depth is not None
                    and self._depth >= pol.max_queue_depth):
                return req, RequestShed(
                    f"admission control: queue depth >= "
                    f"{pol.max_queue_depth}; request rejected at the "
                    "door (retry with backoff)")
            if table is not None and tpol is not None \
                    and tpol.flops_per_s is not None:
                # the rate DEBIT runs last — after every reject-only
                # check — so a request turned away at the admission
                # bound never consumes the tenant's rate budget
                tb = self._tenant_tokens.get(rt)
                if tb is None:
                    tb = self._tenant_tokens[rt] = TokenBucket(
                        tpol.flops_per_s,
                        tpol.flops_per_s * tpol.burst_s,
                        clock=self._clock)
                    while len(self._tenant_tokens) > \
                            self._tenant_tokens_cap:
                        self._tenant_tokens.popitem(last=False)
                else:
                    self._tenant_tokens.move_to_end(rt)
                cost = self.session.recompute_cost(handle, b2.shape[1])
                if not tb.admit(cost):
                    return req, QuotaExceeded(
                        f"tenant {rt!r} is over its "
                        f"{tpol.flops_per_s:.3g} model-flops/s rate; "
                        "retry with backoff — other tenants are "
                        "unaffected")
            bucket = self._buckets.setdefault(key, [])
            bucket.append(req)
            # cheap incremental gauge publish (one batched metrics-
            # lock hold, no full-queue scan on the enqueue hot
            # path); oldest_request_age_s is as of the last queue
            # transition — pop_ready and backpressure() recompute
            # it exactly
            self._depth += 1
            self._max_backlog = max(self._max_backlog, len(bucket))
            if self._oldest is None:
                self._oldest = req.t_submit  # only pops move it back
            gauges = {
                "queue_depth": self._depth,
                "queued_buckets": len(self._buckets),
                "max_bucket_backlog": self._max_backlog,
                "oldest_request_age_s": req.t_submit - self._oldest,
            }
            if rt is not None:
                # in-flight = submitted and unresolved: the cap's
                # denominator. The done-callback decrements on ANY
                # resolution path (completed/failed/shed/expired/
                # cancelled) — registered while the future is pending,
                # so no client code runs under this lock
                n_inf = self._tenant_inflight.get(rt, 0) + 1
                self._tenant_inflight[rt] = n_inf
                req.future.add_done_callback(
                    lambda f, t=rt: self._dec_inflight(t))
                gauges[f"tenant_quota_inflight:{rt}"] = n_inf
            self.session.metrics.set_gauges(gauges)
        return req, None

    def _dec_inflight(self, tenant: str):
        """Future-resolution callback: one tenant's in-flight count
        down (any resolution path — the cap meters live requests). A
        drained tenant's entry AND gauge are dropped — tenant-string
        churn must not grow state or scrape cardinality without bound
        (the round-15 drop_gauge discipline)."""
        with self._lock:
            n = self._tenant_inflight.get(tenant, 0) - 1
            if n <= 0:
                self._tenant_inflight.pop(tenant, None)
            else:
                self._tenant_inflight[tenant] = n
        if n <= 0:
            self.session.metrics.drop_gauge(
                f"tenant_quota_inflight:{tenant}")
        else:
            self.session.metrics.set_gauge(
                f"tenant_quota_inflight:{tenant}", n)

    def tenant_inflight(self, tenant: str) -> int:
        with self._lock:
            return (0 if self._sched is None
                    else self._tenant_inflight.get(str(tenant), 0))

    def reject_admission(self, req: _Request, rejection: Exception):
        """Resolve an admission- or quota-rejected request (call with
        NO locks held — set_exception may run client callbacks). A
        :class:`~.faults.QuotaExceeded` counts the round-18 partition
        (``quota_rejections_total`` + the tenant-labeled
        ``quota_rejected`` outcome); everything else is the round-14
        admission bound."""
        rec = self.session.recorder
        if isinstance(rejection, QuotaExceeded):
            self.session.metrics.inc("quota_rejections_total")
            attr = self.session.attribution
            if attr is not None:
                attr.record_outcome(self._rtenant(req), req.handle,
                                    "quota_rejected")
            if rec is not None:
                rec.decision("quota_reject", handle=req.handle,
                             tenant=self._rtenant(req),
                             outcome="rejected",
                             inputs={"error": str(rejection)})
        else:
            self.session.metrics.inc("admission_rejected_total")
            if rec is not None:
                rec.decision("admission_reject", handle=req.handle,
                             tenant=req.tenant, outcome="rejected",
                             inputs={"error": str(rejection)})
        req.future.set_exception(rejection)

    def pending(self) -> int:
        with self._lock:
            return sum(len(v) for v in self._buckets.values())

    # -- backpressure telemetry (round 12) ---------------------------------

    @staticmethod
    def _head_submit(reqs) -> Optional[float]:
        """Submit time of the oldest LIVE request in a bucket: a
        cancelled-but-undetached request must not pin
        ``oldest_request_age_s`` high (it costs nothing to leave
        queued and nothing to skip at dispatch) — before this, one
        abandoned future could hold the age gauge at its own age
        forever and trigger spurious load shedding."""
        for r in reqs:
            if not r.future.cancelled():
                return r.t_submit
        return None

    def _update_backpressure_locked(self, now: Optional[float] = None):
        """Caller holds the lock. Publish the queue's truth as gauges —
        before this, the only queue signal was the indirect ``queue_s``
        span attribute. Exact recompute, run on pops (the submit hot
        path publishes from the incremental counters instead — module
        state above), so a scrape between dispatches reads the state
        as of the last queue transition. Also resyncs the incremental
        counters."""
        now = time.monotonic() if now is None else now
        m = self.session.metrics
        depths = [len(v) for v in self._buckets.values() if v]
        self._depth = sum(depths)
        self._max_backlog = max(depths, default=0)
        heads = [self._head_submit(reqs)
                 for reqs in self._buckets.values() if reqs]
        self._oldest = min((h for h in heads if h is not None),
                           default=None)
        m.set_gauges({
            "queue_depth": self._depth,
            "queued_buckets": len(depths),
            "max_bucket_backlog": self._max_backlog,
            "oldest_request_age_s": (0.0 if self._oldest is None
                                     else now - self._oldest),
        })

    def backpressure(self) -> dict:
        """Point-in-time queue state, per bucket (JSON-friendly: the
        /metrics gauges carry the aggregates; this is the labeled
        breakdown a debugger wants)."""
        now = time.monotonic()
        with self._lock:
            per_bucket = {}
            for key, reqs in self._buckets.items():
                if not reqs:
                    continue
                head = self._head_submit(reqs)  # cancelled excluded
                per_bucket[repr(key)] = {
                    "backlog": len(reqs),
                    "oldest_age_s": (0.0 if head is None
                                     else now - head)}
        return {
            "queue_depth": sum(v["backlog"] for v in per_bucket.values()),
            "queued_buckets": len(per_bucket),
            "oldest_request_age_s": max(
                (v["oldest_age_s"] for v in per_bucket.values()),
                default=0.0),
            "per_bucket": per_bucket,
        }

    # -- readiness ---------------------------------------------------------

    def next_deadline(self) -> Optional[float]:
        """Earliest monotonic time the worker must act: a bucket's
        max-wait dispatch deadline or a request's own deadline,
        whichever is sooner — so an expiring request fails fast at its
        deadline instead of at the next bucket flush (and an IDLE
        worker sleeps untimed instead of polling)."""
        with self._lock:
            vals = []
            for reqs in self._buckets.values():
                if not reqs:
                    continue
                vals.append(reqs[0].t_submit + self.max_wait)
                vals.extend(r.deadline for r in reqs
                            if r.deadline is not None)
        return min(vals) if vals else None

    def pop_ready(self, now: Optional[float] = None, force: bool = False,
                  expired_out: Optional[List[_Request]] = None
                  ) -> List[Tuple[BucketKey, List[_Request]]]:
        """Detach buckets that are full or past deadline (all of them
        when ``force``). Requests beyond max_batch stay queued.
        Requests past their OWN deadline leave the queue here and fail
        fast (counted, span-annotated) — they never occupy a batch
        lane, and a bucket holding only expired/cancelled requests
        drains without dispatching. ``expired_out``: collect the
        expired requests instead of failing them here — for callers
        that hold a lock of their own (the Executor worker) and must
        run :meth:`_fail_expired` after releasing it (resolving a
        future runs client callbacks)."""
        now = time.monotonic() if now is None else now
        out: List[Tuple[BucketKey, List[_Request]]] = []
        expired: List[_Request] = []
        with self._lock:
            for key in list(self._buckets):
                reqs = self._buckets[key]
                if any(r.deadline is not None and r.deadline <= now
                       for r in reqs):
                    live = []
                    for r in reqs:
                        if (r.deadline is not None and r.deadline <= now
                                and not r.future.done()):
                            expired.append(r)
                        else:
                            live.append(r)
                    self._buckets[key] = reqs = live
                while (len(reqs) >= self.max_batch
                       or (reqs and force)
                       or (reqs and now - reqs[0].t_submit >= self.max_wait)):
                    take, rest = reqs[:self.max_batch], reqs[self.max_batch:]
                    out.append((key, take))
                    self._buckets[key] = reqs = rest
                if not reqs:
                    del self._buckets[key]
            if self._sched is not None and len(out) > 1:
                # round 18: deficit-weighted round-robin dispatch
                # order over per-tenant ready buckets instead of FIFO
                # dict order — same buckets, same programs, different
                # ORDER (bit-parity pinned), so a noisy tenant's
                # backlog cannot push every other tenant's bucket to
                # the back of the dispatch line. The starvation bound
                # is the DeficitScheduler docstring's hand-pinned
                # argument. Bucket tenant: the explicit tenant rides
                # the key (one bucket = one tenant, the round-15
                # invariant), else the first request's operator tenant
                # (request_tenant is lock-free).
                out = self._sched.order([
                    (self.session.request_tenant(reqs[0].handle,
                                                 reqs[0].tenant),
                     len(reqs), (key, reqs))
                    for key, reqs in out])
                deficits = self._sched.deficits()
                self.session.metrics.set_gauges({
                    f"fair_share_deficit:{t}": d
                    for t, d in deficits.items()})
                # gauges for tenants the scheduler pruned are dropped
                # (tenant churn must not grow scrape cardinality)
                for t in self._deficit_gauges - set(deficits):
                    self.session.metrics.drop_gauge(
                        f"fair_share_deficit:{t}")
                self._deficit_gauges = set(deficits)
            if out or expired:
                self._update_backpressure_locked(now)
        if expired_out is None:
            self._fail_expired(expired, now)
        else:
            expired_out.extend(expired)
        return out

    def _fail_expired(self, reqs: List[_Request], now: float):
        """Fail deadline-expired requests fast (outside the queue
        lock: resolving a future can run client callbacks). Counted
        (``deadline_expired_total``), span-annotated, and recorded to
        the SLO error stream — an expiry is a client-visible failure."""
        if not reqs:
            return
        m = self.session.metrics
        tr = self.session.tracer
        slo = self.session.slo
        attr = self.session.attribution
        for r in reqs:
            err = DeadlineExceeded(
                f"deadline exceeded after {now - r.t_submit:.4f}s in "
                "queue (failed fast without occupying a batch lane)")
            try:
                r.future.set_exception(err)
            except InvalidStateError:
                continue  # client cancelled first; counted elsewhere
            m.inc("deadline_expired_total")
            if attr is not None:
                attr.record_outcome(self._rtenant(r), r.handle,
                                    "expired")
            rec = self.session.recorder
            if rec is not None:
                rec.decision("deadline_expired", handle=r.handle,
                             tenant=r.tenant, outcome="failed_fast",
                             inputs={"queue_s": now - r.t_submit,
                                     "deadline_s": r.deadline})
            if tr.enabled:
                sp = r.span or tr.start_span(
                    "serve.request", kind="request",
                    handle=repr(r.handle), queue_s=now - r.t_submit)
                tr.finish_span(sp, error=err, deadline_expired=True)
                r.span = None
            if slo is not None:
                meta = self.session.op_meta(r.handle)
                if meta is not None:
                    slo.record_request(meta[0], meta[1],
                                       now - r.t_submit, ok=False,
                                       tenant=self._rtenant(r))

    # -- admission control + load shedding (round 14) ----------------------

    def maybe_shed(self, now: Optional[float] = None) -> int:
        """The load-shedding reflex, driven by the Executor worker each
        wakeup (one is-None check when no policy). When an overload
        trigger fires — ``oldest_request_age_s`` past ``max_age_s``,
        or the SLO tracker's worst short-window burn rate past
        ``burn_threshold`` — drop ``shed_fraction`` of the queue,
        CHEAPEST-TO-RECOMPUTE FIRST (``Session.recompute_cost``: a
        request against a resident factor re-costs one solve; a cold
        one re-costs factor + solve), failing the shed futures with
        :class:`~.faults.RequestShed`. Returns the number shed."""
        pol = self.shed_policy
        if pol is None:
            return 0
        now = time.monotonic() if now is None else now
        with self._lock:
            depth, oldest = self._depth, self._oldest
        if depth < max(pol.min_queue_depth, 1):
            self.session.metrics.set_gauge("shedding_active", 0.0)
            return 0
        trigger = None
        global_trigger = None
        shed_tenant: Optional[str] = None
        if (pol.max_age_s is not None and oldest is not None
                and now - oldest > pol.max_age_s):
            trigger = f"oldest_request_age_s > {pol.max_age_s}"
        if trigger is None and pol.burn_threshold is not None:
            slo = self.session.slo
            if (slo is not None
                    and now - self._last_burn_check
                    >= pol.check_interval_s):
                self._last_burn_check = now
                # round 18: tenant-scoped objectives shed FIRST and
                # shed ONLY the burning tenant's requests — a noisy
                # tenant pays for its own overload before any global
                # trigger touches its victims' traffic. The GLOBAL
                # burn check still runs (worst_burn_rate walks every
                # objective, tenant-scoped included) so that a burning
                # tenant with nothing left queued cannot suppress the
                # round-14 overload reflex for everyone else.
                if self.tenants is not None:
                    rates = slo.tenant_burn_rates(now=now)
                    over = {t: b for t, b in rates.items()
                            if b > pol.burn_threshold}
                    if over:
                        shed_tenant = max(over, key=lambda t: over[t])
                        trigger = (f"tenant {shed_tenant!r} slo burn "
                                   f"rate {over[shed_tenant]:.3g} > "
                                   f"{pol.burn_threshold}")
                burn = slo.worst_burn_rate(now=now)
                if burn > pol.burn_threshold:
                    global_trigger = (f"slo burn rate {burn:.3g} > "
                                      f"{pol.burn_threshold}")
                    if trigger is None:
                        trigger = global_trigger
                        shed_tenant = None
        if trigger is None:
            self.session.metrics.set_gauge("shedding_active", 0.0)
            return 0
        victims: List[_Request] = []
        with self._lock:
            queued = [(key, r) for key, reqs in self._buckets.items()
                      for r in reqs if not r.future.done()]
            pool = (queued if shed_tenant is None else
                    [kr for kr in queued
                     if self._rtenant(kr[1]) == shed_tenant])
            if not pool and shed_tenant is not None \
                    and global_trigger is not None:
                # the burning tenant has nothing queued: fall back to
                # the global overload reflex instead of skipping the
                # whole interval (review finding, pinned)
                trigger, shed_tenant = global_trigger, None
                pool = queued
            # the floor: never shed below min_queue_depth live
            # requests (the docstring contract); a tenant-scoped shed
            # draws only from that tenant's pool
            n_shed = min(max(1, int(len(pool) * pol.shed_fraction)),
                         len(queued) - max(pol.min_queue_depth, 1),
                         len(pool))
            if n_shed <= 0:
                self.session.metrics.set_gauge("shedding_active", 0.0)
                return 0
            # cheapest-to-recompute first; newest first among equals
            # (the oldest requests are closest to being served)
            pool.sort(key=lambda kr: (
                self.session.recompute_cost(kr[1].handle,
                                            kr[1].b.shape[1]),
                -kr[1].t_submit))
            chosen = pool[:n_shed]
            drop = {id(r) for _, r in chosen}
            for key in list(self._buckets):
                kept = [r for r in self._buckets[key]
                        if id(r) not in drop]
                if kept:
                    self._buckets[key] = kept
                else:
                    del self._buckets[key]
            victims = [r for _, r in chosen]
            self._update_backpressure_locked(now)
        m = self.session.metrics
        m.inc("load_sheds_total")
        if shed_tenant is not None:
            m.inc("tenant_sheds_total")
        m.set_gauge("shedding_active", 1.0)
        tr = self.session.tracer
        attr = self.session.attribution
        shed = 0
        for r in victims:
            try:
                r.future.set_exception(RequestShed(
                    f"load shed ({trigger}); cheapest-to-recompute "
                    "first per the session cost log — retry with "
                    "backoff"))
            except InvalidStateError:
                continue  # cancelled concurrently
            shed += 1
            if attr is not None:
                attr.record_outcome(self._rtenant(r), r.handle, "shed")
            if tr.enabled:
                sp = r.span or tr.start_span(
                    "serve.request", kind="request",
                    handle=repr(r.handle), queue_s=now - r.t_submit)
                tr.finish_span(sp, shed=True)
                r.span = None
        m.inc("shed_requests_total", shed)
        rec = self.session.recorder
        if rec is not None and shed:
            # ONE wave = ONE decision; count carries the victim total
            # (journal parity vs shed_requests_total sums count)
            rec.decision("shed", tenant=shed_tenant, outcome=trigger,
                         count=shed,
                         inputs={"trigger": trigger,
                                 "queued": len(queued),
                                 "victims": shed})
        return shed

    # -- dispatch ----------------------------------------------------------

    def _rtenant(self, r: _Request) -> str:
        """Resolved tenant of one request (explicit override ->
        operator tenant -> default). Only called from seams that
        already verified the attribution/SLO consumer exists."""
        return self.session.request_tenant(r.handle, r.tenant)

    def _attr_queue_wait(self, attr, r: _Request, now: float):
        """Caller verified ``attr is not None``: queue-wait seconds on
        the dyadic grid, same snapped value to the per-tenant cell and
        the ``queue_seconds_total`` global (the conservation seam)."""
        qs = _s_grid(now - r.t_submit)
        if qs:
            self.session.metrics.inc("queue_seconds_total", qs)
            attr.record("queue_seconds", self._rtenant(r), r.handle, qs)

    def run(self, key: BucketKey, reqs: List[_Request]):
        """Solve one detached bucket: stack → one Session solve → split.
        Future resolution (including request latency metrics) happens
        here; exceptions propagate to the caller AND the unresolved
        futures are left pending so the caller can retry (see Executor).
        Idempotent over futures: already-done (resolved on an earlier
        attempt, or client-cancelled) requests are skipped, so a retry
        only covers what is still unresolved.

        Tracing: the batch span is the trace ROOT — N requests meet in
        one dispatch, and a tree has one root, so the per-request spans
        are parented onto the batch span (their queue wait rides along
        as the ``queue_s`` attribute, their end is future resolution);
        the Session's solve/factor/dispatch spans nest under the batch
        span via the contextvar scope."""
        if key and key[0] is _SMALL:
            return self._run_small(key, reqs)
        # key = (handle[, tenant], shape, dtype): the optional round-15
        # tenant splice sits between the handle and the fixed tail
        handle = key[0]
        kshape, kdtype = key[-2], key[-1]
        now = time.monotonic()
        live = self._live(reqs, now)
        if not live:
            return
        tr = self.session.tracer
        bctx = (tr.span("serve.batch", handle=repr(handle),
                        batch_size=len(live), shape=list(kshape),
                        dtype=kdtype) if tr.active else _NOOP_SPAN)
        m = self.session.metrics
        attr = self.session.attribution
        with bctx as bspan:
            # exemplar join key: the batch's trace id (NOOP -> None)
            tid = getattr(bspan, "trace_id", None)
            for r in live:
                # None unless this attempt re-runs a bucket whose spans
                # the Executor already closed (errored attempt) — each
                # attempt gets spans nested in ITS batch span
                if r.span is None:
                    r.span = tr.start_span(
                        "serve.request", parent=bspan, kind="request",
                        handle=repr(handle), shape=list(r.b.shape),
                        dtype=kdtype, queue_s=now - r.t_submit)
                # lifecycle stage 1 (round 12): submit -> dispatch start
                m.observe("stage_queue_wait", now - r.t_submit,
                          exemplar=tid)
                if attr is not None:
                    self._attr_queue_wait(attr, r, now)
            try:
                t_form = time.monotonic()
                stacked = np.concatenate([r.b for r in live], axis=1)
                cols = stacked.shape[1]
                if self.pad_widths:
                    # the shared pow2 quantum (also the batch-dim
                    # bucket of linalg/batched) — one definition, so
                    # the Batcher's padded widths can never drift
                    # from the bucketing the rest of the repo primes
                    from ..ops.blocked import bucket_pow2
                    # round 21: the width quantum comes through the
                    # tuning table when one is active for this handle's
                    # (op, n, dtype) — tuned_width_quantum is a single
                    # `tuning is None` check returning 1 when disabled,
                    # so the untuned pad grid is bit-identical to HEAD
                    w = bucket_pow2(
                        cols, self.session.tuned_width_quantum(handle))
                    if w > cols:
                        stacked = np.concatenate(
                            [stacked, np.zeros((stacked.shape[0],
                                                w - cols),
                                               stacked.dtype)], axis=1)
                # lifecycle stage 2: stack + width-pad the bucket (one
                # observation per batch — formation is batch-scoped)
                m.observe("stage_batch_form", time.monotonic() - t_form,
                          exemplar=tid)
                # served_cols: only the CLIENT columns count as solves
                # — the padded zero columns are executed work (the
                # ledgers see them, split out as padding_waste_flops/
                # bytes — round 12) but not served requests. Passed
                # only when padding actually happened — and the
                # round-15 tenant only when a request carried an
                # explicit override (the key split guarantees the
                # bucket is single-tenant) — so the common path keeps
                # the bare solve(handle, b) signature.
                kw = {}
                if stacked.shape[1] != cols:
                    kw["served_cols"] = cols
                if live[0].tenant is not None:
                    kw["tenant"] = live[0].tenant
                x = self.session.solve(handle, stacked, **kw)
            except Exception as e:
                # close this attempt's request spans INSIDE the batch
                # scope: the exception is about to close the batch span
                # via bctx.__exit__, and children ending after their
                # parent fail the Chrome-trace nesting validator
                for r in live:
                    tr.finish_span(r.span, error=e)
                raise
            m.inc("batches_total")
            m.observe("batch_size", float(len(live)))
            done = time.monotonic()
            slo = self.session.slo
            meta = (self.session.op_meta(handle)
                    if slo is not None else None)
            col = 0
            for r in live:
                w = r.b.shape[1]
                xi = x[:, col:col + w]
                col += w
                try:
                    r.future.set_result(xi[:, 0] if r.vector else xi)
                except InvalidStateError:
                    # client cancelled between our done() check and here
                    m.inc("cancelled_requests")
                    tr.finish_span(r.span, cancelled=True)
                    continue
                lat = done - r.t_submit
                m.inc("completed_requests")
                if attr is not None:
                    attr.record_outcome(self._rtenant(r), r.handle,
                                        "completed")
                m.observe("request_latency", lat, exemplar=tid)
                if meta is not None:
                    slo.record_request(meta[0], meta[1], lat, ok=True,
                                       tenant=self._rtenant(r))
                # total_s (submit -> resolve) is what the slow-request
                # log thresholds on — the client-visible latency
                tr.finish_span(r.span, total_s=lat)
            # lifecycle stage 5: solve done -> futures resolved (the
            # split/copy/notify reply cost, once per batch)
            m.observe("stage_reply", time.monotonic() - done,
                      exemplar=tid)

    def _live(self, reqs: List[_Request], now: float) -> List[_Request]:
        """Dispatch-start filter: drop already-resolved requests and
        fail the deadline-expired ones fast (a request can expire
        between detach and dispatch — e.g. while an earlier bucket
        retried through backoff)."""
        live, expired = [], []
        for r in reqs:
            if r.future.done():
                continue
            if r.deadline is not None and r.deadline <= now:
                expired.append(r)
            else:
                live.append(r)
        self._fail_expired(expired, now)
        return live

    def _run_small(self, key: BucketKey, reqs: List[_Request]):
        """Grouped small-problem dispatch: one bucket of DISTINCT-
        operator requests → ONE batched program pass through
        ``Session.solve_small_batched`` (batched factor for misses +
        one batched solve over the stacked factors). A singular item
        fails ITS OWN future with the per-item info (the SlateError the
        per-request path would have raised); its neighbors' solutions
        are bit-identical to what per-request dispatch produces."""
        # key = (_SMALL, op, n, op-dtype[, refine-policy], rhs-shape,
        # rhs-dtype): mixed entries (round 13) carry their RefinePolicy
        # in the group key so two policies never coalesce — read the
        # fixed head and tail, tolerate the optional middle
        op, n = key[1], key[2]
        shape, bdt = key[-2], key[-1]
        now = time.monotonic()
        live = self._live(reqs, now)
        if not live:
            return
        tr = self.session.tracer
        bctx = (tr.span("serve.batch", op=op, n=n, grouped=True,
                        batch_size=len(live), shape=list(shape),
                        dtype=bdt) if tr.active else _NOOP_SPAN)
        m = self.session.metrics
        attr = self.session.attribution
        with bctx as bspan:
            tid = getattr(bspan, "trace_id", None)
            for r in live:
                if r.span is None:
                    r.span = tr.start_span(
                        "serve.request", parent=bspan, kind="request",
                        handle=repr(r.handle), shape=list(r.b.shape),
                        dtype=bdt, queue_s=now - r.t_submit)
                m.observe("stage_queue_wait", now - r.t_submit,
                          exemplar=tid)
                if attr is not None:
                    self._attr_queue_wait(attr, r, now)
            try:
                # explicit tenant overrides ride the bucket key (one
                # bucket = one explicit tenant), so the per-item
                # tenants list is uniform; None lets the Session
                # resolve each item's operator tenant
                tenants = ([r.tenant for r in live]
                           if live[0].tenant is not None else None)
                xs, infos = self.session.solve_small_batched(
                    [r.handle for r in live], [r.b for r in live],
                    tenants=tenants)
            except Exception as e:
                for r in live:
                    tr.finish_span(r.span, error=e)
                raise
            m.inc("batches_total")
            m.observe("batch_size", float(len(live)))
            done = time.monotonic()
            slo = self.session.slo
            for i, r in enumerate(live):
                if infos[i] != 0:
                    err = SlateError(
                        f"Session: operator {r.handle!r} factorization "
                        f"failed (info={infos[i]})")
                    try:
                        r.future.set_exception(err)
                        m.inc("failed_requests_total")
                        if attr is not None:
                            attr.record_outcome(self._rtenant(r),
                                                r.handle, "failed")
                    except InvalidStateError:
                        m.inc("cancelled_requests")
                    if slo is not None:
                        slo.record_request(op, n, done - r.t_submit,
                                           ok=False,
                                           tenant=self._rtenant(r))
                    tr.finish_span(r.span, error=err)
                    continue
                xi = xs[i]
                try:
                    r.future.set_result(xi[:, 0] if r.vector else xi)
                except InvalidStateError:
                    m.inc("cancelled_requests")
                    tr.finish_span(r.span, cancelled=True)
                    continue
                lat = done - r.t_submit
                m.inc("completed_requests")
                if attr is not None:
                    attr.record_outcome(self._rtenant(r), r.handle,
                                        "completed")
                m.observe("request_latency", lat, exemplar=tid)
                if slo is not None:
                    slo.record_request(op, n, lat, ok=True,
                                       tenant=self._rtenant(r))
                tr.finish_span(r.span, total_s=lat)
            m.observe("stage_reply", time.monotonic() - done,
                      exemplar=tid)

    def run_degraded(self, key: BucketKey, reqs: List[_Request]):
        """The per-request rung of the degradation ladder
        (grouped→per_request, dense→per_request — faults.
        DEGRADATION_LADDER), walked by the Executor when a bucket's
        circuit breaker is open: every live request runs as its OWN
        Session.solve, so one poisoned lane (or a failure mode the
        coalesced program tickles) cannot fail its neighbors.
        Per-item isolation: a request whose own solve raises fails its
        own future; the rest are served. Futures resolve exactly once
        (already-done requests skipped, the run() discipline)."""
        m = self.session.metrics
        tr = self.session.tracer
        slo = self.session.slo
        attr = self.session.attribution
        now = time.monotonic()
        live = self._live(reqs, now)
        if not live:
            return
        m.inc("degraded_dispatches_total")
        bctx = (tr.span("serve.batch.degraded", batch_size=len(live),
                        ladder="per_request")
                if tr.active else _NOOP_SPAN)
        with bctx as bspan:
            tid = getattr(bspan, "trace_id", None)
            for r in live:
                if r.span is None:
                    r.span = tr.start_span(
                        "serve.request", parent=bspan, kind="request",
                        handle=repr(r.handle), degraded=True,
                        queue_s=now - r.t_submit)
                if attr is not None:
                    self._attr_queue_wait(attr, r, now)
                meta = self.session.op_meta(r.handle)
                try:
                    if r.tenant is not None:
                        x = self.session.solve(r.handle, r.b,
                                               tenant=r.tenant)
                    else:
                        x = self.session.solve(r.handle, r.b)
                except Exception as e:  # noqa: BLE001 — per-item isolation
                    try:
                        r.future.set_exception(e)
                        m.inc("failed_requests_total")
                        if attr is not None:
                            attr.record_outcome(self._rtenant(r),
                                                r.handle, "failed")
                    except InvalidStateError:
                        m.inc("cancelled_requests")
                    if slo is not None and meta is not None:
                        slo.record_request(
                            meta[0], meta[1],
                            time.monotonic() - r.t_submit, ok=False,
                            tenant=self._rtenant(r))
                    tr.finish_span(r.span, error=e)
                    continue
                done = time.monotonic()
                try:
                    r.future.set_result(x[:, 0] if r.vector else x)
                except InvalidStateError:
                    m.inc("cancelled_requests")
                    tr.finish_span(r.span, cancelled=True)
                    continue
                lat = done - r.t_submit
                m.inc("completed_requests")
                if attr is not None:
                    attr.record_outcome(self._rtenant(r), r.handle,
                                        "completed")
                m.observe("request_latency", lat, exemplar=tid)
                if slo is not None and meta is not None:
                    slo.record_request(meta[0], meta[1], lat, ok=True,
                                       tenant=self._rtenant(r))
                tr.finish_span(r.span, total_s=lat)

    def flush(self):
        """Synchronously dispatch everything pending (caller's thread)."""
        for key, reqs in self.pop_ready(force=True):
            self.run(key, reqs)
