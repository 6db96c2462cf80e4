"""Structured request-scoped tracing: the span model.

The reference's observability is two-layered: ``trace::Block`` RAII
events gathered into an SVG timeline (include/slate/internal/Trace.hh,
src/auxiliary/Trace.cc:330-446) and the coarse per-phase ``timers`` map
the tester prints at --timer-level 2. ``utils.trace`` ports both; this
module grows them into what a *serving* stack needs: structured spans
with identity (trace-id, span-id, parent-id), attributes (op, shape,
dtype, nb, cache hit/miss, handle), error status, and request-scoped
propagation — a served solve yields a connected span TREE
(batch → request / solve → factor / dispatch / block), exportable as
Chrome-trace JSON (obs.export) next to the legacy SVG.

Design rules:

* **Disabled is free.** ``Tracer.span`` returns a shared no-op context
  manager when tracing is off and the JAX profiler is not recording —
  no Span allocation, no id counter bump, no lock. The runtime's hot
  path stays at its round-6 cost.
* **On the profiler's clock.** While the JAX profiler records, every
  ``Tracer.span`` also opens a ``jax.profiler.TraceAnnotation`` of the
  same name, with obs tracing on or off, so a served request's host
  spans land in the device trace itself, beside the ops they dispatched.
* **One clock, every view.** A finished span also feeds the legacy
  ``trace.timers`` map and (when ``trace.Trace`` is on) the SVG event
  list, so enabling spans never *loses* the coarse views — the span
  model subsumes ``utils.trace.phase``.
* **Propagation is a contextvar**, per thread of execution: nested
  ``with tracer.span(...)`` blocks parent automatically; the Batcher
  parents request spans onto the batch span explicitly (they begin
  life queued, outside any context — see runtime/batching.py).
* **Slow-request log + error capture.** Spans of kind ``"request"``
  whose total latency exceeds ``Tracer.slow_threshold`` land in a
  bounded ``slow_log`` (and a logging.warning); a span closed by an
  exception (or finished with ``error=``) records status="error" and
  the exception text — the Executor feeds failed-retry batches here.
"""

from __future__ import annotations

import contextvars
import itertools
import logging
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from ..utils import trace as legacy_trace

log = logging.getLogger("slate_tpu.obs")


class Span:
    """One timed, attributed node of a trace tree."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "start", "end",
                 "attrs", "thread", "status", "error", "kind")

    def __init__(self, name: str, trace_id: int, span_id: int,
                 parent_id: Optional[int], start: float, thread: int,
                 kind: str = "internal"):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.start = start
        self.end: Optional[float] = None
        self.attrs: Dict[str, Any] = {}
        self.thread = thread
        self.status = "ok"
        self.error: Optional[str] = None
        self.kind = kind

    @property
    def duration(self) -> Optional[float]:
        return None if self.end is None else self.end - self.start

    def set(self, **attrs) -> "Span":
        """Attach attributes (op, shape, dtype, nb, cache hit, ...)."""
        self.attrs.update(attrs)
        return self

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name, "trace_id": self.trace_id,
            "span_id": self.span_id, "parent_id": self.parent_id,
            "start": self.start, "end": self.end, "thread": self.thread,
            "kind": self.kind, "status": self.status, "error": self.error,
            "attrs": dict(self.attrs),
        }


class _NoopSpan:
    """Shared do-nothing span: what disabled tracing hands out (no
    allocation on the hot path). Accepts the full Span surface."""

    __slots__ = ()

    def set(self, **attrs):
        return self

    duration = None
    attrs: Dict[str, Any] = {}

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NOOP_SPAN = _NoopSpan()

_profiling = None


def profiling() -> bool:
    """True while the JAX profiler records a trace (one C++ call; the
    probe resolves lazily so importing obs never imports jax)."""
    global _profiling
    if _profiling is None:
        from jax.profiler import TraceAnnotation
        _profiling = TraceAnnotation.is_enabled
    return _profiling()


class _Annotated:
    """A span as the profiler sees it: a ``TraceAnnotation`` of the
    span's name around the block, and, with obs tracing on, the live
    span inside it (else the shared no-op span)."""

    __slots__ = ("_name", "_inner", "_annotation")

    def __init__(self, name: str, inner):
        self._name = name
        self._inner = inner
        self._annotation = None

    def __enter__(self):
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation(self._name)
        self._annotation.__enter__()
        return self._inner.__enter__()

    def __exit__(self, *exc):
        try:
            return self._inner.__exit__(*exc)
        finally:
            self._annotation.__exit__(*exc)


class _SpanCtx:
    """Context manager for one live span: enters the contextvar scope
    (so nested spans parent onto it), records the exception on exit."""

    __slots__ = ("_tracer", "_span", "_token")

    def __init__(self, tracer: "Tracer", span: Span):
        self._tracer = tracer
        self._span = span
        self._token = None

    def __enter__(self) -> Span:
        self._token = self._tracer._current.set(self._span)
        return self._span

    def __exit__(self, etype, exc, tb):
        if self._token is not None:
            self._tracer._current.reset(self._token)
        self._tracer.finish_span(self._span, error=exc)
        return False


class Tracer:
    """Thread-safe span registry with contextvar propagation.

    ``on()``/``off()`` toggle recording; ``span(name, **attrs)`` is the
    primary entry (a context manager yielding the Span); ``start_span``
    / ``finish_span`` give split lifecycle for spans that outlive one
    lexical scope (the Batcher's request spans). ``spans()`` snapshots
    the finished-span list for export.
    """

    def __init__(self, slow_threshold: Optional[float] = None,
                 max_spans: int = 65536, max_slow: int = 256):
        self.enabled = False
        self.slow_threshold = slow_threshold
        # flight-recorder hook (obs/recorder.py): finished spans feed
        # its bounded ring; None = one is-None check, nothing else
        self.recorder = None
        self._lock = threading.Lock()
        self._spans: List[Span] = []
        self._max_spans = max_spans
        self._dropped = 0
        self.slow_log: "deque[Span]" = deque(maxlen=max_slow)
        self._ids = itertools.count(1)
        self._current: "contextvars.ContextVar[Optional[Span]]" = \
            contextvars.ContextVar("slate_tpu_span", default=None)

    # -- lifecycle ---------------------------------------------------------

    def on(self, slow_threshold: Optional[float] = None):
        if slow_threshold is not None:
            self.slow_threshold = slow_threshold
        self.enabled = True
        return self

    def off(self):
        self.enabled = False
        return self

    def clear(self):
        with self._lock:
            self._spans = []
            self._dropped = 0
        self.slow_log.clear()
        return self

    # -- recording ---------------------------------------------------------

    def current(self) -> Optional[Span]:
        return self._current.get()

    @property
    def active(self) -> bool:
        """Whether ``span`` records anything: obs tracing is on or the
        JAX profiler is recording."""
        return self.enabled or profiling()

    def span(self, name: str, kind: str = "internal", **attrs):
        """Context manager; yields the live Span (or the shared no-op
        when tracing is disabled — zero allocation). While the JAX
        profiler records, the block is also a ``TraceAnnotation`` of
        ``name``."""
        if not self.enabled:
            return _Annotated(name, NOOP_SPAN) if profiling() else NOOP_SPAN
        ctx = _SpanCtx(self, self.start_span(name, kind=kind, **attrs))
        return _Annotated(name, ctx) if profiling() else ctx

    def start_span(self, name: str, parent: Optional[Span] = None,
                   kind: str = "internal", **attrs) -> Optional[Span]:
        """Open a span without entering its scope (it does NOT become
        the contextvar parent). Returns None when disabled, so callers
        can store the result unconditionally."""
        if not self.enabled:
            return None
        sid = next(self._ids)
        # a _NoopSpan parent (captured while tracing was off, e.g. the
        # Batcher's batch context before on()) has no identity — fall
        # back to the contextvar like an absent parent
        p = parent if isinstance(parent, Span) else self._current.get()
        if p is not None:
            trace_id, parent_id = p.trace_id, p.span_id
        else:
            trace_id, parent_id = sid, None
        span = Span(name, trace_id, sid, parent_id, time.perf_counter(),
                    threading.get_ident(), kind)
        if attrs:
            span.attrs.update(attrs)
        return span

    def finish_span(self, span: Optional[Span],
                    parent: Optional[Span] = None,
                    error: Optional[BaseException] = None,
                    **attrs):
        """Close a span (idempotent; no-op on None). ``parent`` re-homes
        the span into the parent's trace (the Batcher adopts queued
        request spans into the batch trace this way)."""
        if span is None or isinstance(span, _NoopSpan) or span.end is not None:
            return
        span.end = time.perf_counter()
        if attrs:
            span.attrs.update(attrs)
        if parent is not None and not isinstance(parent, _NoopSpan):
            span.parent_id = parent.span_id
            span.trace_id = parent.trace_id
        if error is not None:
            span.status = "error"
            span.error = f"{type(error).__name__}: {error}"
        dur = span.end - span.start
        # bridge to the coarse legacy views: the span model subsumes
        # utils.trace.phase (timers map + SVG timeline)
        legacy_trace.add_timer(span.name, dur)
        if legacy_trace.Trace.enabled:
            legacy_trace.Trace.record(span.name, span.start, span.end)
        with self._lock:
            if len(self._spans) < self._max_spans:
                self._spans.append(span)
            else:
                self._dropped += 1
        rec = self.recorder
        if rec is not None:
            rec.span_finished(span)
        if span.kind == "request" and self.slow_threshold is not None:
            total = float(span.attrs.get("total_s", dur))
            if total >= self.slow_threshold:
                self.slow_log.append(span)
                log.warning(
                    "slow request: %s %.3f ms (threshold %.3f ms) attrs=%s",
                    span.name, total * 1e3, self.slow_threshold * 1e3,
                    span.attrs)

    def event(self, name: str, kind: str = "event", **attrs
              ) -> Optional[Span]:
        """Record a zero-duration marker span (SLO breaches, watchdog
        anomalies): opened and finished in one call, parented on the
        current context. No-op (None, no allocation) when disabled."""
        if not self.enabled:
            return None
        span = self.start_span(name, kind=kind, **attrs)
        self.finish_span(span)
        return span

    # -- introspection -----------------------------------------------------

    def spans(self) -> List[Span]:
        """Snapshot of finished spans (recording order)."""
        with self._lock:
            return list(self._spans)

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def trace_tree(self) -> Dict[Optional[int], List[Span]]:
        """parent_id -> children map over the finished spans."""
        tree: Dict[Optional[int], List[Span]] = {}
        for s in self.spans():
            tree.setdefault(s.parent_id, []).append(s)
        return tree


# process-wide default tracer: disabled until someone opts in (the
# serving session, tools/obs_dump.py, the tester's --trace flag)
_DEFAULT = Tracer()


def default_tracer() -> Tracer:
    return _DEFAULT
