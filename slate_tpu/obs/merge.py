"""Device-trace reading + the measured lookahead-overlap metric, and the
multi-process trace combine.

``jax.profiler`` captures device timelines; exported through the
TensorBoard profile plugin (or ``trace_event`` conversion) they arrive
as Chrome-trace JSON whose event names carry our ``jax.named_scope``
labels — the per-level ``potrf_l{k}_tile/_panel/_trail_next/_trail_rest
/_l{k+1}_tile_lookahead`` (linalg/cholesky.py) and ``geqrf_l{k}_*``
(linalg/qr.py) scopes the round-7 pipeline plants. From them
:func:`lookahead_overlap` computes the MEASURED version of the number
PERF_HISTORY.md round 7 only models: for each level k, how much of the
level-(k+1) lookahead panel's device time runs CONCURRENTLY with the
level-k remainder ("trail_rest") gemms. ``overlap_fraction`` = hidden
panel seconds / total lookahead-panel seconds: 1.0 means the panel
chain is fully hidden (the per-level floor is max(panel, trailing)),
0.0 means the schedule serialized (the floor degrades to their sum).

It works on any ``trace_event`` JSON (dict with ``traceEvents`` or a
bare list), gzipped or not — :func:`load_trace` /
:func:`find_device_traces` handle the profiler's output layout. Host
spans need no merging onto a device trace: while the JAX profiler
records, each ``Tracer.span`` is also a ``jax.profiler.TraceAnnotation``
(obs.tracing), on the profiler's own clock.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

SCOPE_RE = re.compile(r"(potrf|getrf|geqrf)_l(\d+)_([a-zA-Z0-9_]+)")

Interval = Tuple[float, float]


def load_trace(path: str):
    """Load a trace_event JSON (optionally .gz); returns the event
    list."""
    if path.endswith(".gz"):
        with gzip.open(path, "rt", encoding="utf-8") as f:
            obj = json.load(f)
    else:
        with open(path, "r", encoding="utf-8") as f:
            obj = json.load(f)
    return events_of(obj)


def events_of(obj) -> List[dict]:
    if isinstance(obj, dict):
        return obj.get("traceEvents", [])
    return list(obj)


def find_device_traces(trace_dir: str) -> List[str]:
    """Chrome-format trace files under a ``jax.profiler.trace`` output
    directory (the TensorBoard plugin writes ``*.trace.json.gz``; some
    versions only emit ``.xplane.pb``, which needs the TensorBoard
    converter first — we return [] then and the caller reports
    'no chrome-format device trace found')."""
    hits: List[str] = []
    for pat in ("**/*.trace.json.gz", "**/*.trace.json"):
        hits.extend(glob.glob(os.path.join(trace_dir, pat), recursive=True))
    return sorted(hits)


# -- interval algebra --------------------------------------------------------


def _merge_intervals(ivs: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _total(ivs: List[Interval]) -> float:
    return sum(e - s for s, e in ivs)


def _overlap(a: List[Interval], b: List[Interval]) -> float:
    """Total overlap seconds between two merged interval lists."""
    i = j = 0
    acc = 0.0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if e > s:
            acc += e - s
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return acc


def _scope_of(e: dict) -> Optional[re.Match]:
    """The named-scope match for one event, searched in the event name
    AND its string-valued args — backends differ on where the
    annotation survives (TPU xplane exports carry the scope path in
    args like ``tf_op``/``long_name``; XLA:CPU drops it entirely, in
    which case the caller honestly reports zero scoped levels)."""
    m = SCOPE_RE.search(e.get("name", ""))
    if m is not None:
        return m
    args = e.get("args")
    if isinstance(args, dict):
        for v in args.values():
            if isinstance(v, str):
                m = SCOPE_RE.search(v)
                if m is not None:
                    return m
    return None


def _scope_intervals(events: Iterable[dict], driver: str
                     ) -> Dict[Tuple[int, str], List[Interval]]:
    """(level, scope-kind) -> merged intervals (seconds) over all "X"
    events carrying a ``{driver}_l{k}_{kind}`` scope (in name or
    args)."""
    buckets: Dict[Tuple[int, str], List[Interval]] = {}
    for e in events:
        if e.get("ph") not in (None, "X"):
            continue
        dur = e.get("dur")
        ts = e.get("ts")
        if dur is None or ts is None:
            continue
        m = _scope_of(e)
        if m is None or m.group(1) != driver:
            continue
        level, kind = int(m.group(2)), m.group(3)
        buckets.setdefault((level, kind), []).append(
            (ts * 1e-6, (ts + dur) * 1e-6))
    return {k: _merge_intervals(v) for k, v in buckets.items()}


# -- the measured lookahead-overlap metric -----------------------------------

# scope kinds the lookahead pipeline factors EARLY (the work the
# schedule tries to hide) and the trailing remainder it hides them under
_LOOKAHEAD_KINDS = ("tile_lookahead", "panel_lookahead")
_REST_KIND = "trail_rest"


def lookahead_overlap(events: Iterable[dict], driver: str = "potrf") -> dict:
    """Measured lookahead overlap from a device trace (see module
    docstring). Returns per-level and aggregate numbers; all times in
    seconds. ``levels`` is empty when the trace carries no lookahead
    scopes (lookahead=0, or the backend stripped metadata)."""
    scoped = _scope_intervals(events, driver)
    levels: Dict[int, dict] = {}
    panel_s = hidden_s = 0.0
    for (level, kind), ivs in scoped.items():
        if kind not in _LOOKAHEAD_KINDS:
            continue
        rest = scoped.get((level - 1, _REST_KIND), [])
        p = _total(ivs)
        h = _overlap(ivs, rest)
        levels[level] = {
            "panel_s": p,
            "hidden_s": h,
            "hidden_fraction": h / p if p > 0 else 0.0,
        }
        panel_s += p
        hidden_s += h
    return {
        "driver": driver,
        "levels": {str(k): v for k, v in sorted(levels.items())},
        "panel_s": panel_s,
        "hidden_s": hidden_s,
        "overlap_fraction": hidden_s / panel_s if panel_s > 0 else 0.0,
    }


# -- multi-process combine (round 12: obs.aggregate's trace half) ------------

# pid namespace stride per process: every process emits pids 0 (host
# threads) and 1 (phase lanes) — see obs.export; 100 leaves room for
# any future lane class
_PROC_PID_STRIDE = 100


def combine_process_traces(traces: Iterable, labels: Optional[List[str]]
                           = None) -> dict:
    """N processes' Chrome traces -> ONE trace, keyed by trace-id.

    The reference merges per-rank Trace buffers post-hoc; this is the
    trace_event version: process i's events keep their relative
    timestamps but move into a disjoint pid namespace
    (``pid + i * 100``), every event's args gain a ``host`` label, and
    span/trace identities are prefixed with it (two processes' span-id
    counters both start at 1 — unprefixed they would alias in one
    Perfetto load). Per-process ``process_name`` metadata is rewritten
    to ``{label}:{original}`` so the lanes stay attributable."""
    out: List[dict] = []
    for i, tr in enumerate(traces):
        label = (labels[i] if labels and i < len(labels) else f"proc{i}")
        base = i * _PROC_PID_STRIDE
        for e in events_of(tr):
            e = dict(e)
            e["pid"] = int(e.get("pid", 0)) + base
            args = dict(e.get("args") or {})
            if e.get("ph") == "M":
                if e.get("name") == "process_name" and "name" in args:
                    args["name"] = f"{label}:{args['name']}"
                e["args"] = args
                out.append(e)
                continue
            for key in ("trace_id", "span_id", "parent_id"):
                if args.get(key) is not None:
                    args[key] = f"{label}/{args[key]}"
            args["host"] = label
            e["args"] = args
            out.append(e)
    # the chrome validator (and readers) expect "X" events in ts order;
    # metadata first, as obs.export emits them
    out.sort(key=lambda e: (e.get("ph") != "M", e.get("ts", 0)))
    return {"traceEvents": out, "displayTimeUnit": "ms"}
