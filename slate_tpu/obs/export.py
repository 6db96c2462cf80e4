"""Chrome-trace (``trace_event``) export of the span model.

Chrome's ``chrome://tracing`` and Perfetto both ingest the JSON
``trace_event`` format (https://docs.google.com/document/d/1CvAClvFfyA5R-
PhYUmn5OOQtYMH4h6I0nSsKchNAySU): a ``traceEvents`` list of complete
("X") events with microsecond ``ts``/``dur``. We emit each span twice,
into two process groups:

* ``pid 0`` ("slate_tpu host") — one lane (``tid``) per OS thread, the
  wall-clock view of what each thread did (the reference SVG's lanes);
* ``pid 1`` ("slate_tpu phases") — one lane per phase class (span
  name), the per-phase-kind view the reference's color legend gives.

``args`` carries the span identity (trace/span/parent ids) plus all
attributes, so the span TREE survives the flat event list — and the
schema validator below checks it does (required keys, monotone ``ts``,
children nested inside their parents' intervals).
"""

from __future__ import annotations

import json
from typing import Any, Dict, Iterable, List, Optional

REQUIRED_KEYS = ("ph", "ts", "dur", "pid", "tid", "name", "args")

HOST_PID = 0
PHASE_PID = 1


def chrome_trace(spans: Iterable, t0: Optional[float] = None) -> dict:
    """Spans -> trace_event JSON object (finished spans only).

    ``ts`` is relative to ``t0`` (default: the earliest span start), in
    microseconds — Perfetto needs no epoch, only consistency."""
    done = [s for s in spans if s.end is not None]
    if not done:
        return {"traceEvents": [], "displayTimeUnit": "ms"}
    if t0 is None:
        t0 = min(s.start for s in done)
    threads = sorted({s.thread for s in done})
    tid_of = {th: i for i, th in enumerate(threads)}
    classes = sorted({s.name for s in done})
    lane_of = {c: i for i, c in enumerate(classes)}

    meta: List[dict] = [
        _meta("process_name", HOST_PID, 0, "slate_tpu host"),
        _meta("process_name", PHASE_PID, 0, "slate_tpu phases"),
    ]
    for th, i in tid_of.items():
        meta.append(_meta("thread_name", HOST_PID, i, f"thread-{th}"))
    for c, i in lane_of.items():
        meta.append(_meta("thread_name", PHASE_PID, i, c))

    events: List[dict] = []
    for s in done:
        args: Dict[str, Any] = {
            "trace_id": s.trace_id, "span_id": s.span_id,
            "parent_id": s.parent_id, "kind": s.kind, "status": s.status,
        }
        if s.error:
            args["error"] = s.error
        args.update(_jsonable(s.attrs))
        base = {
            "ph": "X", "name": s.name, "cat": s.name,
            "ts": (s.start - t0) * 1e6, "dur": (s.end - s.start) * 1e6,
            "args": args,
        }
        events.append(dict(base, pid=HOST_PID, tid=tid_of[s.thread]))
        events.append(dict(base, pid=PHASE_PID, tid=lane_of[s.name]))
    events.sort(key=lambda e: (e["ts"], -e["dur"]))
    return {"traceEvents": meta + events, "displayTimeUnit": "ms"}


def write_chrome_trace(spans: Iterable, path: str,
                       t0: Optional[float] = None) -> str:
    obj = chrome_trace(spans, t0=t0)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)
        f.write("\n")
    return path


def _meta(name: str, pid: int, tid: int, value: str) -> dict:
    return {"ph": "M", "ts": 0, "pid": pid, "tid": tid, "name": name,
            "args": {"name": value}}


def _jsonable(attrs: Dict[str, Any]) -> Dict[str, Any]:
    """Attribute values coerced to JSON-safe scalars/lists."""
    out = {}
    for k, v in attrs.items():
        if isinstance(v, (str, int, float, bool)) or v is None:
            out[k] = v
        elif isinstance(v, (tuple, list)):
            out[k] = [x if isinstance(x, (str, int, float, bool)) else str(x)
                      for x in v]
        else:
            out[k] = str(v)
    return out


# -- schema validation -------------------------------------------------------

def validate_chrome_trace(obj, slack_us: float = 1.0) -> List[str]:
    """Validate a trace_event JSON object; returns a list of problems
    (empty == valid). Checks, per the committed test contract:

    * ``traceEvents`` is a list; every "X" event carries the required
      keys ph/ts/dur/pid/tid/name/args with sane types;
    * ``ts`` is monotone non-decreasing over the "X" events;
    * span nesting: an event whose ``args.parent_id`` names another
      event in the same pid lies inside the parent's [ts, ts+dur]
      interval (within ``slack_us``) — the tree survives export.
    """
    errs: List[str] = []
    events = obj.get("traceEvents") if isinstance(obj, dict) else obj
    if not isinstance(events, list):
        return ["traceEvents: missing or not a list"]
    last_ts = None
    by_id: Dict[tuple, tuple] = {}
    xev: List[dict] = []
    for i, e in enumerate(events):
        if not isinstance(e, dict):
            errs.append(f"event {i}: not an object")
            continue
        ph = e.get("ph")
        if ph == "M":
            continue  # metadata events carry no dur
        if ph != "X":
            errs.append(f"event {i}: unexpected ph {ph!r}")
            continue
        missing = [k for k in REQUIRED_KEYS if k not in e]
        if missing:
            errs.append(f"event {i} ({e.get('name')}): missing {missing}")
            continue
        if not isinstance(e["args"], dict):
            errs.append(f"event {i} ({e['name']}): args not an object")
            continue
        ts, dur = e["ts"], e["dur"]
        if not (isinstance(ts, (int, float)) and ts >= 0):
            errs.append(f"event {i} ({e['name']}): bad ts {ts!r}")
            continue
        if not (isinstance(dur, (int, float)) and dur >= 0):
            errs.append(f"event {i} ({e['name']}): bad dur {dur!r}")
            continue
        if last_ts is not None and ts < last_ts:
            errs.append(f"event {i} ({e['name']}): ts not monotone "
                        f"({ts} after {last_ts})")
        last_ts = ts
        xev.append(e)
        sid = e["args"].get("span_id")
        if sid is not None:
            by_id[(e["pid"], sid)] = (ts, ts + dur)
    for e in xev:
        pid_ = e["args"].get("parent_id")
        if pid_ is None:
            continue
        parent = by_id.get((e["pid"], pid_))
        if parent is None:
            continue  # parent not exported (e.g. still open) — not an error
        p0, p1 = parent
        ts, t1 = e["ts"], e["ts"] + e["dur"]
        if ts < p0 - slack_us or t1 > p1 + slack_us:
            errs.append(
                f"event {e['name']} (span {e['args'].get('span_id')}): "
                f"[{ts:.1f}, {t1:.1f}] not nested in parent "
                f"[{p0:.1f}, {p1:.1f}]")
    return errs
