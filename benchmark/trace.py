"""Profiler trace of a run's window, reduced to the numbers the
per-layer readers take.

The JAX profiler writes one ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. On a TPU the device plane (``/device:TPU:<i>``) carries:

- ``XLA Modules``: one event per execution of a compiled program, named
  ``<module>(<fingerprint>)``;
- ``XLA Ops``: one event per HLO instruction executed, named by the
  instruction's text (``%fusion.12 = f32[...] fusion(...), ...``). A
  ``while`` event spans the events of its body, so the events nest:
  each event's self time is its duration less its children's.

Device events carry no scope path. ``add_program`` takes a compiled
program's HLO text, whose instructions carry ``op_name`` metadata (the
``jax.named_scope`` path), and the reduction joins each event to it by
the instruction's name. The harness's own host spans
(``jax.profiler.TraceAnnotation``: ``bench.window``, ``bench.call``,
``bench.submit``, ``bench.wait``) name what the host was doing in each
idle gap of the device.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import shutil
import tempfile

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_EVENT_NAME = re.compile(r"^%?([\w.\-]+)")
_MODULE = re.compile(r"^(.*?)(?:\(\d+\))?$")
WINDOW = "bench.window"
TOP = 10  # entries in each list of the breakdown
MIN_GAP_S = 1e-6  # idle gaps shorter than this are not listed


def hlo_index(text: str) -> tuple[str, dict, set]:
    """(module name, instruction name -> op_name, names of the Mosaic
    custom calls) of one compiled program's HLO text."""
    module = re.search(r"HloModule\s+([\w.\-]+)", text).group(1)
    names, custom = {}, set()
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        names[m.group(1)] = op.group(1) if op else ""
        if 'custom_call_target="tpu_custom_call"' in line:
            custom.add(m.group(1))
    return module, names, custom


def module_name(event_name: str) -> str:
    """``jit_posv(1541...)`` -> ``jit_posv``."""
    return _MODULE.match(event_name).group(1)


def scope_of(op_name: str) -> str:
    """The named-scope path of an op_name: the ``jit(...)`` frames and
    the primitive at the end left out."""
    parts = [p for p in op_name.split("/")[:-1]
             if not p.startswith("jit(") and not p.startswith("vmap(")]
    return "/".join(parts)


def union(intervals) -> list[tuple[float, float]]:
    """Merged (start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def self_times(events) -> list[float]:
    """Self time of each (start, duration) event, where an event that
    starts inside another and ends by its end is its child."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    self_t = [float(d) for _, d in events]
    stack = []  # indices of the open ancestors
    for i in order:
        s, d = events[i]
        while stack and events[stack[-1]][0] + events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            p = stack[-1]
            self_t[p] -= min(d, events[p][0] + events[p][1] - s)
        stack.append(i)
    return self_t


@dataclasses.dataclass
class Reduced:
    window_s: float  # length of the traced window (host span)
    busy_s: float  # union of device op intervals inside it
    modules: dict  # program name -> durations (s) of its executions
    scope_s: dict  # named-scope path -> device self time (s)
    custom_s: float  # device self time of the Mosaic custom calls
    ops_s: dict  # op_name with digits as '#', or the instruction kind
    gaps: list  # (seconds, what the host was doing), longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        top = sorted(self.ops_s.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[label, s] for s, label in self.gaps[:TOP]]}


def reduce_xspace(pd, programs: dict, device: int = 0) -> Reduced:
    """Reduce a ``ProfileData`` to a ``Reduced``. ``programs`` maps a
    module name to ``hlo_index``'s (names, custom) of that program."""
    planes = {p.name: p for p in pd.planes}
    host = planes["/host:CPU"]
    spans = []  # host spans: (start, end, name)
    window = None
    for line in host.lines:
        for e in line.events:
            if e.name == WINDOW:
                window = (e.start_ns, e.start_ns + e.duration_ns)
            spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name,
                          line.name))
    if window is None:
        raise ValueError(f"no {WINDOW} span in the trace")
    w0, w1 = window
    dev = planes[f"/device:TPU:{device}"]
    lines = {ln.name: ln for ln in dev.lines}
    modules = {}
    mod_spans = []
    for e in lines["XLA Modules"].events:
        s, t = e.start_ns, e.start_ns + e.duration_ns
        if t < w0 or s > w1:
            continue
        name = module_name(e.name)
        modules.setdefault(name, []).append(e.duration_ns * 1e-9)
        mod_spans.append((s, t, name))
    mod_spans.sort()
    raw = [(e.start_ns, e.duration_ns, e.name)
           for e in lines["XLA Ops"].events
           if e.start_ns + e.duration_ns >= w0 and e.start_ns <= w1]
    selfs = self_times([(s, d) for s, d, _ in raw])
    scope_s, ops_s, custom_s = {}, {}, 0.0
    mi = 0
    for (s, d, text), st in sorted(zip(raw, selfs)):
        while mi + 1 < len(mod_spans) and mod_spans[mi][1] < s:
            mi += 1
        prog = programs.get(mod_spans[mi][2]) if mod_spans else None
        instr = _EVENT_NAME.match(text).group(1)
        sec = st * 1e-9
        op = prog[0].get(instr) if prog else None
        if op is not None:  # an instruction of a program the run added
            key = scope_of(op)
            scope_s[key] = scope_s.get(key, 0.0) + sec
        group = (re.sub(r"\d+", "#", op) if op
                 else re.sub(r"[.\d]+$", "", instr))
        ops_s[group] = ops_s.get(group, 0.0) + sec
        if prog and instr in prog[1]:
            custom_s += sec
    busy = union((max(s, w0), min(s + d, w1)) for s, d, _ in raw)
    busy_s = sum(e - s for s, e in busy) * 1e-9
    gaps = []
    prev = w0
    for s, e in busy + [(w1, w1)]:
        if s - prev >= MIN_GAP_S * 1e9:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    gaps = [((e - s) * 1e-9, _host_label(spans, s, e))
            for s, e in gaps[:TOP]]
    return Reduced(window_s=(w1 - w0) * 1e-9, busy_s=busy_s,
                   modules=modules, scope_s=scope_s, custom_s=custom_s,
                   ops_s=ops_s, gaps=gaps)


def _host_label(spans, s, e) -> str:
    """What the host was doing in the gap [s, e]: the innermost of the
    harness's spans, and the innermost other host span, that cover its
    middle."""
    mid = 0.5 * (s + e)
    bench, other = None, None
    for a, b, name, _ in spans:
        if a <= mid <= b and name != WINDOW:
            if name.startswith("bench."):
                if bench is None or b - a < bench[1] - bench[0]:
                    bench = (a, b, name)
            elif other is None or b - a < other[1] - other[0]:
                other = (a, b, name)
    label = bench[2] if bench else "idle host"
    return f"{label}/{other[2]}" if other else label


class Tracer:
    """Traces one window of a run with the JAX profiler, into a fresh
    directory under ``TMPDIR`` that ``close`` removes."""

    enabled = True

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.programs = {}

    def add_program(self, hlo_text: str):
        module, names, custom = hlo_index(hlo_text)
        self.programs[module] = (names, custom)

    @contextlib.contextmanager
    def window(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW):
                yield
        finally:
            jax.profiler.stop_trace()

    def annotate(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def reduce(self) -> Reduced:
        from jax.profiler import ProfileData

        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise RuntimeError(f"expected one trace in {self.dir}, found "
                               f"{found}")
        return reduce_xspace(ProfileData.from_file(found[0]),
                             self.programs)

    def close(self):
        shutil.rmtree(self.dir, ignore_errors=True)


class _NoTrace:
    enabled = False

    def add_program(self, hlo_text: str):
        pass

    def window(self):
        return contextlib.nullcontext()

    def annotate(self, name: str):
        return contextlib.nullcontext()

    def reduce(self):
        return None

    def close(self):
        pass


NO_TRACE = _NoTrace()
