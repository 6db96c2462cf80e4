"""Cost-model observability: the HBM/bytes and collective-traffic half.

The round-8 FLOP ledger (obs/flops.py) made model *flops* a first-class
process counter; this module does the same for the other two axes of
SLATE's performance story — **memory** and **communication** (the
reference credits its wins to tile residency and to hiding the
2D-block-cyclic communication, SURVEY §2.2/§3.5; the BASELINE pod run
is HBM- and ICI-bound, not flop-bound):

* :func:`program_costs` harvests XLA's own analyses off a compiled
  executable — ``Compiled.cost_analysis()`` (flops, bytes-accessed),
  ``Compiled.memory_analysis()`` (argument/output/temp bytes), and a
  collective census parsed from the optimized HLO text
  (``Compiled.as_text()``): one row per all-reduce / all-gather /
  reduce-scatter / collective-permute / all-to-all instruction with
  payload bytes, replica-group size, and modeled interconnect traffic.
  Every source degrades gracefully (XLA:CPU returns no temp sizes and
  sometimes no per-op breakdown): missing axes come back ``None`` and
  ``ProgramCosts.partial`` is set, never an exception on the serving
  path.

* :class:`BytesLedger` is the process-wide monotone **bytes** ledger —
  the peer of ``flops.LEDGER``. Executed programs credit bytes-accessed
  and collective traffic per *execution* (same discipline as the flop
  ledger: compile-time tracing credits nothing). Prometheus exposition
  renders it as ``slate_tpu_driver_bytes_total`` /
  ``slate_tpu_collective_bytes_total`` (obs/exposition.py); the
  roofline join (obs/roofline.py) divides the flop ledger by it.

* :func:`call_analyzed` instruments the explicitly-scheduled mesh
  drivers (parallel/summa.py, parallel/panel.py): first call per shape
  AOT-lowers the jitted driver once for analysis (cached), every call
  credits the ledger with the program's collective traffic — the
  telemetry the shard_map drivers never had.

Traffic model (per collective instruction, payload ``b`` bytes per
participant, group size ``g``): ring all-reduce moves ``2·(g−1)/g·b``
per participant; all-gather and reduce-scatter move ``(g−1)/g`` of the
gathered/scattered buffer; collective-permute and all-to-all move the
payload once. These are the standard bandwidth-optimal counts (the
reference's hypercube bcast/reduce overlays have the same asymptotics);
the census counts each HLO instruction once, EXCEPT inside ``while``
bodies whose instruction carries XLA's ``known_trip_count`` backend
config (round 10): those collectives are multiplied by the trip count,
because they execute once per iteration. A while without a trip count
(data-dependent loops) falls back to counted-once — so looped programs
report a LOWER bound exactly when XLA itself cannot bound the loop
(documented in PERF_HISTORY.md Rounds 9–10; nested whiles multiply by the
innermost counted loop only, again a lower bound).
"""

from __future__ import annotations

import dataclasses
import re
import threading
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

# NOTE: no jax import at module scope — importing slate_tpu.obs must
# stay jax-free (the round-8 rule); everything jax-touching resolves
# lazily inside functions.

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2,
    "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

# %all-reduce.3 = f32[4,2]{1,0} all-reduce(...), replica_groups={{0,1},..}
_COLLECTIVE_RE = re.compile(
    r"=\s*(?:\()?\s*([a-z0-9]+)\[([0-9,]*)\][^=]*?"
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(?:-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
# computation header: "%region_0.24 (args...) -> type {" / "ENTRY %main ..."
_COMP_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s+\([^=]*\)\s*->")
# while instr: "... while(%t), condition=%c, body=%region_0.24,
#   backend_config={"known_trip_count":{"n":"5"}}"
_WHILE_BODY_RE = re.compile(r"\bbody=%?([\w.\-]+)")
_TRIP_RE = re.compile(
    r"known_trip_count[\"']?\s*[:=]?\s*\{\s*[\"']?n[\"']?\s*[:=]?"
    r"\s*[\"']?(\d+)")
# XLA's iota form: replica_groups=[2,4]<=[8] — 2 groups of 4 (the
# common TPU spelling for sharded programs; the brace form above is
# what small CPU meshes emit)
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_PAIRS_RE = re.compile(r"source_target_pairs=\{\{")


@dataclasses.dataclass
class CollectiveCost:
    """Aggregated census of one collective kind in one program."""

    kind: str
    count: int = 0
    payload_bytes: int = 0       # per-shard payload summed over instrs
    traffic_bytes: int = 0       # modeled interconnect bytes (see model)
    group_size: int = 1          # largest replica group seen

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class ProgramCosts:
    """What XLA knows about one compiled program. ``None`` = the
    backend's analysis did not report that axis (``partial`` is set)."""

    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    argument_bytes: Optional[int] = None
    output_bytes: Optional[int] = None
    temp_bytes: Optional[int] = None
    peak_bytes: Optional[int] = None     # argument + output + temp
    collectives: Dict[str, CollectiveCost] = dataclasses.field(
        default_factory=dict)
    collective_bytes: int = 0            # total modeled traffic
    partial: bool = False

    @property
    def transient_bytes(self) -> int:
        """Execution-transient footprint beyond the program's inputs:
        temp scratch + freshly-allocated outputs. This is the number the
        Session adds on top of its cached-factor bytes when it checks
        the HBM budget (the inputs are the cached factor + the caller's
        operand, both already accounted)."""
        return int(self.temp_bytes or 0) + int(self.output_bytes or 0)

    def intensity(self) -> Optional[float]:
        """Arithmetic intensity (flops per byte accessed)."""
        if self.flops is None or not self.bytes_accessed:
            return None
        return self.flops / self.bytes_accessed

    def collective_counts(self) -> Dict[str, int]:
        """kind → instruction count (trip-count-weighted): the compact
        census summary the mesh-serving tests assert on — nonzero
        counts mean the scheduled HLO really contains collectives (the
        multichip artifact rows carry the same summary, built from the
        ``to_dict`` form in ``Session.cost_log``)."""
        return {k: c.count for k, c in self.collectives.items()}

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["collectives"] = {k: v.to_dict()
                            for k, v in self.collectives.items()}
        d["transient_bytes"] = self.transient_bytes
        d["intensity"] = self.intensity()
        return d


def collective_traffic(kind: str, payload: int, group: int) -> int:
    """Modeled interconnect bytes per participant for one collective
    (bandwidth-optimal algorithm counts — module docstring). A
    single-participant (or unparsed) group moves nothing, uniformly
    across kinds."""
    g = max(int(group), 1)
    if g <= 1:
        return 0
    if kind == "all-reduce":
        return int(2 * (g - 1) * payload / g)
    if kind in ("all-gather", "reduce-scatter"):
        return int((g - 1) * payload / g)
    # collective-permute / all-to-all: the payload crosses once
    return int(payload)


def _shape_bytes(dtype: str, dims: str) -> int:
    itemsize = _DTYPE_BYTES.get(dtype, 4)
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n * itemsize


def while_trip_counts(hlo_text: str) -> Dict[str, int]:
    """body-computation name → trip count, parsed off ``while``
    instructions whose ``backend_config`` carries XLA's
    ``known_trip_count`` estimate. Data-dependent loops (no trip count)
    are absent — their bodies fall back to counted-once."""
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        if "while(" not in line:
            continue
        bm = _WHILE_BODY_RE.search(line)
        tm = _TRIP_RE.search(line)
        if bm is not None and tm is not None:
            out[bm.group(1)] = max(int(tm.group(1)), 1)
    return out


def parse_collectives(hlo_text: str) -> Dict[str, CollectiveCost]:
    """Census of collective instructions in optimized HLO text: kind →
    aggregated (count, payload bytes, modeled traffic, group size).

    Computation-aware (round 10): a collective inside a while BODY
    whose ``while`` carries ``known_trip_count`` is credited once per
    iteration (count/payload/traffic × trip count); bodies of
    data-dependent loops keep the counted-once lower bound."""
    trips = while_trip_counts(hlo_text)
    out: Dict[str, CollectiveCost] = {}
    comp = None
    for line in hlo_text.splitlines():
        cm = _COMP_RE.match(line)
        if cm is not None and "{" in line:
            comp = cm.group(1)
            continue
        m = _COLLECTIVE_RE.search(line)
        if m is None:
            continue
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        payload = _shape_bytes(dtype, dims)
        gm = _GROUPS_RE.search(line)
        im = _IOTA_GROUPS_RE.search(line)
        if gm is not None:
            group = len([t for t in gm.group(1).split(",") if t.strip()])
        elif im is not None:
            group = int(im.group(2))  # [n_groups, group_size]<=[total]
        elif _PAIRS_RE.search(line):
            group = 2  # permute: pairwise exchange
        else:
            group = 1
        mult = trips.get(comp, 1)
        cc = out.setdefault(kind, CollectiveCost(kind))
        cc.count += mult
        cc.payload_bytes += payload * mult
        cc.traffic_bytes += collective_traffic(kind, payload, group) * mult
        cc.group_size = max(cc.group_size, group)
    return out


def program_costs(compiled) -> ProgramCosts:
    """Harvest every analysis the backend offers off a jax ``Compiled``
    (``jit(f).lower(...).compile()``). Never raises: axes the backend
    cannot analyze come back ``None`` with ``partial=True``."""
    pc = ProgramCosts()
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        pc.flops = float(ca["flops"]) if "flops" in ca else None
        pc.bytes_accessed = (float(ca["bytes accessed"])
                             if "bytes accessed" in ca else None)
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        pc.argument_bytes = int(getattr(ma, "argument_size_in_bytes"))
        pc.output_bytes = int(getattr(ma, "output_size_in_bytes"))
        pc.temp_bytes = int(getattr(ma, "temp_size_in_bytes"))
        pc.peak_bytes = (pc.argument_bytes + pc.output_bytes
                         + pc.temp_bytes)
    except Exception:
        pass
    try:
        text = compiled.as_text()
        if text:
            pc.collectives = parse_collectives(text)
            pc.collective_bytes = sum(c.traffic_bytes
                                      for c in pc.collectives.values())
    except Exception:
        pass
    pc.partial = (pc.flops is None or pc.bytes_accessed is None
                  or pc.temp_bytes is None)
    return pc


def score_measured(model_flops: Optional[float], seconds: float,
                   bytes_accessed: Optional[float] = None,
                   machine=None) -> dict:
    """Join ONE measured slope-timed row with the program's
    compile-time cost analysis (the round-21 autotune scorer): always
    the measured GFLOP/s against the model-flop numerator; the
    arithmetic intensity when the backend reported bytes-accessed; and
    the roofline fraction/bound whenever a MachineModel is configured
    (``machine=`` or the SLATE_TPU_PEAK_GFLOPS/HBM_GBPS env — the
    round-9 roofline substrate, reused verbatim). CPU-smoke rows
    typically score gflops-only (XLA:CPU reports no byte analysis and
    no machine model is set) — honest degradation, the bench_gate
    platform policy."""
    from .roofline import MachineModel, roofline_row
    if machine is None:
        machine = MachineModel.from_env()
    row = roofline_row("tuning.candidate", model_flops, bytes_accessed,
                       seconds=seconds, machine=machine)
    return {k: row[k] for k in ("gflops", "gbps", "intensity", "bound",
                                "attainable_gflops", "roof_fraction")}


# -- process-wide bytes ledger ----------------------------------------------


class BytesLedger:
    """Monotone bytes accumulator per op — the memory/communication peer
    of :class:`flops.FlopLedger`. ``record`` credits one program
    *execution*; collective traffic is additionally broken out per
    collective kind (the fleet alarm is on ICI bytes, not op names)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._bytes_total = 0.0
        self._collective_total = 0.0
        self._per_op: Dict[str, Dict[str, float]] = {}
        self._per_kind: Dict[str, Dict[str, float]] = {}

    def record(self, op: str, bytes_accessed: float = 0.0,
               collective_bytes: float = 0.0,
               collectives: Optional[Dict[str, CollectiveCost]] = None):
        with self._lock:
            self._bytes_total += bytes_accessed
            self._collective_total += collective_bytes
            row = self._per_op.setdefault(
                op, {"bytes": 0.0, "collective_bytes": 0.0, "calls": 0})
            row["bytes"] += bytes_accessed
            row["collective_bytes"] += collective_bytes
            row["calls"] += 1
            for kind, cc in (collectives or {}).items():
                kr = self._per_kind.setdefault(
                    kind, {"bytes": 0.0, "count": 0})
                kr["bytes"] += cc.traffic_bytes
                kr["count"] += cc.count

    def record_costs(self, op: str, pc: ProgramCosts):
        """Credit one execution of an analyzed program."""
        self.record(op, pc.bytes_accessed or 0.0, pc.collective_bytes,
                    pc.collectives)

    @property
    def total(self) -> float:
        with self._lock:
            return self._bytes_total

    def reset(self):
        with self._lock:
            self._bytes_total = 0.0
            self._collective_total = 0.0
            self._per_op = {}
            self._per_kind = {}

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "bytes_total": self._bytes_total,
                "collective_bytes_total": self._collective_total,
                "per_op": {k: dict(v) for k, v in self._per_op.items()},
                "per_collective": {k: dict(v)
                                   for k, v in self._per_kind.items()},
            }


BYTES = BytesLedger()


# -- mesh-driver instrumentation --------------------------------------------

# per-(label, shapes) analysis cache: the mesh drivers rebuild their
# shard_map closure every call, so the memo key is structural
_ANALYSIS_LOCK = threading.Lock()
_ANALYSIS: "OrderedDict[Tuple, Tuple[Any, ProgramCosts]]" = OrderedDict()
_ANALYSIS_CAP = 64


def _arg_key(args) -> Tuple:
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (treedef,
            tuple((tuple(l.shape), str(getattr(l, "dtype", type(l))))
                  for l in leaves))


def call_analyzed(fn, args: Tuple, label: str,
                  ledger: Optional[BytesLedger] = None):
    """Run ``fn(*args)`` with cost telemetry: the first call per
    (label, arg-structure) AOT-compiles the program once for
    :func:`program_costs` (analysis cached) and executes through that
    same compiled program; later calls run ``fn`` exactly as the
    uninstrumented driver did (the mesh drivers rebuild their closure
    — alpha/beta and grid baked in — every call, so a compiled
    executable cannot be reused across calls) and EVERY call credits
    the bytes ledger — collective traffic included — under ``label``.

    Under an active jax trace (the driver is being composed into a
    larger jitted program) this degrades to a plain call: analysis and
    crediting belong to whoever compiles the outer program. Any
    analysis failure also degrades to the plain call — the telemetry
    must never take down the math."""
    from . import _jax_eager

    if not _jax_eager():
        return fn(*args)
    import jax

    key = (label,) + _arg_key(args)
    with _ANALYSIS_LOCK:
        hit = _ANALYSIS.get(key)
        if hit is not None:
            _ANALYSIS.move_to_end(key)
    led = ledger if ledger is not None else BYTES
    if hit is not None:
        led.record_costs(label, hit[1])
        return fn(*args)
    exe = None
    try:
        exe = jax.jit(fn).lower(*args).compile()
        pc = program_costs(exe)
    except Exception:
        exe, pc = None, ProgramCosts(partial=True)
    with _ANALYSIS_LOCK:
        _ANALYSIS[key] = (label, pc)
        while len(_ANALYSIS) > _ANALYSIS_CAP:
            _ANALYSIS.popitem(last=False)
    led.record_costs(label, pc)
    # the analysis compile serves this call's execution too — no
    # second trace+compile of the same program
    return exe(*args) if exe is not None else fn(*args)


def analyzed_costs(label: str) -> Dict[Tuple, ProgramCosts]:
    """Cached analyses recorded under ``label`` (for dumps/tests)."""
    with _ANALYSIS_LOCK:
        return {k: v[1] for k, v in _ANALYSIS.items() if k[0] == label}
