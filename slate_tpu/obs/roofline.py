"""Roofline/arithmetic-intensity reporting: flops ÷ bytes, per op.

Joins the round-8 FLOP ledger (obs/flops.py — model flops per driver
verb) with the round-9 bytes ledger (obs/costs.py — XLA bytes-accessed
and collective traffic per executed program) into the rows a roofline
analysis needs: arithmetic intensity (flops/byte), measured GFLOP/s and
GB/s (joined against the phase-timer map like ``gflops_report``), and —
when a machine model is known — which roof bounds the op and the
attainable rate.

The machine model is explicit, never guessed: pass a
:class:`MachineModel` or set ``SLATE_TPU_PEAK_GFLOPS`` /
``SLATE_TPU_HBM_GBPS`` in the environment (per-chip numbers; for the
BASELINE pod run the ICI roof matters too — ``ici_gbps``). Without one,
rows still carry intensity and measured rates; the bound/attainable
columns are ``None`` (an honest roofline needs a measured roof, PERF_HISTORY.md
Round 9).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional

from . import costs as costs_mod
from . import flops as flops_mod


@dataclasses.dataclass
class MachineModel:
    """Per-chip roofs (GFLOP/s, GB/s). ``ridge`` = flops/byte at which
    the compute roof takes over from the HBM roof."""

    peak_gflops: float
    hbm_gbps: float
    ici_gbps: Optional[float] = None
    name: str = "custom"

    @property
    def ridge(self) -> float:
        return self.peak_gflops / self.hbm_gbps

    def attainable_gflops(self, intensity: float) -> float:
        """min(compute roof, intensity × bandwidth roof)."""
        return min(self.peak_gflops, intensity * self.hbm_gbps)

    @classmethod
    def from_env(cls) -> Optional["MachineModel"]:
        peak = os.environ.get("SLATE_TPU_PEAK_GFLOPS")
        bw = os.environ.get("SLATE_TPU_HBM_GBPS")
        if not peak or not bw:
            return None
        ici = os.environ.get("SLATE_TPU_ICI_GBPS")
        return cls(float(peak), float(bw),
                   float(ici) if ici else None, name="env")


def intensity(flops: Optional[float],
              bytes_: Optional[float]) -> Optional[float]:
    """Arithmetic intensity; None when either axis is unknown."""
    if flops is None or not bytes_:
        return None
    return flops / bytes_


def roofline_row(op: str, flops: Optional[float], bytes_: Optional[float],
                 seconds: float = 0.0,
                 collective_bytes: Optional[float] = None,
                 machine: Optional[MachineModel] = None) -> dict:
    """One roofline row. ``seconds`` > 0 adds measured GFLOP/s + GB/s;
    a machine model adds the bound ("memory"/"compute") and the
    attainable rate the measurement should be compared against."""
    ai = intensity(flops, bytes_)
    row = {
        "op": op,
        "flops": flops,
        "bytes": bytes_,
        "collective_bytes": collective_bytes,
        "intensity": ai,
        "seconds": seconds or None,
        "gflops": (flops / seconds / 1e9
                   if flops is not None and seconds > 0 else None),
        "gbps": (bytes_ / seconds / 1e9
                 if bytes_ and seconds > 0 else None),
        "bound": None,
        "attainable_gflops": None,
        "roof_fraction": None,
    }
    if machine is not None and ai is not None:
        row["bound"] = "memory" if ai < machine.ridge else "compute"
        row["attainable_gflops"] = machine.attainable_gflops(ai)
        if row["gflops"] is not None and row["attainable_gflops"]:
            row["roof_fraction"] = row["gflops"] / row["attainable_gflops"]
    return row


def roofline_report(ledger: Optional[flops_mod.FlopLedger] = None,
                    bytes_ledger: Optional[costs_mod.BytesLedger] = None,
                    timers: Optional[Dict[str, float]] = None,
                    machine: Optional[MachineModel] = None) -> dict:
    """Join the process flop + bytes ledgers (default) against the
    phase-timer map: one roofline row per op that BOTH ledgers know
    (the served verbs — serve.factor/serve.solve — and any analyzed
    mesh driver), plus flop-only rows for ops with no byte telemetry
    (the eager verbs XLA never analyzed), flagged ``bytes: None``."""
    ledger = ledger if ledger is not None else flops_mod.LEDGER
    bytes_ledger = (bytes_ledger if bytes_ledger is not None
                    else costs_mod.BYTES)
    if timers is None:
        from ..utils.trace import timers as timers_
        timers = dict(timers_)
    if machine is None:
        machine = MachineModel.from_env()
    fsnap = ledger.snapshot()
    bsnap = bytes_ledger.snapshot()
    rows = []
    ops = sorted(set(fsnap["per_op"]) | set(bsnap["per_op"]))
    for op in ops:
        fl = fsnap["per_op"].get(op)
        brow = bsnap["per_op"].get(op)
        secs = timers.get(f"api.{op}", 0.0) or timers.get(op, 0.0)
        rows.append(roofline_row(
            op, fl, brow["bytes"] if brow else None, secs,
            brow["collective_bytes"] if brow else None, machine))
    return {
        "machine": dataclasses.asdict(machine) if machine else None,
        "flops_total": fsnap["flops_total"],
        "bytes_total": bsnap["bytes_total"],
        "collective_bytes_total": bsnap["collective_bytes_total"],
        "rows": rows,
    }
