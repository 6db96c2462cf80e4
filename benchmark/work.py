"""The work a call needs, from its shapes alone, and the chip's peaks.

The FLOP formulas are those of BLAS++ ``blas::Gflop`` as the reference
tester uses them (potrf n³/3, getrf 2n³/3, a k-column solve against a
factor 2·n²·k), copied here from ``slate_tpu/obs/flops.py`` so that no
change to the program moves the yardstick. Each verb's ``cost``
(``benchmark/verbs/<verb>.py``) adds them up for one call, with the
least bytes the call moves: its operands read once, its answer written
once.
"""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def potrf_flops(n: int) -> float:
    return n ** 3 / 3.0


def getrf_flops(n: int) -> float:
    return 2.0 * n ** 3 / 3.0


def solve_flops(n: int, k: int) -> float:
    """Two triangular solves of k columns against an n×n factor."""
    return 2.0 * n * n * k


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The published peaks of ``device_kind``; a device that is not in
    the table is an error, never a default."""
    with open(path) as f:
        table = json.load(f)
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; add them with their source") from None


def roofline_s(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take and which bound sets it:
    operations over the bf16 peak, or bytes over HBM bandwidth."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes,
                                                            "memory")
