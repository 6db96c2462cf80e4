"""FLOP/byte ledger: ONE home for the model-GFLOP formulas.

The lawn41-convention flop models that slate_tpu/tester.py (the ~40
``register(..., flops=...)`` lambdas) and runtime/session.py
(``_factor_flops``/``_solve_flops`` feeding the serving metrics) use
are defined here once, so the numerators cannot drift, in the
reference tester's conventions (blas::Gflop as used by
test/test_*.cc; lawn41 counts).

The module also keeps a process-wide :class:`FlopLedger`: every
simplified-API driver call (api.py) credits its model flops here, so
``flops_total`` is monotone across the whole process — not just inside
a serving Session — and per-phase GFLOP/s falls out of any snapshot by
dividing against the ``utils.trace.timers`` phase map (``gflops_report``
does exactly that). Prometheus exposition (obs/exposition.py) renders
the ledger as ``slate_tpu_driver_flops_total`` counters.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, Optional

# -- canonical model formulas (lawn41 / reference-tester conventions) -------


def gemm(m: int, n: int, k: int) -> float:
    return 2.0 * m * n * k


def symm(n: int) -> float:
    return 2.0 * n ** 3


def syrk(n: int) -> float:
    return float(n) ** 3


def syr2k(n: int) -> float:
    return 2.0 * n ** 3


def rank_k(n: int, k: int) -> float:
    """n×n rank-k update (syrk/herk actual count)."""
    return float(n) * n * k


def rank_2k(n: int, k: int) -> float:
    return 2.0 * n * n * k


def tri_mm(n: int, k: int) -> float:
    """n×n triangular times n×k (trmm/trsm actual count). For
    Side.Right pass k = the OTHER operand's row count — the model is
    n²·k either way with n the triangular dimension."""
    return float(n) * n * k


def band_mm(n: int, k: int, band: int) -> float:
    """Band matrix (stored bandwidth kl+ku = ``band``) times a k-wide
    operand: each of the n columns holds ≤ band+1 entries, one mul-add
    per entry per output column — NOT dense gemm (a kd-band multiply
    executes ~n/band of the dense count)."""
    return 2.0 * (band + 1) * n * k


def trmm(m: int, n: int) -> float:
    # reference-tester sweep convention (square triangular operand)
    return float(n) ** 3


def trsm(m: int, n: int) -> float:
    return float(n) ** 3


def trtri(n: int) -> float:
    return n ** 3 / 3.0


def potrf(n: int) -> float:
    return n ** 3 / 3.0


def potri(n: int) -> float:
    return 2.0 * n ** 3 / 3.0


def getrf(n: int, m: Optional[int] = None) -> float:
    # square convention throughout the sweeps; m kept for symmetry
    return 2.0 * n ** 3 / 3.0


def getri(n: int) -> float:
    return 2.0 * n ** 3


def geqrf(m: int, n: int) -> float:
    return 2.0 * m * n * n - 2.0 * n ** 3 / 3.0


def gelqf(m: int, n: int) -> float:
    return 2.0 * m * m * n - 2.0 * m ** 3 / 3.0


def gels(m: int, n: int) -> float:
    return 2.0 * m * n * n


def hetrf(n: int) -> float:
    return n ** 3 / 3.0


def heev(n: int, vectors: bool = False) -> float:
    """values: (4/3)n³ (the he2td reduction dominates); +2n³ for the
    eigenvector back-transform."""
    return (4.0 / 3.0 + (2.0 if vectors else 0.0)) * n ** 3


def heev_2stage(n: int) -> float:
    return 9.0 * n ** 3


def svd(m: int, n: int, vectors: bool = False) -> float:
    """values: (8/3)mn² (gebrd count); +4n³ for the U and V
    back-transforms (square-vectors convention of the tester)."""
    f = 8.0 * m * n * n / 3.0
    if vectors:
        f += 4.0 * n ** 3
    return f


def band_factor(n: int, band: int) -> float:
    """band = kl+ku (or kd for Hermitian): O(n·band²)."""
    return 2.0 * n * band * band if band else 2.0 * n


# -- incremental factor maintenance (round 20) ------------------------------


def update_chol(n: int, k: int) -> float:
    """Rank-k Cholesky up/downdate of a resident n×n L (GGMS '74 /
    Davis–Hager rotation sweep): each of the k vectors touches every
    column once — one rotation build + one length-(n-j) axpy pair per
    (column, vector), ~4·Σ(n-j) ≈ 2n² per vector."""
    return 2.0 * n * n * k


def update_qr(m: int, n: int, k: int) -> float:
    """Append k rows to a resident m×n QR: the structured factorization
    of [R; U] — per column j a length-k reflector applied to the n-j
    trailing columns of (R row j, U), ~6·Σ k·(n-j) ≈ 3n²k (build +
    two-sided apply; m enters only through the base factor, kept for
    signature symmetry)."""
    return 3.0 * n * n * k


def update_flops(op: str, m: int, n: int, k: int) -> float:
    """Model flops of one rank-k/row-k incremental update against a
    resident factor, keyed by the Session op kind (chol/chol_small
    share the dense model — the batched dispatch credits B×)."""
    if op in ("chol", "chol_small"):
        return update_chol(n, k)
    if op == "qr":
        return update_qr(m, n, k)
    raise ValueError(f"update_flops: unsupported op {op!r}")


# -- spectral two-stage per-stage models (round 19) -------------------------

# heev_2stage's 9n³ total splits across the staged programs roughly as
# he2hb (4/3)n³ + chase O(n²·nb) + the two back-transform sweeps ~2n³
# each + stedc merges; the per-stage table below names the dominant
# term of EACH analyzed program so the Session's cost_log rows carry a
# defensible model numerator (the round-6 bench convention: model the
# work the program body executes, not the end-to-end headline).
SPECTRAL_STAGE_MODELS: Dict[str, Callable[[int, int, int], float]] = {
    # (m, n, nb) -> flops; square ops ignore m
    "spectral.he2hb": lambda m, n, nb: 4.0 * n ** 3 / 3.0,
    "spectral.hb2td": lambda m, n, nb: 6.0 * n * n * nb,
    "spectral.unmtr": lambda m, n, nb: 4.0 * n ** 3,
    "spectral.heev_dense": lambda m, n, nb: heev(n, vectors=True),
    "spectral.ge2tb": lambda m, n, nb: 8.0 * m * n * n / 3.0,
    "spectral.tb2bd": lambda m, n, nb: 24.0 * n * n * nb,
    "spectral.unmbr": lambda m, n, nb: 2.0 * m * n * n + 2.0 * n ** 3,
    "spectral.svd_dense": lambda m, n, nb: svd(m, n, vectors=True),
}


def spectral_stage_flops(stage: str, m: int, n: int, nb: int) -> float:
    """Model flops of one staged spectral program (0 for unknown
    stages — the census still carries measured bytes)."""
    model = SPECTRAL_STAGE_MODELS.get(stage)
    return model(m, n, nb) if model else 0.0


# -- solve / factor dispatch (the serving Session's accounting) -------------


def factor_flops(op: str, m: int, n: int, band: int = 0) -> float:
    """Model flops of one factorization, keyed by the Session op kind
    ({lu, chol, qr, band_lu, band_chol, lu_small, chol_small, eig,
    svd} — the *_small ops are one ITEM of the batched engine: same
    per-item model, credited B× by the batched dispatch; eig/svd are
    the round-19 two-stage spectral registrations)."""
    if op in ("lu", "lu_small"):
        return getrf(n)
    if op in ("chol", "chol_small"):
        return potrf(n)
    if op == "qr":
        return geqrf(m, n)
    if op == "eig":
        return heev_2stage(n)
    if op == "svd":
        return svd(m, n, vectors=True)
    return band_factor(n, band)


def solve_flops(op: str, m: int, n: int, k: int, band: int = 0) -> float:
    """Model flops of a k-column solve against a resident factor."""
    if op in ("lu", "chol", "lu_small", "chol_small"):
        return 2.0 * n * n * k
    if op == "qr":
        return (4.0 * m * n - 2.0 * n * n) * k
    if op in ("eig", "svd"):
        # served spectral apply = two gemms against the resident bases
        # (+ a diagonal scale, O(nk), below model resolution)
        return 4.0 * m * n * k
    return 4.0 * n * band * k if band else 4.0 * n * k


# -- the tester's sweep models (m, n) -> flops ------------------------------

# the reference tester parameterizes every row by (m, n); these wrap the
# canonical formulas in that signature so tester.py registers against
# ONE table instead of inline lambdas
TESTER_MODELS: Dict[str, Callable[[int, int], float]] = {
    "gemm": lambda m, n: gemm(m, m, n),
    "symm": lambda m, n: symm(n),
    "hemm": lambda m, n: symm(n),
    "syrk": lambda m, n: syrk(n),
    "herk": lambda m, n: syrk(n),
    "syr2k": lambda m, n: syr2k(n),
    "her2k": lambda m, n: syr2k(n),
    "trmm": lambda m, n: trmm(m, n),
    "trsm": lambda m, n: trsm(m, n),
    "trtri": lambda m, n: trtri(n),
    "potrf": lambda m, n: potrf(n),
    "posv": lambda m, n: potrf(n),
    "potri": lambda m, n: potri(n),
    "posv_mixed": lambda m, n: potrf(n),
    "posv_mixed_gmres": lambda m, n: potrf(n),
    "getrf": lambda m, n: getrf(n),
    "gesv": lambda m, n: getrf(n),
    "gesv_nopiv": lambda m, n: getrf(n),
    "gesv_rbt": lambda m, n: getrf(n),
    "gesv_tntpiv": lambda m, n: getrf(n),
    "gesv_mixed": lambda m, n: getrf(n),
    "gesv_mixed_gmres": lambda m, n: getrf(n),
    # round 13: the served mixed paths use the per-item factor model
    # (refinement overhead is credited separately, as serve.refine —
    # the useful-vs-refinement ledger split); the batched tester rows
    # time a FIXED B=4 stack, so their model is 4x per-item — a row's
    # GFLOP/s column must describe the work its body executes
    "gesv_mixed_batched": lambda m, n: 4.0 * getrf(n),
    "posv_mixed_batched": lambda m, n: 4.0 * potrf(n),
    "gesv_mixed_served": lambda m, n: getrf(n),
    "posv_mixed_served": lambda m, n: potrf(n),
    "getri": lambda m, n: getri(n),
    "geqrf": geqrf,
    "gelqf": gelqf,
    "cholqr": gels,
    "gels": gels,
    "heev": lambda m, n: heev(n),
    "heev_2stage": lambda m, n: heev_2stage(n),
    "heev_vec": lambda m, n: heev_2stage(n),
    "hegv": lambda m, n: heev_2stage(n),
    "svd": svd,
    "svd_vec": lambda m, n: heev_2stage(n),
    "hesv": lambda m, n: hetrf(n),
    # round 20: incremental-maintenance rows use a FIXED k=4 (same
    # discipline as the batched rows' fixed B=4 — an (m, n) sweep row
    # must name the work its body executes); the serving ledger charges
    # the EXACT rank via update_flops(op, m, n, k)
    "potrf_update": lambda m, n: update_chol(n, 4),
    "geqrf_rowadd": lambda m, n: update_qr(m, n, 4),
}


def tester_model(name: str) -> Callable[[int, int], float]:
    """(m, n) -> model flops for a tester sweep row."""
    return TESTER_MODELS[name]


# -- process-wide ledger ----------------------------------------------------


class FlopLedger:
    """Monotone model-flop accumulator, per driver op. Thread-safe and
    cheap (one lock + two float adds per driver call)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._total = 0.0
        self._per_op: Dict[str, float] = {}
        self._calls: Dict[str, int] = {}

    def record(self, op: str, flops: float):
        with self._lock:
            self._total += flops
            self._per_op[op] = self._per_op.get(op, 0.0) + flops
            self._calls[op] = self._calls.get(op, 0) + 1

    @property
    def total(self) -> float:
        with self._lock:
            return self._total

    def reset(self):
        with self._lock:
            self._total = 0.0
            self._per_op = {}
            self._calls = {}

    def snapshot(self) -> dict:
        with self._lock:
            return {"flops_total": self._total,
                    "per_op": dict(self._per_op),
                    "calls": dict(self._calls)}

    def gflops_report(self, timers: Optional[Dict[str, float]] = None
                      ) -> dict:
        """Per-op flops joined against a phase-timer map (default: the
        legacy ``utils.trace.timers``): ops whose name matches a timer
        phase (``api.<op>``) get a measured GFLOP/s column. Round 9:
        ops the bytes ledger (obs/costs.py) also knows gain
        ``bytes`` / ``collective_bytes`` / ``intensity`` (flops per
        byte) columns — the roofline join, see obs/roofline.py for the
        full report with machine roofs."""
        if timers is None:
            from ..utils.trace import timers as timers_
            timers = timers_
        from . import costs as costs_mod
        bsnap = costs_mod.BYTES.snapshot()
        snap = self.snapshot()
        report = {}
        for op, fl in snap["per_op"].items():
            secs = timers.get(f"api.{op}", 0.0) or timers.get(op, 0.0)
            row = {
                "flops": fl,
                "calls": snap["calls"][op],
                "seconds": secs,
                "gflops": fl / secs / 1e9 if secs > 0 else None,
            }
            brow = bsnap["per_op"].get(op)
            if brow is not None:
                row["bytes"] = brow["bytes"]
                row["collective_bytes"] = brow["collective_bytes"]
                row["intensity"] = (fl / brow["bytes"]
                                    if brow["bytes"] else None)
            report[op] = row
        return {"flops_total": snap["flops_total"], "per_op": report}


LEDGER = FlopLedger()
