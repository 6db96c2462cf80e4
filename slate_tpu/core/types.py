"""Enums and option types for slate-tpu.

TPU-native re-design of the reference's enum/option vocabulary
(reference: include/slate/enums.hh, include/slate/types.hh). We keep the
same *semantic* vocabulary (Uplo/Op/Diag/Side/Norm, per-routine Method
enums, an Options bag) but express it as plain Python enums/dataclasses:
there is no Target::{HostTask,HostNest,HostBatch,Devices} dispatch here —
XLA owns scheduling, so the "target" axis collapses to how a routine is
jitted/sharded (see slate_tpu.parallel).
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Optional


class Uplo(enum.Enum):
    """Which triangle of a matrix is stored/referenced.

    Reference: include/slate/enums.hh (blas::Uplo re-export).
    """

    General = "g"
    Lower = "l"
    Upper = "u"

    def flipped(self) -> "Uplo":
        if self is Uplo.Lower:
            return Uplo.Upper
        if self is Uplo.Upper:
            return Uplo.Lower
        return self


class Op(enum.Enum):
    """Transposition view state (zero-copy in the reference; metadata here).

    Reference: BaseMatrix::op_ (include/slate/BaseMatrix.hh:783-786) and the
    transpose/conj_transpose free functions (BaseMatrix.hh:140-148).
    """

    NoTrans = "n"
    Trans = "t"
    ConjTrans = "c"


class Diag(enum.Enum):
    NonUnit = "n"
    Unit = "u"


class Side(enum.Enum):
    Left = "l"
    Right = "r"


class Norm(enum.Enum):
    """Matrix norm kind. Reference: include/slate/enums.hh (lapack::Norm)."""

    One = "1"
    Two = "2"
    Inf = "i"
    Fro = "f"
    Max = "m"


class NormScope(enum.Enum):
    """Reference: enums.hh:514 (NormScope{Columns,Rows,Matrix})."""

    Matrix = "m"
    Columns = "c"
    Rows = "r"


class Direction(enum.Enum):
    Forward = "f"
    Backward = "b"


class Layout(enum.Enum):
    """Kept for API parity; on TPU all storage is row-major jax.Arrays and
    layout conversion (reference BaseMatrix.hh:551-603) is a no-op/XLA detail.
    """

    ColMajor = "c"
    RowMajor = "r"


class GridOrder(enum.Enum):
    """2D process-grid ordering. Reference: enums.hh:524 GridOrder."""

    Col = "c"
    Row = "r"


class MatrixKind(enum.Enum):
    """Which matrix-kind a TiledMatrix represents.

    The reference uses a subclass per kind (Matrix, TrapezoidMatrix,
    TriangularMatrix, SymmetricMatrix, HermitianMatrix, BandMatrix,
    TriangularBandMatrix, HermitianBandMatrix — one header each in
    include/slate/). Here kinds are a metadata field on one pytree class;
    thin constructor aliases live in slate_tpu.core.tiled_matrix.
    """

    General = "ge"
    Trapezoid = "tz"
    Triangular = "tr"
    Symmetric = "sy"
    Hermitian = "he"
    Band = "gb"
    TriangularBand = "tb"
    HermitianBand = "hb"


# ---------------------------------------------------------------------------
# Per-routine algorithm-variant enums ("Methods").
# Reference: include/slate/enums.hh:61-455 and §2.3/P10 of SURVEY.md.
# ---------------------------------------------------------------------------


class MethodGemm(enum.Enum):
    Auto = "auto"
    A = "A"  # stationary-A: partial products where A lives, then reduce
    C = "C"  # stationary-C: broadcast A column / B row panels (SUMMA)
    # explicit hand-scheduled SUMMA via shard_map + ring broadcasts
    # (parallel/summa.gemm_summa) instead of GSPMD constraint inference
    SUMMA = "summa"


class MethodTrsm(enum.Enum):
    Auto = "auto"
    A = "A"
    B = "B"


class MethodHemm(enum.Enum):
    Auto = "auto"
    A = "A"
    C = "C"


class MethodLU(enum.Enum):
    """Reference: enums.hh:302 MethodLU; dispatch in src/getrf.cc:324-353
    (PartialPiv/CALU/NoPiv wired; RBT via gesv_rbt entry point)."""

    Auto = "auto"
    PartialPiv = "ppiv"
    CALU = "calu"
    NoPiv = "nopiv"
    RBT = "rbt"


class MethodGels(enum.Enum):
    Auto = "auto"
    QR = "qr"
    CholQR = "cholqr"


class MethodHesv(enum.Enum):
    """Hermitian-indefinite factorization variant (the reference ships
    pivoted Aasen, src/hetrf.cc; RBT is our no-pivot LDLᴴ trade)."""

    Auto = "auto"      # = Aasen (pivoted — deterministic stability)
    Aasen = "aasen"    # LTLᴴ with symmetric partial pivoting
    RBT = "rbt"        # symmetric butterfly + no-pivot LDLᴴ + IR


class MethodEig(enum.Enum):
    Auto = "auto"
    QR = "qr"  # steqr QR iteration
    DC = "dc"  # divide & conquer


class MethodSVD(enum.Enum):
    Auto = "auto"
    QR = "qr"
    DC = "dc"


class TileReleaseStrategy(enum.Enum):
    """Kept for API parity only: workspace life-cycle is XLA's job on TPU."""

    None_ = "none"
    Internal = "internal"
    Slate = "slate"
    All = "all"


# ---------------------------------------------------------------------------
# Options
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Options:
    """Per-call options bag.

    Reference: slate::Options = std::map<Option, OptionValue>
    (include/slate/types.hh:24-80, keys at enums.hh:461-498). We use a typed
    frozen dataclass; every driver takes ``opts: Options = Options()`` as its
    last argument, mirroring the reference call convention.

    Fields that only make sense under MPI/OpenMP (MaxPanelThreads, Target,
    HoldLocalWorkspace, TileReleaseStrategy) are kept as inert parity fields.
    """

    # Depth of the factorization pipeline (the reference's
    # Option::Lookahead, enums.hh:461-498; functional since round 7).
    # ≥ 1: the iterative outer loops of potrf/getrf/geqrf split each
    # trailing update at the next-panel slab and factor panel k+1
    # between the slab and the remainder, so the serial panel chain of
    # step k+1 carries no data edge to step k's remainder gemms and the
    # scheduler may interleave them (lookahead-1 — PLASMA/DPLASMA
    # lineage puts most of the win there). 0 = the strictly sequential
    # round-6 schedule (bit-identical results; the reference arm for
    # tests and A/B timing). Depths > 1 CLAMP to 1 with a one-time
    # warning at the driver consumption seam (normalize_lookahead,
    # below): the pipeline implements depth 1, and round 21's
    # autotuner must not search a dimension that is a no-op — the
    # clamp (and its bit-identity to depth 1) is pinned in
    # tests/test_tuning.py.
    lookahead: int = 1
    block_size: int = 256  # nb — tile size
    inner_blocking: int = 32  # ib — panel inner blocking
    max_panel_threads: int = 1  # parity only
    tolerance: Optional[float] = None
    max_iterations: int = 30
    use_fallback_solver: bool = True
    pivot_threshold: float = 1.0
    depth: int = 2  # RBT butterfly depth
    # Matmul precision for the large trailing-update gemms of the
    # factorization drivers. On TPU "high" = bf16x3 passes (≈ f32-accurate,
    # 2× the "highest" rate, measured 60.7 vs 30.7 TFLOP/s on v5e); panel
    # and reflector math always runs at "highest" (core/precision.py).
    # No analog in the reference (cuBLAS runs native fp64); closest is
    # the gemm-autotuning Target/Method machinery.
    update_precision: str = "high"
    # Method selection (P10):
    method_gemm: MethodGemm = MethodGemm.Auto
    method_trsm: MethodTrsm = MethodTrsm.Auto
    method_hemm: MethodHemm = MethodHemm.Auto
    method_lu: MethodLU = MethodLU.Auto
    # explicit shard_map panel factorization for getrf: per-column
    # maxloc pivot collective + masked-psum row swaps over the grid row
    # axis (parallel/panel.py — the hand-scheduled counterpart of the
    # GSPMD-inferred panel; reference Tile_getrf.hh:209-270)
    lu_dist_panel: bool = False
    # Round-6 fast paths (PERF_HISTORY.md "Round 6"). lu_pivot_fusion: fold the
    # per-level row permutation into the trailing-update gemm READS
    # (gather-as-you-read + deferred left swaps) instead of
    # materializing a full-width permuted copy per level — the
    # TPU-native analog of the reference's device-batched swaps
    # (internal_swap.cc:503-560). False restores the materialized-copy
    # reference path (bit-identical results; kept for A/B + tests).
    lu_pivot_fusion: bool = True
    # Round 7: CALU tournament rounds as ONE batched panel LU per round
    # (blocked.panel_getrf_batched) instead of vmap(lax.linalg.lu)'s
    # sequential per-block custom-call loop. False restores the
    # lax.linalg.lu rounds (A/B timing + dispatch-policy reference;
    # winner selection may differ between arms — both valid tournament
    # pivotings).
    lu_tournament_batched: bool = True
    # factor_iter_large: run the right-looking iterative outer loop with
    # in-place (dynamic_update_slice) trailing updates at ALL sizes with
    # nt ≤ 64 for potrf/getrf — the round-5 n=2048 crossover was set by
    # the loop's concatenation/permute-copy traffic, which the in-place
    # slab updates and pivot fusion remove. False restores the 2×2
    # recursion dispatch above the old crossover.
    factor_iter_large: bool = True
    method_gels: MethodGels = MethodGels.Auto
    method_hesv: MethodHesv = MethodHesv.Auto
    method_eig: MethodEig = MethodEig.Auto
    # stage-1 reduction strategy for the DC eigensolver path:
    # "he2td" = direct blocked tridiagonalization (one stage, half the
    # flops in sequential full-matrix matvecs); "two_stage" = he2hb
    # band reduction (all-gemm) + hb2td bulge chase on O(n·nb) data
    # (the reference's he2hb+hb2st split, src/he2hb.cc + src/hb2st.cc);
    # "auto" picks per backend/size (see eig._heev_td and PERF_HISTORY.md)
    eig_stage1: str = "auto"
    method_svd: MethodSVD = MethodSVD.Auto
    # printing (reference enums.hh:477-487)
    print_verbose: int = 4
    print_edgeitems: int = 16
    print_width: int = 10
    print_precision: int = 4

    def replace(self, **kw) -> "Options":
        return dataclasses.replace(self, **kw)


DEFAULT_OPTIONS = Options()

# one-time-warning latch for normalize_lookahead (process-wide: the
# point is not to spam a serving log once per solve)
_LOOKAHEAD_WARNED = False


def normalize_lookahead(depth: int) -> int:
    """The effective pipeline depth for a requested ``lookahead``.

    The round-7 pipeline implements depths 0 and 1; deeper requests
    used to be silently scheduled as depth 1 (the old ``Options``
    comment admitted it). Round 21 makes that contract explicit —
    the autotuner must not search a dimension that is a no-op:
    negative depths are rejected, depths > 1 CLAMP to 1 with a
    one-time warning, and the clamped schedule is bit-identical to an
    explicit depth-1 run (pinned in tests/test_tuning.py). Called at
    the driver consumption seams (cholesky/lu/qr), so every entry
    point — Options, tuning tables, direct kwargs — shares one rule.
    """
    global _LOOKAHEAD_WARNED
    depth = int(depth)
    if depth < 0:
        raise ValueError(f"Options.lookahead must be >= 0, got {depth}")
    if depth > 1:
        if not _LOOKAHEAD_WARNED:
            _LOOKAHEAD_WARNED = True
            import warnings
            warnings.warn(
                f"Options.lookahead={depth} clamps to 1: the "
                "factorization pipeline implements lookahead-1 "
                "(PLASMA/DPLASMA lineage puts most of the win there); "
                "deeper depths schedule identically. This warning is "
                "emitted once per process.", stacklevel=2)
        return 1
    return depth
