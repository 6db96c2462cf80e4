"""Batched small-problem drivers: many [B, n, n] systems, ONE program.

Production traffic is overwhelmingly *many small systems*, not one
giant one — the reference's answer is the HostBatch/Devices batched-
gemm target class (PAPER.md L3) and the batched one-sided
factorizations of Haidar et al. (IJHPCA 2015). This module is the
driver layer over the hand-batched blocked kernels in ops/blocked.py
(potrf_batched / getrf_batched / geqrf_batched and the batched
triangular solves) — which are never ``vmap`` of per-item custom calls
(backends execute those as a sequential per-item loop; the round-7
CALU measurement was 6× slower with ~40 s more compile).

**Pow2 batch-bucket compilation.** Every entry point pads the batch
dim to the next power of two and runs through a per-bucket compiled
program cache: one ``jit(...).lower(...).compile()`` per
(op, B-bucket, n, nb, dtype), so a serving fleet handling arbitrary
batch sizes compiles ≤ log2(B_max) programs per operator class
instead of one per batch size. Padding items are identities (LU/QR) —
they factor cleanly, flag no info, and cannot perturb their neighbors
because every kernel's arithmetic is batch-independent; results are
therefore BIT-IDENTICAL across paddings of the same bucket for every
dtype, and across different buckets (a B=1 per-request run vs a B=100
batched one) for real dtypes. Complex is the one caveat: XLA:CPU
FMA-contracts the real mul/add pairs inside fused complex arithmetic
differently at different batch shapes (a single complex multiply
reproduces it), so c64 lanes agree across buckets only to a few ulp
on the CPU backend — exact within a bucket, and not a TPU property
(complex matmuls lower to real MXU pairs there). All pinned in
tests/test_batched.py; PERF_HISTORY.md Round 10 documents the caveat.

Per-item ``info`` vectors follow the LAPACK convention (0 = ok,
k > 0 = first failing column/minor); one singular item flags itself
and leaves its neighbors' bits untouched.

Observability: each compiled bucket program is cost-analyzed at the
compile seam (obs/costs.program_costs) and every execution credits the
process BYTES ledger under the driver name — the round-9 per-execution
discipline. Model flops are credited B×model by the api.py verbs
(api.gesv_batched / posv_batched / geqrf_batched / gels_batched).
Round 12: the padded lanes' share — (bucket − B)/bucket of the
program's bytes, plus their per-item model flops — is split out to
the ``padding.waste`` ledger op at this layer (the padding happens
here, so it is accounted here; exactly zero at full pow2 occupancy).
The fixed k' = max(k, 2) rhs-width quantum stays credited as the
verb's own cost — it is a constant tile-shape floor, not bucket
padding. Under an outer jax trace the drivers degrade to plain traced
calls (composition into a larger program; whoever compiles it
accounts it).
"""

from __future__ import annotations

import contextlib
import functools
import threading
from collections import OrderedDict
from typing import Hashable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.exceptions import SlateError
from ..obs import costs as _costs
from ..obs import flops as _flops
from ..ops import blocked
from ..refine import engine as _refine
from ..refine.policy import canonical_dtype_name as _dtype_name
from ..refine.policy import check_cast_kinds as _check_cast_kinds
from ..refine.policy import jax_dtype as _jax_dtype


def _guard_mixed_dtype(work_dtype, lo: str, what: str) -> str:
    """Real/complex kind agreement for the mixed drivers (a
    complex→real astype silently discards the imaginary part — the
    factor would be of Re(A) only, info=0, never convergent)."""
    try:
        _check_cast_kinds(work_dtype, lo, what)
    except ValueError as e:
        raise SlateError(str(e))
    return lo

Array = jax.Array

# default panel width for the small-problem regime: one panel for
# n ≤ 32 (the whole factorization is one hand-batched kernel), 32-wide
# panels above it (n ≤ 256 stays ≤ 8 python-unrolled outer steps)
DEFAULT_NB = 32


def default_nb(n: int) -> int:
    return n if n <= DEFAULT_NB else DEFAULT_NB


def batch_bucket(b: int, quantum: int = 1) -> int:
    """Smallest ``quantum``·2^i ≥ b — the batch-dim compilation
    bucket. The default quantum 1 is the plain pow2 grid every round
    since 10; a tuning table (round 21) may coarsen/offset it per
    (op, n, dtype, platform) through :func:`resolved_quantum`."""
    return blocked.bucket_pow2(max(int(b), 1), max(int(quantum), 1))


# -- tuning-table consultation (round 21, slate_tpu/tuning/) ----------------
# The bucket program cache is process-global, so its tuning seam is
# too: tuning.activate_table() installs the table these resolvers
# consult when a caller leaves nb unset. One `table is None` check
# when disabled — with no active table, resolved_nb IS default_nb and
# resolved_quantum IS 1, so every program key, pad shape, and served
# bit matches the untuned tree (pinned in tests/test_tuning.py).


def _tuned_cfg(op: str, n: int, dtype):
    from ..tuning.table import active_table
    t = active_table()
    if t is None:
        return None
    return t.resolve(op, int(n), str(np.dtype(dtype)),
                     jax.default_backend())


def resolved_nb(op: str, n: int, dtype, nb: Optional[int] = None) -> int:
    """The panel width for one small-engine call: the caller's
    explicit nb wins, then the active table's first-match entry
    (clamped to n — a panel wider than the problem is the whole
    problem), then :func:`default_nb`."""
    if nb is not None:
        return nb
    cfg = _tuned_cfg(op, n, dtype)
    if cfg is not None and cfg.nb:
        return min(int(cfg.nb), int(n))
    return default_nb(n)


def resolved_quantum(op: str, n: int, dtype) -> int:
    """The batch-dim bucket quantum: the active table's
    ``batch_quantum`` when one matches, else 1 (plain pow2)."""
    cfg = _tuned_cfg(op, n, dtype)
    return (1 if cfg is None or not cfg.batch_quantum
            else max(1, int(cfg.batch_quantum)))


# -- per-bucket compiled program cache --------------------------------------

_LOCK = threading.Lock()
_PROGRAMS: "OrderedDict[Hashable, Tuple]" = OrderedDict()
_PROGRAM_CAP = 128
_COMPILES = 0


def _arg_key(args) -> Tuple:
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (treedef, tuple((tuple(l.shape), str(l.dtype))
                           for l in leaves))


_SUPPRESS = threading.local()


@contextlib.contextmanager
def suppress_accounting():
    """Skip the per-execution BYTES-ledger crediting inside this block
    (this thread only). For warmup probes: Session.warmup runs a
    zero-rhs solve purely to populate the bucket program cache — a
    probe must not show up as served traffic in the round-9 ledger."""
    _SUPPRESS.on = True
    try:
        yield
    finally:
        _SUPPRESS.on = False


def _run_bucket(name: str, fn, nb: int, *args, live_batch=None):
    """Run ``fn(*args, nb)`` through the per-bucket program cache: the
    first call per (name, nb, arg shapes/dtypes) lowers + compiles ONE
    program (cost-analyzed at the seam), later calls reuse the
    executable; every execution credits the process bytes ledger under
    ``name``. Under an outer jax trace this degrades to a plain traced
    call — the composition is compiled (and accounted) by the caller.

    ``live_batch`` (round 12) is the caller's pre-padding batch size:
    the padded lanes' share of the program's bytes — (bucket − live) /
    bucket of every axis, the kernels being batch-uniform — is split
    out to the ``padding.waste`` ledger op instead of ``name``, so the
    bucket quantization's real-but-useless device traffic stops being
    credited as served work. Exactly zero split at full occupancy."""
    global _COMPILES
    from ..obs import _jax_eager
    if not _jax_eager():
        return fn(*args, nb)
    key = (name, nb) + _arg_key(args)
    with _LOCK:
        hit = _PROGRAMS.get(key)
        if hit is not None:
            _PROGRAMS.move_to_end(key)
    if hit is None:
        exe = jax.jit(lambda *a: fn(*a, nb)).lower(*args).compile()
        pc = _costs.program_costs(exe)
        with _LOCK:
            _COMPILES += 1
            _PROGRAMS[key] = hit = (exe, pc)
            while len(_PROGRAMS) > _PROGRAM_CAP:
                _PROGRAMS.popitem(last=False)
    exe, pc = hit
    if not getattr(_SUPPRESS, "on", False):
        executed = int(getattr(args[0], "shape", (0,))[0]) or 1
        if live_batch is not None and 0 < live_batch < executed:
            frac = live_batch / executed
            ba = pc.bytes_accessed or 0.0
            _costs.BYTES.record(name, ba * frac,
                                pc.collective_bytes * frac,
                                pc.collectives)
            _costs.BYTES.record("padding.waste", ba * (1.0 - frac),
                                pc.collective_bytes * (1.0 - frac))
        else:
            _costs.BYTES.record_costs(name, pc)
    return exe(*args)


def _credit_padding_flops(waste_items: int, per_item_flops: float):
    """Model flops of the pow2-bucket padding lanes, credited to the
    process ledger's ``padding.waste`` op (round 12): the padded
    identities/zeros execute the SAME per-item arithmetic as live
    lanes — real device work the round-8 ledger used to ignore.
    Skipped under suppression (warmup probes) like the bytes ledger;
    callers only invoke this on the eager path (_run_bucket already
    degraded under an outer trace)."""
    if waste_items <= 0 or getattr(_SUPPRESS, "on", False):
        return
    from ..obs import _jax_eager
    from ..obs.flops import LEDGER
    if not _jax_eager():
        return
    LEDGER.record("padding.waste", waste_items * per_item_flops)


def bucket_stats() -> dict:
    """Bucket-cache introspection (tests + bench): resident program
    count and the monotone compile counter — "compiles once per
    (op, n, nb, dtype, B-bucket)" is asserted against this."""
    with _LOCK:
        return {"programs": len(_PROGRAMS), "compiles": _COMPILES}


def bucket_hlo(name: str, batch: Optional[int] = None,
               n: Optional[int] = None):
    """Optimized-HLO texts of the cached programs for ``name`` — the
    tests'/bench's structural evidence (no per-item factorization
    custom-call loop in a batched program). ``batch``/``n`` filter by
    the FIRST program operand's leading/trailing dims (the [B, m, n]
    operand stack every driver passes first), so a caller can assert
    about one specific bucket program instead of everything ever
    compiled under ``name``."""
    def _match(key) -> bool:
        if batch is None and n is None:
            return True
        shapes = key[3] if len(key) > 3 else ()
        if not shapes:
            return False
        shp = shapes[0][0]
        if batch is not None and (not shp or shp[0] != batch):
            return False
        if n is not None and (not shp or shp[-1] != n):
            return False
        return True

    with _LOCK:
        entries = [v[0] for k, v in _PROGRAMS.items()
                   if k[0] == name and _match(k)]
    out = []
    for exe in entries:
        try:
            out.append(exe.as_text())
        except Exception:
            pass
    return out


def clear_programs():
    """Drop the program cache (tests)."""
    global _COMPILES
    with _LOCK:
        _PROGRAMS.clear()
        _COMPILES = 0


# -- kernels (traced bodies; precision pinned inside the program) -----------
# Panel/base math must run at HIGHEST regardless of the caller's
# context (core/precision.py rationale); pinning INSIDE the traced
# body makes the compiled bucket program independent of call-site
# context, so a cache hit can never silently change precision.


def _k_potrf(a, nb):
    with jax.default_matmul_precision("highest"):
        return blocked.potrf_batched(a, nb)


def _k_getrf(a, nb):
    with jax.default_matmul_precision("highest"):
        return blocked.getrf_batched(a, nb)


def _k_geqrf(a, nb):
    with jax.default_matmul_precision("highest"):
        return blocked.geqrf_batched(a, nb)


def _k_getrs(lu, perm, b, nb):
    with jax.default_matmul_precision("highest"):
        return blocked.getrs_batched(lu, perm, b)


def _k_potrs(l, b, nb):
    with jax.default_matmul_precision("highest"):
        return blocked.potrs_batched(l, b)


def _k_gels_solve(vr, taus, ts, b, nb):
    with jax.default_matmul_precision("highest"):
        return blocked.gels_qr_solve_batched(vr, taus, ts, b, nb)


def _k_gesv(a, b, nb):
    with jax.default_matmul_precision("highest"):
        lu, perm, info = blocked.getrf_batched(a, nb)
        return blocked.getrs_batched(lu, perm, b), info


def _k_posv(a, b, nb):
    with jax.default_matmul_precision("highest"):
        l, info = blocked.potrf_batched(a, nb)
        return blocked.potrs_batched(l, b), info


def _k_gels(a, b, nb):
    with jax.default_matmul_precision("highest"):
        vr, taus, ts = blocked.geqrf_batched(a, nb)
        return blocked.gels_qr_solve_batched(vr, taus, ts, b, nb)


# -- stacking / padding helpers ---------------------------------------------


def _as_stack(A, what: str) -> Array:
    a = jnp.asarray(A)
    if a.ndim != 3:
        raise SlateError(f"{what}: expected a [B, m, n] stack, got "
                         f"shape {tuple(a.shape)}")
    return a


def _rhs_stack(B, bsz: int, rows: int, dtype, what: str):
    """Normalize right-hand sides to a [B, rows, k'] stack; returns
    (stack, vector_rank, k) where vector_rank restores [B, rows]
    inputs and k is the CALLER's column count (callers slice
    ``x[:, :, :k]`` back out).

    k' = max(k, 2): a zero column pads single-column solves because
    XLA:CPU lowers a batch-of-matvec ([B, n, n]·[B, n, 1]) with a
    reduction order that DEPENDS on the batch size — k ≥ 2 keeps every
    per-item gemm in the batch-size-independent regime, which is what
    makes the B=1 per-request path bit-identical to the batched bucket
    (pinned by tests/test_batched.py). On TPU any k below the 128
    lane width pads to the same tile regardless."""
    b = jnp.asarray(B, dtype=dtype)
    vector = b.ndim == 2
    if vector:
        b = b[:, :, None]
    if b.ndim != 3 or b.shape[0] != bsz or b.shape[1] != rows:
        raise SlateError(f"{what}: rhs stack must be [B, {rows}, k] or "
                         f"[B, {rows}], got {tuple(b.shape)}")
    k = b.shape[2]
    if k < 2:
        b = jnp.concatenate(
            [b, jnp.zeros((bsz, rows, 2 - k), b.dtype)], axis=2)
    return b, vector, k


def _pad_eye(a: Array, bb: int) -> Array:
    """Pad the batch dim to the bucket with IDENTITY items: they factor
    cleanly under every op here (LU picks its unit diagonal pivots, QR
    of I embeds trivially), flag info = 0, and — the arithmetic being
    batch-independent — cannot change any real item's bits."""
    bsz, m, n = a.shape
    if bsz == bb:
        return a
    pad = jnp.broadcast_to(jnp.eye(m, n, dtype=a.dtype)[None],
                           (bb - bsz, m, n))
    return jnp.concatenate([a, pad], axis=0)


def _pad_zeros(b: Array, bb: int) -> Array:
    bsz = b.shape[0]
    if bsz == bb:
        return b
    pad = jnp.zeros((bb - bsz,) + b.shape[1:], b.dtype)
    return jnp.concatenate([b, pad], axis=0)


def _pad_arange(perm: Array, bb: int) -> Array:
    bsz, n = perm.shape
    if bsz == bb:
        return perm
    pad = jnp.broadcast_to(jnp.arange(n, dtype=perm.dtype)[None],
                           (bb - bsz, n))
    return jnp.concatenate([perm, pad], axis=0)


# -- factorization drivers --------------------------------------------------


def getrf_batched(A, nb: Optional[int] = None):
    """Batched partial-pivot LU of a [B, n, n] stack → (LU, perm,
    info[B]) with gather-semantics perms (a[perm] = L·U per item)."""
    a = _as_stack(A, "getrf_batched")
    bsz, m, n = a.shape
    if m != n:
        raise SlateError("getrf_batched: items must be square")
    nb = resolved_nb("lu_small", n, a.dtype, nb)
    bb = batch_bucket(bsz, resolved_quantum("lu_small", n, a.dtype))
    ap = _pad_eye(a, bb)
    _credit_padding_flops(bb - bsz, _flops.getrf(n))
    lu, perm, info = _run_bucket("getrf_batched", _k_getrf, nb, ap,
                                 live_batch=bsz)
    return lu[:bsz], perm[:bsz], info[:bsz]


def potrf_batched(A, nb: Optional[int] = None):
    """Batched lower Cholesky of a Hermitian [B, n, n] stack →
    (tril L, info[B]). Only the lower triangles are read."""
    a = _as_stack(A, "potrf_batched")
    bsz, m, n = a.shape
    if m != n:
        raise SlateError("potrf_batched: items must be square")
    nb = resolved_nb("chol_small", n, a.dtype, nb)
    bb = batch_bucket(bsz, resolved_quantum("chol_small", n, a.dtype))
    ap = _pad_eye(a, bb)
    _credit_padding_flops(bb - bsz, _flops.potrf(n))
    l, info = _run_bucket("potrf_batched", _k_potrf, nb, ap,
                          live_batch=bsz)
    return l[:bsz], info[:bsz]


def geqrf_batched(A, nb: Optional[int] = None):
    """Batched Householder QR of a [B, m, n] stack (m ≥ n) →
    (packed V\\R, taus [B, n], Ts [B, ceil(n/nb), nb, nb])."""
    a = _as_stack(A, "geqrf_batched")
    bsz, m, n = a.shape
    if m < n:
        raise SlateError("geqrf_batched: items must have m >= n")
    nb = resolved_nb("qr_small", n, a.dtype, nb)
    bb = batch_bucket(bsz, resolved_quantum("qr_small", n, a.dtype))
    ap = _pad_eye(a, bb)
    _credit_padding_flops(bb - bsz, _flops.geqrf(m, n))
    vr, taus, ts = _run_bucket("geqrf_batched", _k_geqrf, nb, ap,
                               live_batch=bsz)
    return vr[:bsz], taus[:bsz], ts[:bsz]


# -- solve-using-factor drivers (the serving Session's batched path) --------


def getrs_batched(LU, perm, B):
    """Batched solve from getrf_batched factors."""
    lu = _as_stack(LU, "getrs_batched")
    bsz, n, _ = lu.shape
    b, vector, k = _rhs_stack(B, bsz, n, lu.dtype, "getrs_batched")
    bb = batch_bucket(bsz, resolved_quantum("lu_small", n, lu.dtype))
    _credit_padding_flops(bb - bsz,
                          _flops.solve_flops("lu", n, n, int(b.shape[2])))
    x = _run_bucket("getrs_batched", _k_getrs, 0, _pad_eye(lu, bb),
                    _pad_arange(jnp.asarray(perm), bb), _pad_zeros(b, bb),
                    live_batch=bsz)
    x = x[:bsz, :, :k]
    return x[:, :, 0] if vector else x


def potrs_batched(L, B):
    """Batched solve from potrf_batched factors."""
    l = _as_stack(L, "potrs_batched")
    bsz, n, _ = l.shape
    b, vector, k = _rhs_stack(B, bsz, n, l.dtype, "potrs_batched")
    bb = batch_bucket(bsz, resolved_quantum("chol_small", n, l.dtype))
    _credit_padding_flops(bb - bsz,
                          _flops.solve_flops("chol", n, n,
                                             int(b.shape[2])))
    x = _run_bucket("potrs_batched", _k_potrs, 0, _pad_eye(l, bb),
                    _pad_zeros(b, bb), live_batch=bsz)
    x = x[:bsz, :, :k]
    return x[:, :, 0] if vector else x


def gels_batched_using_factor(VR, taus, Ts, B, nb: Optional[int] = None):
    """Batched least-squares solve from geqrf_batched factors →
    [B, n, k] (or [B, n]) minimizers."""
    vr = _as_stack(VR, "gels_batched_using_factor")
    bsz, m, n = vr.shape
    taus = jnp.asarray(taus)
    ts = jnp.asarray(Ts)
    nb = int(ts.shape[-1]) if nb is None else nb
    b, vector, k = _rhs_stack(B, bsz, m, vr.dtype,
                              "gels_batched_using_factor")
    bb = batch_bucket(bsz, resolved_quantum("qr_small", n, vr.dtype))
    _credit_padding_flops(bb - bsz,
                          _flops.solve_flops("qr", m, n,
                                             int(b.shape[2])))
    x = _run_bucket("gels_batched_using_factor", _k_gels_solve, nb,
                    _pad_eye(vr, bb), _pad_zeros(taus, bb),
                    _pad_zeros(ts, bb), _pad_zeros(b, bb),
                    live_batch=bsz)
    x = x[:bsz, :, :k]
    return x[:, :, 0] if vector else x


# -- fused factor+solve drivers (one program per bucket) --------------------


def gesv_batched(A, B, nb: Optional[int] = None):
    """Batched A·X = B: factor + solve as ONE program per bucket →
    (X, info[B])."""
    a = _as_stack(A, "gesv_batched")
    bsz, m, n = a.shape
    if m != n:
        raise SlateError("gesv_batched: items must be square")
    nb = resolved_nb("lu_small", n, a.dtype, nb)
    b, vector, k = _rhs_stack(B, bsz, n, a.dtype, "gesv_batched")
    bb = batch_bucket(bsz, resolved_quantum("lu_small", n, a.dtype))
    _credit_padding_flops(
        bb - bsz,
        _flops.getrf(n) + _flops.solve_flops("lu", n, n,
                                             int(b.shape[2])))
    x, info = _run_bucket("gesv_batched", _k_gesv, nb, _pad_eye(a, bb),
                          _pad_zeros(b, bb), live_batch=bsz)
    x, info = x[:bsz, :, :k], info[:bsz]
    return (x[:, :, 0] if vector else x), info


def posv_batched(A, B, nb: Optional[int] = None):
    """Batched Hermitian-positive-definite A·X = B (lower storage):
    factor + solve as ONE program per bucket → (X, info[B])."""
    a = _as_stack(A, "posv_batched")
    bsz, m, n = a.shape
    if m != n:
        raise SlateError("posv_batched: items must be square")
    nb = resolved_nb("chol_small", n, a.dtype, nb)
    b, vector, k = _rhs_stack(B, bsz, n, a.dtype, "posv_batched")
    bb = batch_bucket(bsz, resolved_quantum("chol_small", n, a.dtype))
    _credit_padding_flops(
        bb - bsz,
        _flops.potrf(n) + _flops.solve_flops("chol", n, n,
                                             int(b.shape[2])))
    x, info = _run_bucket("posv_batched", _k_posv, nb, _pad_eye(a, bb),
                          _pad_zeros(b, bb), live_batch=bsz)
    x, info = x[:bsz, :, :k], info[:bsz]
    return (x[:, :, 0] if vector else x), info


# -- mixed-precision batched drivers (round 13: the refine/ subsystem) ------
# Factor the stack in a LOWER precision, refine every item to the
# working precision with the unified per-item-masked IR loop
# (refine/engine.batched_ir_loop) — ONE program per pow2 bucket, end to
# end (cast + batched factor + the whole refinement while-loop compile
# into the bucket executable). Static knobs (factor dtype, iteration
# budget, tolerance) are encoded into the bucket NAME so two policies
# can never share a program. Per-item isolation carries over: a
# non-convergent (or singular-in-low-precision) item flags only its own
# lane — converged lanes freeze bit-exactly inside the masked loop, so
# B=1 runs are bit-identical to any bucket lane (the linalg/batched
# contract, pinned by tests/test_refine.py).


def _k_getrf_mixed(a, nb, lo):
    with jax.default_matmul_precision("highest"):
        return blocked.getrf_batched(a.astype(lo), nb)


def _k_potrf_mixed(a, nb, lo):
    with jax.default_matmul_precision("highest"):
        return blocked.potrf_batched(a.astype(lo), nb)


def _lo_cast_up(v_lo, work):
    """Cast a low-precision solve result back to the working dtype
    behind an optimization barrier. WITHOUT the barrier XLA:CPU fuses
    the upcast into the solve's final gemm and the fused kernel's
    rounding becomes BATCH-SHAPE-DEPENDENT (measured: the identical
    bf16 getrs lane differs bitwise between the B=1 and B=8 bucket
    programs once an .astype(f32) consumer follows — the same fusion
    class as the documented c64 caveat). The barrier pins the
    low-precision rounding, restoring the cross-bucket bit-identity
    contract; cost is one blocked fusion per cast-up."""
    return jax.lax.optimization_barrier(v_lo).astype(work)


def _k_getrs_refined(a, lu, perm, b, nb, max_iters, tol):
    with jax.default_matmul_precision("highest"):
        lo, work = lu.dtype, a.dtype

        def apply_lo(r):
            return _lo_cast_up(
                blocked.getrs_batched(lu, perm, r.astype(lo)), work)

        x0 = apply_lo(b)
        cte = _refine.batched_cte(a, tol)
        return _refine.batched_ir_loop(a, b, x0, apply_lo, cte, max_iters)


def _herm_full(a):
    """Reconstruct the full Hermitian stack from lower storage: the
    refinement residual gemms read ALL of A (unlike potrf/potrs, which
    only read the lower triangles), and the batched Hermitian
    convention is lower-storage — so the kernel symmetrizes, making
    full and tril-only operands equivalent."""
    lo_tri = jnp.tril(a)
    return lo_tri + jnp.conj(jnp.swapaxes(jnp.tril(a, -1), 1, 2))


def _k_potrs_refined(a, l, b, nb, max_iters, tol):
    with jax.default_matmul_precision("highest"):
        lo, work = l.dtype, a.dtype
        af = _herm_full(a)

        def apply_lo(r):
            return _lo_cast_up(blocked.potrs_batched(l, r.astype(lo)),
                               work)

        x0 = apply_lo(b)
        cte = _refine.batched_cte(af, tol)
        return _refine.batched_ir_loop(af, b, x0, apply_lo, cte,
                                       max_iters)


def _k_gesv_mixed(a, b, nb, lo, max_iters, tol):
    with jax.default_matmul_precision("highest"):
        lu, perm, info = blocked.getrf_batched(a.astype(lo), nb)
        x, iters, conv = _k_getrs_refined(a, lu, perm, b, nb,
                                          max_iters, tol)
        return x, info, iters, conv


def _k_posv_mixed(a, b, nb, lo, max_iters, tol):
    with jax.default_matmul_precision("highest"):
        l, info = blocked.potrf_batched(a.astype(lo), nb)
        x, iters, conv = _k_potrs_refined(a, l, b, nb, max_iters, tol)
        return x, info, iters, conv


def getrf_mixed_batched(A, factor_dtype="bfloat16",
                        nb: Optional[int] = None):
    """Batched LOW-PRECISION LU of a working-precision [B, n, n] stack
    → (LU_lo, perm, info[B]): the cast happens inside the bucket
    program, so the factors come back in ``factor_dtype`` — the
    half-HBM residents the serving Session caches for refined solves."""
    a = _as_stack(A, "getrf_mixed_batched")
    bsz, m, n = a.shape
    if m != n:
        raise SlateError("getrf_mixed_batched: items must be square")
    nb = resolved_nb("lu_small", n, a.dtype, nb)
    lo = _guard_mixed_dtype(a.dtype, _dtype_name(factor_dtype),
                            "getrf_mixed_batched")
    bb = batch_bucket(bsz, resolved_quantum("lu_small", n, a.dtype))
    ap = _pad_eye(a, bb)
    _credit_padding_flops(bb - bsz, _flops.getrf(n))
    lu, perm, info = _run_bucket(
        f"getrf_mixed_batched[{lo}]",
        functools.partial(_k_getrf_mixed, lo=_jax_dtype(lo)), nb, ap,
        live_batch=bsz)
    return lu[:bsz], perm[:bsz], info[:bsz]


def potrf_mixed_batched(A, factor_dtype="bfloat16",
                        nb: Optional[int] = None):
    """Batched low-precision lower Cholesky → (L_lo, info[B])."""
    a = _as_stack(A, "potrf_mixed_batched")
    bsz, m, n = a.shape
    if m != n:
        raise SlateError("potrf_mixed_batched: items must be square")
    nb = resolved_nb("chol_small", n, a.dtype, nb)
    lo = _guard_mixed_dtype(a.dtype, _dtype_name(factor_dtype),
                            "potrf_mixed_batched")
    bb = batch_bucket(bsz, resolved_quantum("chol_small", n, a.dtype))
    ap = _pad_eye(a, bb)
    _credit_padding_flops(bb - bsz, _flops.potrf(n))
    l, info = _run_bucket(
        f"potrf_mixed_batched[{lo}]",
        functools.partial(_k_potrf_mixed, lo=_jax_dtype(lo)), nb, ap,
        live_batch=bsz)
    return l[:bsz], info[:bsz]


def getrs_refined_batched(A, LU_lo, perm, B, max_iters: int = 30,
                          tol: Optional[float] = None):
    """Batched refined solve from resident LOW-precision LU factors:
    the serving path — initial lo solve + the per-item-masked IR loop,
    one program per bucket. ``A`` is the working-precision operand
    stack (the residual gemms read it). Returns (x, iters[B],
    converged[B]); iters counts residual checks per item."""
    a = _as_stack(A, "getrs_refined_batched")
    lu = _as_stack(LU_lo, "getrs_refined_batched")
    bsz, n, _ = a.shape
    b, vector, k = _rhs_stack(B, bsz, n, a.dtype, "getrs_refined_batched")
    bb = batch_bucket(bsz, resolved_quantum("lu_small", n, a.dtype))
    _credit_padding_flops(
        bb - bsz, _flops.solve_flops("lu", n, n, int(b.shape[2])))
    name = (f"getrs_refined_batched[{_dtype_name(lu.dtype)},"
            f"{max_iters},{tol!r}]")
    x, iters, conv = _run_bucket(
        name,
        functools.partial(_k_getrs_refined, max_iters=max_iters, tol=tol),
        0, _pad_eye(a, bb), _pad_eye(lu, bb),
        _pad_arange(jnp.asarray(perm), bb), _pad_zeros(b, bb),
        live_batch=bsz)
    x = x[:bsz, :, :k]
    return (x[:, :, 0] if vector else x), iters[:bsz], conv[:bsz]


def potrs_refined_batched(A, L_lo, B, max_iters: int = 30,
                          tol: Optional[float] = None):
    """Batched refined solve from resident low-precision Cholesky
    factors → (x, iters[B], converged[B])."""
    a = _as_stack(A, "potrs_refined_batched")
    l = _as_stack(L_lo, "potrs_refined_batched")
    bsz, n, _ = a.shape
    b, vector, k = _rhs_stack(B, bsz, n, a.dtype, "potrs_refined_batched")
    bb = batch_bucket(bsz, resolved_quantum("chol_small", n, a.dtype))
    _credit_padding_flops(
        bb - bsz, _flops.solve_flops("chol", n, n, int(b.shape[2])))
    name = (f"potrs_refined_batched[{_dtype_name(l.dtype)},"
            f"{max_iters},{tol!r}]")
    x, iters, conv = _run_bucket(
        name,
        functools.partial(_k_potrs_refined, max_iters=max_iters, tol=tol),
        0, _pad_eye(a, bb), _pad_eye(l, bb), _pad_zeros(b, bb),
        live_batch=bsz)
    x = x[:bsz, :, :k]
    return (x[:, :, 0] if vector else x), iters[:bsz], conv[:bsz]


def gesv_mixed_batched(A, B, nb: Optional[int] = None,
                       factor_dtype="bfloat16", max_iters: int = 30,
                       tol: Optional[float] = None):
    """Batched mixed-precision A·X = B: low-precision LU + per-item
    refinement as ONE program per bucket → (X, info[B], iters[B]);
    iters[i] < 0 ⇒ item i did not converge (its X is the best iterate —
    callers own the fallback, see api.gesv_mixed_batched)."""
    a = _as_stack(A, "gesv_mixed_batched")
    bsz, m, n = a.shape
    if m != n:
        raise SlateError("gesv_mixed_batched: items must be square")
    nb = resolved_nb("lu_small", n, a.dtype, nb)
    lo = _guard_mixed_dtype(a.dtype, _dtype_name(factor_dtype),
                            "gesv_mixed_batched")
    b, vector, k = _rhs_stack(B, bsz, n, a.dtype, "gesv_mixed_batched")
    bb = batch_bucket(bsz, resolved_quantum("lu_small", n, a.dtype))
    _credit_padding_flops(
        bb - bsz,
        _flops.getrf(n) + _flops.solve_flops("lu", n, n,
                                             int(b.shape[2])))
    x, info, iters, conv = _run_bucket(
        f"gesv_mixed_batched[{lo},{max_iters},{tol!r}]",
        functools.partial(_k_gesv_mixed, lo=_jax_dtype(lo),
                          max_iters=max_iters, tol=tol),
        nb, _pad_eye(a, bb), _pad_zeros(b, bb), live_batch=bsz)
    x, info, iters, conv = (x[:bsz, :, :k], info[:bsz], iters[:bsz],
                            conv[:bsz])
    iters = jnp.where(conv, iters, -iters)
    return (x[:, :, 0] if vector else x), info, iters


def posv_mixed_batched(A, B, nb: Optional[int] = None,
                       factor_dtype="bfloat16", max_iters: int = 30,
                       tol: Optional[float] = None):
    """Batched mixed-precision Hermitian-positive-definite solve (lower
    storage): low-precision Cholesky + per-item refinement as ONE
    program per bucket → (X, info[B], iters[B]); iters < 0 ⇒ not
    converged."""
    a = _as_stack(A, "posv_mixed_batched")
    bsz, m, n = a.shape
    if m != n:
        raise SlateError("posv_mixed_batched: items must be square")
    nb = resolved_nb("chol_small", n, a.dtype, nb)
    lo = _guard_mixed_dtype(a.dtype, _dtype_name(factor_dtype),
                            "posv_mixed_batched")
    b, vector, k = _rhs_stack(B, bsz, n, a.dtype, "posv_mixed_batched")
    bb = batch_bucket(bsz, resolved_quantum("chol_small", n, a.dtype))
    _credit_padding_flops(
        bb - bsz,
        _flops.potrf(n) + _flops.solve_flops("chol", n, n,
                                             int(b.shape[2])))
    x, info, iters, conv = _run_bucket(
        f"posv_mixed_batched[{lo},{max_iters},{tol!r}]",
        functools.partial(_k_posv_mixed, lo=_jax_dtype(lo),
                          max_iters=max_iters, tol=tol),
        nb, _pad_eye(a, bb), _pad_zeros(b, bb), live_batch=bsz)
    x, info, iters, conv = (x[:bsz, :, :k], info[:bsz], iters[:bsz],
                            conv[:bsz])
    iters = jnp.where(conv, iters, -iters)
    return (x[:, :, 0] if vector else x), info, iters


def gels_batched(A, B, nb: Optional[int] = None):
    """Batched least squares min‖A·X − B‖ (m ≥ n): QR factor + solve
    as ONE program per bucket → (X [B, n, k], info[B] — always 0; QR
    of a full stack never fails structurally, matching gels)."""
    a = _as_stack(A, "gels_batched")
    bsz, m, n = a.shape
    if m < n:
        raise SlateError("gels_batched: items must have m >= n")
    nb = resolved_nb("qr_small", n, a.dtype, nb)
    b, vector, k = _rhs_stack(B, bsz, m, a.dtype, "gels_batched")
    bb = batch_bucket(bsz, resolved_quantum("qr_small", n, a.dtype))
    _credit_padding_flops(
        bb - bsz,
        _flops.geqrf(m, n) + _flops.solve_flops("qr", m, n,
                                                int(b.shape[2])))
    x = _run_bucket("gels_batched", _k_gels, nb, _pad_eye(a, bb),
                    _pad_zeros(b, bb), live_batch=bsz)
    x = x[:bsz, :, :k]
    info = np.zeros((bsz,), np.int32)
    return (x[:, :, 0] if vector else x), info
