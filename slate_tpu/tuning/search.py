"""Offline empirical config search (the ATLAS half of the autotuner).

``tools/autotune.py`` drives :func:`run_search` over a declared config
space per (op, pow2-n-bucket, dtype, platform): for the dense drivers
(chol/lu/qr) it sweeps (nb, inner_blocking, lookahead) — the wide-panel
64/128 dispatch cells are exactly the nb ≤ 128 rows, recorded as
``wide_panel`` — and for the small-problem engine (lu_small/chol_small)
it sweeps (nb, batch/width bucket quantum). Each candidate is
AOT-compiled ONCE (``jit(...).lower(...).compile()``, compiles counted)
and slope-timed (time k1 then k2 executions;
the per-iteration difference quotient cancels dispatch overhead), then
scored by joining the measured seconds against the program's
compile-time cost analysis through
:func:`slate_tpu.obs.costs.score_measured` — measured GFLOP/s always,
roofline fraction whenever a MachineModel is configured (env). The
winner per cell becomes one ``TUNING_r01.json`` entry.

Determinism (pinned): with a fixed ``seed`` and a deterministic
``measure`` callable, two runs emit byte-identical documents — the
config enumeration order is static, operands are seeded per
(op, n, dtype), ties break to the earlier candidate, and the document
carries no timestamps. The ``measure`` parameter exists exactly for
that pin (tests inject a pure function); the default measurer runs the
real program on the local device, so the committed table is honest
about its platform (CPU-smoke tables are labeled ``cpu`` and gate
nothing — the bench_gate platform policy).

The offline search itself never runs in tier-1 (~seconds per candidate
adds up): the committed table is the test fixture.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from .table import TUNING_SCHEMA, TunedConfig

DENSE_OPS = ("chol", "lu", "qr")
SMALL_OPS = ("lu_small", "chol_small")
DEFAULT_OPS = DENSE_OPS + SMALL_OPS

# slope-timing iteration counts (small: a search visits
# |space| × |cells| programs)
SLOPE_K1 = 2
SLOPE_K2 = 6
# live batch the small-engine candidates execute: deliberately off the
# pow2 grid so the quantum knob changes the executed bucket
# (bucket_pow2(5, 1) = 8 vs bucket_pow2(5, 3) = 6 — padding waste is
# real device work and the per-live-item score sees it)
SMALL_PROBE_BATCH = 5


def config_space(op: str, n: int, quick: bool = False) -> List[dict]:
    """The declared candidate grid for one (op, n-bucket) cell, in the
    deterministic order ties resolve by. Every candidate is a plain
    config dict (the TUNING_r01.json ``config`` column)."""
    out: List[dict] = []
    if op in DENSE_OPS:
        nbs = (32, 64) if quick else (32, 64, 128)
        ibs = (16, 32)
        for nb in nbs:
            if nb > n:
                continue
            for ib in ibs:
                if ib > nb:
                    continue
                for la in (0, 1):
                    out.append({
                        "nb": nb, "inner_blocking": ib, "lookahead": la,
                        # the round-7 wide-base dispatch cell this nb
                        # lands in (ops/blocked.py: w ≤ 128 runs as one
                        # wide kernel invocation)
                        "wide_panel": nb if nb <= 128 else None,
                    })
    elif op in SMALL_OPS:
        nbs = (8, 16) if quick else (8, 16, 32)
        for nb in nbs:
            if nb > n:
                continue
            for q in (1, 3):
                out.append({"nb": nb, "batch_quantum": q,
                            "width_quantum": q})
    else:
        raise ValueError(f"config_space: unknown op {op!r}")
    return out


def slope_seconds(call: Callable[[], None], k1: int = SLOPE_K1,
                  k2: int = SLOPE_K2, target_s: float = 0.02) -> float:
    """Per-iteration seconds by the slope method: time k1
    executions, then k2, and return the difference quotient — constant
    dispatch overhead cancels. The iteration counts auto-scale so the
    first window spans ~``target_s`` (a µs-scale program slope-timed
    over 2-vs-6 raw calls measures scheduler jitter, not the program);
    a still-non-positive slope falls back to the all-in mean — honest,
    slightly dispatch-inflated, never absurd."""
    t0 = time.perf_counter()
    call()  # warm + calibrate
    once = time.perf_counter() - t0
    scale = max(1, int(round(target_s / max(once, 1e-7))))
    k1, k2 = k1 * scale, k2 * scale
    t0 = time.perf_counter()
    for _ in range(k1):
        call()
    t1 = time.perf_counter()
    for _ in range(k2):
        call()
    t2 = time.perf_counter()
    slope = ((t2 - t1) - (t1 - t0)) / (k2 - k1)
    if slope <= 0:
        slope = (t2 - t0) / (k1 + k2)
    return slope


def _seeded_operand(op: str, n: int, dtype: str, seed: int):
    """Deterministic operand per (op, n, dtype, seed): SPD for the
    cholesky families, diagonally-dominant general otherwise."""
    import numpy as np
    rng = np.random.default_rng(
        (seed * 1000003 + n * 101 + len(op) * 17) & 0x7FFFFFFF)
    a = rng.standard_normal((n, n)).astype(dtype)
    if op in ("chol", "chol_small"):
        return a @ a.T + n * np.eye(n, dtype=dtype)
    return a + n * np.eye(n, dtype=dtype)


def measure_config(op: str, n: int, dtype: str, config: dict,
                   seed: int = 0) -> dict:
    """Measure ONE candidate on the local device: AOT-compile the
    config's factor program once, slope-time it, and return the raw
    row the scorer joins — {seconds_per_iter, model_flops,
    bytes_accessed, compiles, live_items}. ``model_flops`` /
    ``seconds_per_iter`` are per LIVE work item, so the small-engine
    rows charge their own padding waste."""
    import dataclasses as _dc

    import jax
    import numpy as np

    from ..core.types import DEFAULT_OPTIONS, MatrixKind, Uplo
    from ..obs import costs as _costs
    from ..obs import flops as _flops
    a = _seeded_operand(op, n, dtype, seed)
    cfg = TunedConfig(**{k: v for k, v in config.items()
                         if k in TunedConfig.__dataclass_fields__})
    if op in DENSE_OPS:
        from ..core.tiled_matrix import from_dense
        from ..runtime.session import _make_factor_fn
        opts = cfg.apply(_dc.replace(DEFAULT_OPTIONS))
        nb = int(config["nb"])
        if op == "chol":
            A = from_dense(np.tril(a), nb=nb, kind=MatrixKind.Symmetric,
                           uplo=Uplo.Lower)
        else:
            A = from_dense(a, nb=nb)
        fn = jax.jit(_make_factor_fn(op, opts))
        exe = fn.lower(A).compile()
        model_fl = {"chol": _flops.potrf, "lu": _flops.getrf,
                    "qr": lambda nn: _flops.geqrf(nn, nn)}[op](n)
        live = 1

        def call():
            jax.block_until_ready(exe(A))
    else:
        from ..linalg import batched as _batched
        from ..ops.blocked import bucket_pow2
        nb = min(int(config["nb"]), n)
        q = int(config.get("batch_quantum", 1) or 1)
        live = SMALL_PROBE_BATCH
        bb = bucket_pow2(live, q)
        stack = np.broadcast_to(a, (live,) + a.shape)
        kern = (_batched._k_getrf if op == "lu_small"
                else _batched._k_potrf)
        ap = np.concatenate(
            [stack, np.broadcast_to(np.eye(n, dtype=a.dtype),
                                    (bb - live, n, n))], axis=0)
        fn = jax.jit(lambda x: kern(x, nb))
        exe = fn.lower(ap).compile()
        per_item = (_flops.getrf(n) if op == "lu_small"
                    else _flops.potrf(n))
        model_fl = per_item * live

        def call():
            jax.block_until_ready(exe(ap))
    sec = slope_seconds(call)
    pc = _costs.program_costs(exe)
    return {
        "seconds_per_iter": sec,
        "model_flops": float(model_fl),
        "bytes_accessed": pc.bytes_accessed,
        "compiles": 1,
        "live_items": live,
    }


def run_search(ops: Sequence[str] = DEFAULT_OPS,
               n_buckets: Sequence[int] = (64,),
               dtypes: Sequence[str] = ("float32",),
               platform: Optional[str] = None,
               seed: int = 0, quick: bool = False,
               measure: Optional[Callable] = None,
               log: Optional[Callable[[str], None]] = None) -> dict:
    """Sweep the config space and emit the TUNING document (the
    committed-artifact schema; ``tools/bench_gate.py --check-schema``
    validates it). One entry per (op, n-bucket, dtype): the
    highest-GFLOP/s candidate, with its score row (measured GFLOP/s,
    per-iter seconds, roofline fraction when a machine model is
    configured, compile count, candidate census) as provenance.

    ``measure(op, n, dtype, config, seed)`` defaults to
    :func:`measure_config` (real device); injecting a pure function
    makes the whole search deterministic — the pinned property."""
    from ..obs import costs as _costs
    if platform is None:
        import jax
        platform = jax.default_backend()
    if measure is None:
        measure = measure_config
    entries: List[dict] = []
    total_compiles = 0
    for op in ops:
        for bucket in n_buckets:
            for dtype in dtypes:
                space = config_space(op, int(bucket), quick=quick)
                best: Optional[Tuple[float, dict, dict]] = None
                compiles = 0
                for config in space:
                    row = measure(op, int(bucket), dtype, config, seed)
                    compiles += int(row.get("compiles", 1))
                    score = _costs.score_measured(
                        row["model_flops"], row["seconds_per_iter"],
                        bytes_accessed=row.get("bytes_accessed"))
                    gf = score.get("gflops") or 0.0
                    if best is None or gf > best[0]:
                        best = (gf, config,
                                dict(score,
                                     seconds_per_iter=row[
                                         "seconds_per_iter"]))
                    if log is not None:
                        log(f"  {op} n<={bucket} {dtype} {config} -> "
                            f"{gf:.2f} GFLOP/s")
                if best is None:
                    continue
                total_compiles += compiles
                gf, config, score = best
                entries.append({
                    "op": op, "n_max": int(bucket), "dtype": dtype,
                    "platform": platform,
                    "config": {k: v for k, v in config.items()
                               if v is not None},
                    "score": {
                        "gflops": score.get("gflops"),
                        "seconds_per_iter": score["seconds_per_iter"],
                        "intensity": score.get("intensity"),
                        "roof_fraction": score.get("roof_fraction"),
                        "compiles": compiles,
                        "candidates": len(space),
                    },
                })
    return {
        "schema": TUNING_SCHEMA,
        "generated_by": "tools/autotune.py",
        "platform": platform,
        "seed": int(seed),
        "quick": bool(quick),
        "search": {"ops": list(ops),
                   "n_buckets": [int(b) for b in n_buckets],
                   "dtypes": list(dtypes),
                   "total_compiles": total_compiles},
        "entries": entries,
    }
