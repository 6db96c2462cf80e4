"""Warm-iteration slope timing (round 6) for a subject that cannot be
wrapped in a jit scan: tester.Ctx.timed's ``--iters`` mode (heev/svd
drivers route secular/deflation stages through the host).

Methodology: warm once, then time back-to-back batches of k1 and k2
calls with ONE result fetch at each batch end — jax dispatch is async,
so the device queue drains the chain while the host runs ahead, and
the fixed dispatch/fetch round-trip (the term that made single-shot
sweep rows ~100× below bench steady state on the remote-TPU setup of
rounds 2–5) cancels in the slope (t₂ − t₁)/(k₂ − k₁).
"""

from __future__ import annotations

import time


def sync_tree(out):
    """Block until ``out`` is materialized (fetch of the first leaf)."""
    import jax
    import numpy as np

    np.asarray(jax.tree_util.tree_leaves(out)[0]).ravel()[:1]


def eager_slope_seconds(fn, k1: int, k2: int, reps: int = 1,
                        sync=sync_tree):
    """Steady-state per-call seconds for an eager (non-jittable) call.

    Returns (result_of_warm_call, seconds). ``reps`` takes the min of
    that many timings per batch length (noise guard). Resolution floor:
    when t₂ − t₁ sinks under timer noise (tiny problems), degrade to a
    tenth of the mean per-call time rather than report a nonsense
    slope."""
    out = fn()
    sync(out)

    def batch(k):
        o = None
        t0 = time.perf_counter()
        for _ in range(k):
            o = fn()
        sync(o)
        return time.perf_counter() - t0

    t1 = min(batch(k1) for _ in range(reps))
    t2 = min(batch(k2) for _ in range(reps))
    return out, max((t2 - t1) / (k2 - k1), t2 / k2 / 10.0, 1e-9)
