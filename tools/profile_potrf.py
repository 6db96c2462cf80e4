#!/usr/bin/env python
"""Phase decomposition + profiler trace for the iterative potrf.

Pre-staged for the on-chip session (VERDICT r4 next-round #1): answers
"where does the potrf time go on a real chip" with measurements, not
arguments. Three chained-phase timings reconstruct the per-step budget
of _potrf_iter (slate_tpu/linalg/cholesky.py):

  tiles    — the nt sequential diagonal-tile Choleskys (latency floor)
  panels   — per-step batched-leaf inverse + panel gemm
  trailing — per-step triangle-aware herk recursion (the MXU flops)

and the full driver is timed with the same scan methodology as bench.py
(dispatch/sync overhead cancels between two scan lengths). If
t_total ≈ t_tiles + t_panels + t_trailing the phases serialize (single
chip: expected — there is a true data dependence); the printed
panel_fraction is the share a mesh's async scheduler could hide under
the trailing update (the Lookahead/P3 capability,
/root/reference/src/potrf.cc:84-195).

Round 7: the iterative loop is the LOOKAHEAD pipeline by default and
every level's ops carry jax.named_scope labels (potrf_l{k}_tile /
_panel / _trail_next / _l{k+1}_tile_lookahead / _trail_rest — see
linalg/cholesky.py::_potrf_iter), so a --trace artifact shows
per-level panel/trailing timestamps directly: overlap, where the
backend schedules it, appears as the l{k+1} tile-factor region
straddling the l{k} trail_rest gemms. --lookahead {0,1} selects the
schedule; the output also reports the lookahead A/B total
(panel-hidden vs exposed — the lookahead model's per-level floor is
max(panel, trailing) instead of their sum).

Optionally captures a jax.profiler trace of ONE full potrf call
(--trace DIR) for the committed artifact; on a ≥2-device backend the
trace is the direct overlap evidence (look for all-gather ops running
concurrently with the trailing-update fusions).

Usage: python tools/profile_potrf.py [n] [nb] [--trace DIR]
                                     [--lookahead {0,1}]
Writes one JSON line to stdout; commentary to stderr.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# single source of truth for the timing protocol — the two committed
# evidence producers (bench.py, this) must share one methodology
from bench import _per_iter_seconds  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("n", type=int, nargs="?", default=8192)
    ap.add_argument("nb", type=int, nargs="?", default=1024)
    ap.add_argument("--trace", default=None, metavar="DIR")
    ap.add_argument("--lookahead", type=int, default=1, choices=(0, 1),
                    help="pipeline schedule for the traced/timed "
                         "driver (1 = lookahead pipeline, 0 = the "
                         "sequential round-6 schedule)")
    opts = ap.parse_args()
    n, nb, trace_dir = opts.n, opts.nb, opts.trace
    lookahead = opts.lookahead

    import slate_tpu as st
    from slate_tpu.core.types import Uplo
    from slate_tpu.linalg.cholesky import _potrf_iter, _tile_chol
    from slate_tpu.matgen import random_spd
    from slate_tpu.ops import blocked

    plat = jax.devices()[0].platform
    print(f"# platform={plat} n={n} nb={nb} nt={n // nb}", file=sys.stderr)

    a0 = jnp.tril(random_spd(n, dtype=jnp.float32, seed=3))
    a0 = a0 + n * jnp.eye(n, dtype=jnp.float32)  # keep iterates SPD
    nt = n // nb
    prec = "high"

    def full(a):
        out, _ = _potrf_iter(a, nb, prec, lookahead)
        return a + 1e-30 * out

    def full_other(a):
        out, _ = _potrf_iter(a, nb, prec, 1 - lookahead)
        return a + 1e-30 * out

    def tiles_only(a):
        out = a
        for k in range(nt):
            k0, k1 = k * nb, (k + 1) * nb
            lkk, _ = _tile_chol(out[k0:k1, k0:k1])
            out = jax.lax.dynamic_update_slice(out, lkk, (k0, k0))
        return a + 1e-30 * out

    def panels_only(a):
        out = a
        for k in range(nt - 1):
            k0, k1 = k * nb, (k + 1) * nb
            inv = blocked.trtri_lower_batched(out[k0:k1, k0:k1])
            pan = blocked.mm(out[k1:, k0:k1], jnp.conj(inv).T, prec)
            out = jax.lax.dynamic_update_slice(out, pan, (k1, k0))
        return a + 1e-30 * out

    def trailing_only(a):
        # round 6: the loop's trailing phase is the slab-wise in-place
        # update (herk_trailing_inplace) — the reconstruction must time
        # what the driver actually runs
        out = a
        for k in range(nt - 1):
            k0, k1 = k * nb, (k + 1) * nb
            out = blocked.herk_trailing_inplace(
                out, out[k1:, k0:k1], k1, nb, prec=prec)
        return a + 1e-30 * out

    res = {"platform": plat, "n": n, "nb": nb, "nt": nt,
           "lookahead": lookahead}
    for name, fn in (("total", full), ("tiles", tiles_only),
                     ("panels", panels_only), ("trailing", trailing_only)):
        t = _per_iter_seconds(lambda c, cs, f=fn: f(c), a0, (), k1=2, k2=6)
        res[f"t_{name}_ms"] = round(t * 1e3, 2)
        print(f"# {name:9s} {t * 1e3:8.2f} ms/iter", file=sys.stderr)
    # lookahead A/B: the other schedule's total (round 7). The
    # lookahead model's floor replaces tiles+panels+trailing SUM with
    # per-level max(panel chain, remainder trailing): hidden_floor
    # below is that model evaluated from the measured phase terms.
    t_other = _per_iter_seconds(lambda c, cs: full_other(c), a0, (),
                                k1=2, k2=6)
    res[f"t_total_lookahead{1 - lookahead}_ms"] = round(t_other * 1e3, 2)
    print(f"# total(lookahead={1 - lookahead}) {t_other * 1e3:8.2f} "
          "ms/iter", file=sys.stderr)
    phase_sum = res["t_tiles_ms"] + res["t_panels_ms"] + res["t_trailing_ms"]
    res["t_phase_sum_ms"] = round(phase_sum, 2)
    res["panel_fraction"] = round(
        (res["t_tiles_ms"] + res["t_panels_ms"]) / max(res["t_total_ms"], 1e-9), 3)
    res["serialization"] = round(res["t_total_ms"] / max(phase_sum, 1e-9), 3)
    # per-level lookahead floor: panel terms hide under the remainder
    # trailing (or vice versa) — the exposed schedule pays their sum
    res["t_lookahead_model_floor_ms"] = round(
        max(res["t_tiles_ms"] + res["t_panels_ms"], res["t_trailing_ms"]),
        2)
    from slate_tpu.obs import flops as model_flops
    gflops = model_flops.potrf(n) / 1e9 / max(res["t_total_ms"] / 1e3, 1e-9)
    res["potrf_gflops"] = round(gflops, 1)

    if trace_dir:
        # trace the JITTED program (eager dispatch would serialize ops
        # host-side and falsely show zero overlap)
        jit_potrf = jax.jit(lambda x: _potrf_iter(x, nb, prec, lookahead))
        jax.block_until_ready(jit_potrf(a0))  # warm the compile cache
        with jax.profiler.trace(trace_dir):
            out, info = jit_potrf(a0)
            jax.block_until_ready(out)
        res["trace_dir"] = trace_dir
        print(f"# trace written to {trace_dir}", file=sys.stderr)
        # MEASURED lookahead overlap (ISSUE 4): when the profiler run
        # left a chrome-format device trace, align the per-level
        # potrf_l{k}_* named scopes and report how much of each
        # level-(k+1) lookahead tile-factor ran under the level-k
        # trail_rest gemms — the number the PERF_HISTORY.md round-7 model
        # (per-level floor = max(panel, trailing)) only predicts.
        from slate_tpu.obs import merge as obs_merge
        paths = obs_merge.find_device_traces(trace_dir)
        if paths:
            events = obs_merge.load_trace(paths[0])
            ov = obs_merge.lookahead_overlap(events, driver="potrf")
            res["lookahead_overlap"] = {
                "panel_s": round(ov["panel_s"], 6),
                "hidden_s": round(ov["hidden_s"], 6),
                "overlap_fraction": round(ov["overlap_fraction"], 3),
                "levels": len(ov["levels"]),
                "source": paths[0],
            }
            if ov["levels"]:
                print(f"# measured lookahead overlap: "
                      f"{ov['overlap_fraction']:.1%} of lookahead-panel "
                      "time hidden under trailing gemms", file=sys.stderr)
            else:
                print("# no lookahead-scoped device events in the trace "
                      "(XLA:CPU strips named-scope metadata; on TPU the "
                      "scopes survive in event args) — overlap reported "
                      "as 0 levels", file=sys.stderr)
        else:
            res["lookahead_overlap"] = None
            print("# no chrome-format device trace found under "
                  f"{trace_dir} (xplane-only profiler output needs the "
                  "tensorboard converter) — overlap not measured",
                  file=sys.stderr)

    print(json.dumps(res))


if __name__ == "__main__":
    main()
