#!/usr/bin/env python3
"""One sweep of an open-loop cell's arrival rate, in one process on the
chip, to find the highest rate the system sustains:

    python3 benchmark/sweep.py --config chol_spd_n16384_f32 \\
        --traffic served_poisson --rates 100,120,140 --seconds 10 --seed 1

For each rate it runs the cell's own served path (its Session, Executor
and mix, the rate replaced) for ``--seconds`` and prints one line: the
backlog at the middle and at the end of the arrivals, the latency
quantiles from the due time, the batch mean and how late the generator
ran. A rate is sustained where the backlog at the end is no larger than
at the middle, give or take one batch in flight (``max_batch``). The
cell's mix file then fixes its rate below that; the benchmark's own
runs never sweep.
"""

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmark import cache  # noqa: E402

cache.place(ROOT)  # before JAX is imported
from benchmark import manifest, trace  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True,
                    type=lambda t: [float(r) for r in t.split(",")])
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = manifest.assemble(ROOT, args.config, args.traffic)
    if cell.traffic["kind"] != "open_loop":
        ap.error(f"{args.traffic} is not an open-loop mix")
    from slate_tpu.compat.platform import enable_compile_cache

    enable_compile_cache()
    import jax

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    held = {}
    try:
        for rate in args.rates:
            c = dataclasses.replace(cell, traffic=dict(cell.traffic,
                                                       rate_per_s=rate))
            out = c.loop.run(c, args.seed, args.seconds,
                                  trace.NO_TRACE, rehearse=args.rehearse,
                                  held=held)
            d = out.diagnostics
            b = out.context["before"]["histograms"].get("batch_size", {})
            a = out.context["after"]["histograms"]["batch_size"]
            print(json.dumps({
                "rate_per_s": rate, "requests": out.attempted,
                "failed": out.failed,
                "backlog_middle": d["backlog_middle"],
                "backlog_end": d["backlog_end"],
                "sustained": d["backlog_end"] <= d["backlog_middle"]
                + int(cell.traffic["max_batch"]),
                "p50_ms": out.values["served_p50_ms"],
                "p99_ms": out.values["served_p99_ms"],
                "batch_mean": (a["sum"] - b.get("sum", 0.0))
                / max(1, a["count"] - b.get("count", 0)),
                "late_p99_ms": d["late_p99_ms"], "drain_s": d["drain_s"],
                "residual_max": out.compared["residual_max"]}), flush=True)
    finally:
        for sess, ex in held.values():
            ex.shutdown()
            sess.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
