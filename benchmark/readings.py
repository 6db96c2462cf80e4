#!/usr/bin/env python3
"""The readings that the limits in ``benchmark/limits/<cell>.json`` are
set from, for one cell, in one process on the chip:

    python3 benchmark/readings.py --workload <cell> \\
        --seeds 11,12,... --control-seeds 21,22,23 --seconds 5

(``--config <name> --traffic <mix>`` in place of ``--workload`` for a
cell that the manifest does not hold yet.)

For each seed it runs the cell's own timed path, at the cell's sizes
and load, for a short window and prints the numbers that decide
``correct``; then the same for the control, the configuration's own
lower-precision path (its ``control`` options). The programs compile
once and serve every seed. The last line holds, for each number
compared, the largest reading of the program (the lower reading), the
smallest of the control (the upper reading) and their ratio. The
benchmark's own runs never run the control.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmark import cache  # noqa: E402

cache.place(ROOT)  # before JAX is imported
from benchmark import manifest, trace  # noqa: E402


def seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--config")
    ap.add_argument("--traffic")
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.workload:
        cell = manifest.cell(ROOT, args.workload)
    elif args.config and args.traffic:
        cell = manifest.assemble(ROOT, args.config, args.traffic)
    else:
        ap.error("give --workload, or --config and --traffic")
    from slate_tpu.compat.platform import enable_compile_cache

    enable_compile_cache()
    import jax

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    held = {}
    readings = {False: [], True: []}
    names = []  # the numbers compared, as the loop names them
    for control, group in ((False, args.seeds),
                           (True, args.control_seeds)):
        for seed in group:
            t0 = time.perf_counter()
            try:
                out = cell.loop.run(cell, seed, args.seconds,
                                    trace.NO_TRACE, rehearse=args.rehearse,
                                    control=control, held=held)
            except Exception as e:  # a control that crashes has failed
                if not control:
                    raise
                print(json.dumps({"control": True, "seed": seed,
                                  "error": f"{type(e).__name__}: {e}"}),
                      flush=True)
                continue
            row = dict(out.compared, control=control, seed=seed,
                       attempted=out.attempted, failed=out.failed,
                       wall_s=time.perf_counter() - t0,
                       residual_median=out.diagnostics["residual_median"])
            readings[control].append(row)
            names = names or list(out.compared)
            print(json.dumps(row), flush=True)
    summary = {}
    for name in names:
        lo = [r[name] for r in readings[False]]
        up = [r[name] for r in readings[True]]
        summary[name] = {"lower": max(lo) if lo else None,
                         "upper": min(up) if up else None,
                         "program_seeds": len(lo), "control_seeds": len(up)}
        if lo and up and max(lo) > 0:
            summary[name]["ratio"] = min(up) / max(lo)
    print(json.dumps({"workload": cell.name, "readings": summary}),
          flush=True)
    for sess_ex in held.values():
        if isinstance(sess_ex, tuple):
            sess_ex[1].shutdown()
            sess_ex[0].close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
