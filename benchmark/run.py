#!/usr/bin/env python3
"""Run one cell of the benchmark once, from the root of a checkout:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

One process: place JAX's compile cache inside the checkout, make the
cell's data on the device from the seed, lower and warm the cell's own
programs (set-up), measure for ``--seconds`` (``--trace 1``: trace a
short window of the mix's ``trace_seconds`` instead), check the answers
against the plain reference, then print diagnostic lines and, last, one
JSON line: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (``breakdown`` with ``--trace 1``), and ``compared``, each
number compared beside its limit. The compared numbers also go to
standard error, as its last lines.

A device that is not a TPU, or fewer chips than the cell asks for, ends
the run with exit code 2 and no result. ``--rehearse`` runs the cell at
tiny sizes on whatever device JAX has (the CPU tests): its line carries
no metric, because a CPU run measures nothing of the chip.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmark import cache  # noqa: E402

cache.place(ROOT)  # before JAX is imported
from benchmark import manifest, trace  # noqa: E402


def within(value: float, limit: float) -> bool:
    """value ≤ limit, where NaN is never within."""
    return bool(value <= limit)


def emit(**row):
    print(json.dumps(row), flush=True)


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def measure(cell, args, devices, rehearse: bool):
    """Set-up, window and check of one run; the result line's dict."""
    seconds = (float(cell.traffic["trace_seconds"]) if args.trace
               else args.seconds)
    tracer = trace.Tracer() if args.trace else trace.NO_TRACE
    try:
        out = cell.loop.run(cell, args.seed, seconds, tracer,
                            rehearse=rehearse)
        reduced = None if rehearse else tracer.reduce()
    finally:
        tracer.close()
    setup_s = out.t_window - T_START
    emit(workload=cell.name, seed=args.seed, setup_s=setup_s,
         **out.diagnostics)
    compared = {name: {"value": out.compared[name], "limit": lim}
                for name, lim in cell.limits.items()}
    correct = all(within(v["value"], v["limit"])
                  for v in compared.values())
    device = device_info(devices)
    device["memory_peak_bytes"] = out.diagnostics.get("memory_peak_bytes")
    result = {"correct": correct, "attempted": out.attempted,
              "failed": out.failed, "metrics": {}, "device": device}
    if rehearse:
        result["rehearsal"] = ("CPU rehearsal at tiny sizes: not a chip "
                               "measurement, no metric")
    elif args.trace:
        ctx = dict(out.context, trace=reduced, config=cell.config,
                   traffic=cell.traffic, device_kind=device["kind"])
        for m in cell.per_layer:
            v = cell.readers[m["name"]](ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    else:
        values = dict(out.values, setup_s=setup_s)
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any device; prints no metric")
    args = ap.parse_args(argv)
    cell = manifest.cell(ROOT, args.workload)

    from slate_tpu.compat.platform import enable_compile_cache

    enable_compile_cache()
    import jax

    devices = jax.devices()
    if not args.rehearse and devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}; a benchmark run "
              f"needs the chip (--rehearse is for the CPU tests)",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"{args.workload} needs {cell.chips} chips, JAX has "
              f"{len(devices)}", file=sys.stderr)
        return 2
    result = measure(cell, args, devices[:max(cell.chips, 1)],
                     args.rehearse)
    for name, v in result["compared"].items():
        print(f"compared {name} = {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
