#!/usr/bin/env python
"""Offline config search → the committed tuning table (round 21).

Sweeps the per-op knob space the runtime actually consumes —
``nb`` / ``inner_blocking`` / ``lookahead`` / wide-panel width for the
dense one-shot drivers, ``nb`` and the batch/width bucket quantum for
the small batched engine — per (op, pow2-n-bucket, dtype, platform).
Every candidate is AOT-compiled ONCE (compiles are counted into the
artifact — the search's own cost is part of the record), then
slope-timed: seconds(k2 iters) − seconds(k1 iters) over (k2 − k1)
cancels the per-call dispatch constant, the same measurement
discipline bench.py --phases uses. The score joins the measured slope
against the roofline cost model (obs/costs.py ``score_measured`` →
model-flops GFLOP/s, intensity, roof fraction) and the argmax-GFLOP/s
candidate per (op, n-bucket, dtype) becomes one table entry.

The output document (default: the committed repo-root
``TUNING_r01.json``) carries the declared schema
``slate_tpu.tuning_table.v1``; ``tools/bench_gate.py --check-schema``
validates it with a jax-free mirror and ``slate_tpu/tuning/table.py``
loads it at serve time — one file, two readers, one schema.

Determinism: fixed ``--seed`` derives every operand; candidate order
is the declared grid order; ties break to the earlier candidate; the
document carries no timestamps — the same seed on the same platform
writes the same bytes (pinned in tests/test_tuning.py with an
injected measure function).

NEVER run from tier-1: the committed table is the fixture tests load;
regenerating it is a deliberate, platform-stamped act. A table
generated on a host CPU is honestly stamped ``"platform": "cpu"`` —
serving sessions on TPU will not resolve through it (first-match
requires the platform to match or be ``"*"``), which is exactly
right: CPU-smoke timings must never steer TPU configs.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

def main(argv=None) -> int:
    from slate_tpu.tuning.search import (DEFAULT_OPS, run_search)
    from slate_tpu.tuning.table import (TUNING_FILENAME, table_path,
                                        validate_table)

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--ops", nargs="+", default=list(DEFAULT_OPS),
                   help=f"ops to sweep (default: {' '.join(DEFAULT_OPS)})")
    p.add_argument("--n", type=int, nargs="+", default=[64],
                   dest="n_buckets", metavar="N",
                   help="pow2 n-bucket ceilings: each table entry "
                        "matches problems with n <= its bucket "
                        "(default: 64 — the tier-1-budget shape)")
    p.add_argument("--dtypes", nargs="+", default=["float32"])
    p.add_argument("--seed", type=int, default=0,
                   help="operand seed (the determinism pin)")
    p.add_argument("--quick", action="store_true",
                   help="reduced candidate grid (CPU-smoke scale)")
    p.add_argument("--out", default=None, metavar="PATH",
                   help="output table path (default: the committed "
                        f"repo-root {TUNING_FILENAME})")
    args = p.parse_args(argv)

    out = table_path() if args.out is None else args.out

    def log(msg):
        print(f"# {msg}", file=sys.stderr)

    doc = run_search(ops=tuple(args.ops),
                     n_buckets=tuple(args.n_buckets),
                     dtypes=tuple(args.dtypes),
                     seed=args.seed, quick=args.quick, log=log)
    errs = validate_table(doc)
    if errs:  # a search emitting an invalid table is a search bug
        print(json.dumps({"ok": False, "schema_errors": errs}))
        return 1
    with open(out, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps({
        "out": out, "platform": doc["platform"],
        "entries": len(doc["entries"]),
        "total_compiles": doc["search"]["total_compiles"],
        "ok": True,
    }, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
