#!/usr/bin/env python
"""Measure Mosaic compile wall-time of the panel-base kernels per height.

Round-5 finding: the in-VMEM LU/QR panel kernels are fast to EXECUTE
but were expensive to COMPILE while their column loops were Python-
unrolled (pre-fix, first-call latency at n=16384 exceeded 30 minutes
on the remote-TPU setup of rounds 2–5 and its compile helper was
OOM-killed on the 8 MB MLIR). The loops are lax.fori_loop now; this probe times
compile+first-call per height so the eligibility gates carry measured
height bounds (scoped-vmem limits, see pallas_ops._PANEL_MAX_CELLS)
instead of guesses.

Usage: python tools/panel_compile_probe.py [qr|lu] [heights_csv]
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from slate_tpu.ops import pallas_ops  # noqa: E402


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "qr"
    heights = ([int(x) for x in sys.argv[2].split(",")]
               if len(sys.argv) > 2 else [512, 1024, 2048, 4096])
    w = 32
    rng = np.random.default_rng(0)
    print(f"# {which}_panel_base compile probe on {jax.devices()[0]}")
    for h in heights:
        a = jnp.asarray(rng.standard_normal((h, w)).astype(np.float32))
        t0 = time.time()
        if which == "qr":
            out = pallas_ops.qr_panel_base(a)
        else:
            out = pallas_ops.lu_panel_base(a)
        jax.block_until_ready(out)
        t_compile = time.time() - t0
        t0 = time.time()
        if which == "qr":
            out = pallas_ops.qr_panel_base(a)
        else:
            out = pallas_ops.lu_panel_base(a)
        jax.block_until_ready(out)
        t_run = time.time() - t0
        print(f"H={h:6d}: compile+first {t_compile:8.2f} s, "
              f"cached call {t_run * 1e3:8.2f} ms", flush=True)


if __name__ == "__main__":
    main()
