"""One caller calls the configuration's verb, jitted once, back to back on
operands resident on the device; each call ends in
``block_until_ready``.

Mix parameters: ``rhs_blocks`` (right-hand sides cycle through that many
blocks made from the seed), ``checked_calls`` (answers kept for the
check, drawn from the seed, besides the last), ``trace_seconds`` (the
window of a ``--trace 1`` run).
"""

import time

import numpy as np

from benchmark import data, drive


def run(cell, seed: int, seconds: float, tracer, rehearse=False,
        control=False, held=None) -> drive.Outcome:
    """``held``: a dict that keeps the compiled program from one call of
    this function to the next in one process (``readings.py``)."""
    import jax
    import slate_tpu as st

    cfg, mix, verb = cell.config, cell.traffic, cell.verb
    n, nb = drive.sizes(cfg, rehearse)
    k, blocks = int(cfg["nrhs"]), int(mix["rhs_blocks"])
    a, bs = data.operands(seed, cell.operand.make, n, k, blocks,
                          cfg["dtype"])
    A = verb.wrap(a, nb)
    Bs = [st.from_dense(bs[i], nb=nb) for i in range(blocks)]
    opts = drive.options(cfg, control)

    def call(A, B):
        return verb.call(A, B, opts)

    call.__name__ = cfg["verb"]  # the program is jit_<verb> in a trace
    t0 = time.perf_counter()
    exe = (held or {}).get(control)
    if exe is None:
        lowered = jax.jit(call).lower(A, Bs[0])
        t1 = time.perf_counter()
        exe = lowered.compile()
        del lowered
        if held is not None:
            held[control] = exe
    else:
        t1 = t0
    t2 = time.perf_counter()
    if tracer.enabled:
        tracer.add_program(exe.as_text())
    for B in Bs[:2]:
        jax.block_until_ready(exe(A, B))
    lowerings = drive.Lowerings()
    keep = drive.Reservoir(int(mix["checked_calls"]), data.rng(seed, 3))
    infos, calls = [], 0
    t_window = time.perf_counter()
    with tracer.window():
        while True:
            j = calls % blocks
            with tracer.annotate("bench.call"):
                X, info = jax.block_until_ready(exe(A, Bs[j]))
            infos.append(info)
            keep.offer((calls, j, X))
            calls += 1
            t_end = time.perf_counter()
            if t_end - t_window >= seconds:
                break
    elapsed = t_end - t_window
    lowered = lowerings.stop()
    peak = drive.peak_bytes(jax.devices()[:cell.chips])
    # the last call and a sample of the others, drawn from the seed
    picked = {c: (j_, x) for c, j_, x in keep.items}
    picked[calls - 1] = (j, X)
    order = sorted(picked)
    xs = np.concatenate([np.asarray(picked[c][1].data)[:n, :k]
                         for c in order], axis=1)
    failed = int((np.asarray(jax.device_get(infos)) != 0).sum())
    a_host, bs_host = np.asarray(a), np.asarray(bs)
    rhs = np.concatenate([bs_host[picked[c][0]] for c in order], axis=1)
    del A, Bs, exe, a, bs, X, keep, picked, infos
    res = cell.check.compare(a_host, xs, rhs, cfg["dtype"])
    return drive.Outcome(
        t_window=t_window,
        values={"solve_ms": 1e3 * elapsed / calls},
        attempted=calls, failed=failed,
        compared={"residual_max": float(res.max()),
                  "calls_failed": failed},
        context={"calls": calls, "program": "jit_" + cfg["verb"],
                 "work": verb.cost(n, k, np.dtype(cfg["dtype"]).itemsize)},
        diagnostics={"lower_s": t1 - t0, "compile_s": t2 - t1,
                     "calls": calls,
                     "lowered_in_window": lowered,
                     "window_s": elapsed, "checked_calls": len(order),
                     "residual_median": float(np.median(res)),
                     "memory_peak_bytes": peak})
