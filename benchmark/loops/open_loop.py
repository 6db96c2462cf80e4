"""Single-column requests served from one resident factor: the operand is
registered in a ``Session`` under its verb's factorization and warmed,
an ``Executor`` serves it, and requests arrive open loop with Poisson
gaps (``data.poisson_offsets``), each timed from the moment it was due.

Mix parameters: ``rate_per_s``, ``max_batch`` and ``max_wait_s`` (the
Executor's), ``checked_requests`` (answers checked, drawn from the seed,
besides the slowest), ``drain_s`` (how long past the window's close an
answer may still come), ``trace_seconds``.
"""

import time

import numpy as np

from benchmark import data, drive


def run(cell, seed: int, seconds: float, tracer, rehearse=False,
        control=False, held=None) -> drive.Outcome:
    """``held``: a dict that keeps the Session and its Executor from one
    call of this function to the next in one process (``readings.py``,
    ``sweep.py``); each call registers its own operator."""
    import jax
    from slate_tpu.runtime import Executor, Session

    cfg, mix = cell.config, cell.traffic
    n, nb = drive.sizes(cfg, rehearse)
    a, _ = data.operands(seed, cell.operand.make, n, 1, 1, cfg["dtype"])
    offsets = data.poisson_offsets(seed, float(mix["rate_per_s"]), seconds)
    count = len(offsets)
    warm = int(mix["max_batch"])
    rhs = data.request_rhs(seed, count + warm, n, cfg["dtype"])
    if held is not None and control in held:
        sess, ex = held[control]
    else:
        sess = Session()
        ex = Executor(sess, max_batch=int(mix["max_batch"]),
                      max_wait=float(mix["max_wait_s"]))
        if held is not None:
            held[control] = sess, ex
    try:
        t0 = time.perf_counter()
        h = sess.register(cell.verb.wrap(a, nb), op=cell.verb.FACTOR,
                          opts=drive.options(cfg, control))
        ex.warmup([h])
        t1 = time.perf_counter()
        # the request path once, host pad and crop included
        for f in [ex.submit(h, rhs[count + i]) for i in range(warm)]:
            f.result()
        lowerings = drive.Lowerings()
        before = sess.metrics.snapshot()
        done = np.full(count, np.nan)
        futs = []

        def finished(i):
            def cb(_):
                done[i] = time.perf_counter()
            return cb

        half = seconds / 2.0
        backlog = {}
        t_window = time.perf_counter() + 0.01
        due = t_window + offsets
        submitted = np.empty(count)
        with tracer.window():
            for i in range(count):
                wait = due[i] - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                if "middle" not in backlog and offsets[i] >= half:
                    backlog["middle"] = i - int(np.isfinite(done).sum())
                with tracer.annotate("bench.submit"):
                    submitted[i] = time.perf_counter()
                    f = ex.submit(h, rhs[i])
                f.add_done_callback(finished(i))
                futs.append(f)
            t_close = t_window + seconds
            wait = t_close - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            backlog["end"] = count - int(np.isfinite(done).sum())
            deadline = t_close + float(mix["drain_s"])
            with tracer.annotate("bench.wait"):
                for f in futs:
                    try:
                        f.exception(timeout=max(
                            0.0, deadline - time.perf_counter()))
                    except TimeoutError:  # never answered: failed below
                        pass
        t_drained = time.perf_counter()
        lowered = lowerings.stop()
        after = sess.metrics.snapshot()
        peak = drive.peak_bytes(jax.devices()[:cell.chips])
    finally:
        if held is None:
            ex.shutdown()
    answers, failed = [], 0
    for f in futs:
        ok = f.done() and f.exception(timeout=0) is None
        answers.append(f.result() if ok else None)
        failed += not ok
    lat = np.where(np.isfinite(done), done, t_drained) - due
    pick = data.rng(seed, 4).choice(count, size=min(int(
        mix["checked_requests"]), count), replace=False)
    pick = np.union1d(pick, [int(np.argmax(lat))])
    good = [i for i in pick if answers[i] is not None
            and np.shape(answers[i]) == (n,)]
    a_host = np.asarray(a)
    if held is None:
        sess.close()
    else:
        sess.unregister(h)
    del sess, ex, a, futs
    res = (cell.check.compare(a_host,
                              np.stack([answers[i] for i in good], 1),
                              rhs[good].T, cfg["dtype"])
           if good else np.array([np.inf]))
    wrong_shape = len(pick) - len(good) - sum(answers[i] is None
                                              for i in pick)
    late = submitted - due
    return drive.Outcome(
        t_window=t_window,
        values={"served_p50_ms": 1e3 * drive.nearest_rank(lat, 0.50),
                "served_p99_ms": 1e3 * drive.nearest_rank(lat, 0.99)},
        attempted=count, failed=failed,
        compared={"residual_max": float(res.max()),
                  "requests_failed": failed,
                  "answers_malformed": int(wrong_shape)},
        context={"before": before, "after": after, "requests": count,
                 "factor": cell.verb.FACTOR},
        diagnostics={"register_and_warmup_s": t1 - t0,
                     "lowered_in_window": lowered,
                     "requests": count, "rate_per_s": mix["rate_per_s"],
                     "checked_requests": len(pick),
                     "residual_median": float(np.median(res)),
                     "backlog_middle": backlog.get("middle"),
                     "backlog_end": backlog.get("end"),
                     "late_p50_ms": 1e3 * drive.nearest_rank(late, 0.5),
                     "late_p99_ms": 1e3 * drive.nearest_rank(late, 0.99),
                     "late_max_ms": 1e3 * float(late.max()),
                     "drain_s": t_drained - t_close,
                     "memory_peak_bytes": peak})
