#!/usr/bin/env python
"""Bring-up smoke: drive slate-tpu's main path once on the chip.

One process, public entry points only (the ``slate_tpu`` verbs,
``ProcessGrid``, ``Session``, ``Executor``), random operands from
``slate_tpu.matgen`` with fixed seeds. Every answer is held to its
LAPACK-style ε-scaled bound, the formulas of ``slate_tpu/tester.py``
(``_solve_err``, ``_t_gels``, ``_t_heev_vec``), computed on the host in
float64.

    python chip_smoke.py              # one v5e chip
    python chip_smoke.py --full       # one chip: the phases the default
                                      # run cuts, at full size
    python chip_smoke.py --chips 4    # the 2x2 host: sharded posv/gesv
                                      # and a mesh Session vs a 1x1 grid
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny [--chips 4]

Without ``--tiny`` a device that is not a TPU is an error. At n=16384
each program takes minutes to compile; on the 2x2 host (140 GiB) the
verbs' programs compile on a thread pool while the main thread serves
(XLA compiles without the GIL), so there a phase's ``compile_s`` is
wall time shared with other compiles. Earlier output lines are
diagnostics, one JSON object per phase: compile and run seconds on the
host clock around ``block_until_ready``, the residual beside its bound,
the Pallas kernel call sites in the compiled program. The last line is
the contract line ``{"ok": ..., "device": {"platform", "kind",
"count"}}``; any failed phase or violated bound exits 1 with
``"ok": false``.
"""

import argparse
import json
import resource
import sys
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# Shapes, (n, nb) or (m, n, nb). A cold run must finish in 1200 s on
# one v5e chip, and compiling dominates it: the chip's host compiled
# posv at n=16384 in 162-199 s (+50-160 s to lower), and each n=16384
# program peaks near 26 GiB of its host's 40 GiB, so compiles run one
# at a time. The default run keeps the bring-up issue's posv and heev
# (16384, 4096) and cuts gesv, gels and the served operators to sizes
# whose programs compile in 1-2 minutes; --full runs those three at
# the issue's sizes, as a call of its own (PERF.md, "Cells").
ONE_CHIP = dict(posv=(16384, 1024), gesv=(4096, 1024),
                gels=(8192, 2048, 512), heev=(4096, 256),
                served=(4096, 256), nrhs=16, requests=16)
FULL = dict(gesv=(16384, 1024), gels=(32768, 8192, 512),
            served=(16384, 1024), nrhs=16, requests=16)
# 2x2 vs 1x1 (--chips 4): the sharded programs compile slower still
# (posv 2x2 at n=8192: 190 s here, beside two other compiles)
FOUR_CHIPS = dict(posv=(4096, 1024), gesv=(4096, 1024), nrhs=16)
TINY = dict(posv=(512, 128), gesv=(512, 128), gels=(1024, 256, 128),
            heev=(256, 64), served=(512, 128), nrhs=16, requests=16)
TOL = 3.0  # tester.DEFAULT_TOL: residual / (ε·n·norms) must stay below


def emit(**row):
    print(json.dumps(row), flush=True)


def _f64(v):
    return np.asarray(v, dtype=np.float64)


def _scaled(a, r, x, eps):
    """max over columns of ‖r‖₁ / (ε·n·‖A‖₁·‖x‖₁), in float64."""
    a, r, x = _f64(a), _f64(r), _f64(x)
    den = eps * a.shape[1] * np.linalg.norm(a, 1) * np.maximum(
        np.abs(x).sum(axis=0), 1e-300)
    return float((np.abs(r).sum(axis=0) / den).max())


def solve_err(a, x, b, eps):
    """tester._solve_err, ‖b − A·x‖ scaled, for each column of x (one
    request each): the largest."""
    a = _f64(a)
    return _scaled(a, _f64(b) - a @ _f64(x), x, eps)


def diff_err(a, x1, x2, eps):
    """‖A·(x₁ − x₂)‖ scaled like solve_err: two backward-stable answers
    to one system agree within twice the solve bound."""
    a = _f64(a)
    return _scaled(a, a @ (_f64(x1) - _f64(x2)), x1, eps)


def lsq_err(a, x, b, eps):
    """‖Aᴴ(A·x − b)‖₁ / (ε·m·‖A‖₁²·‖x‖₁) — tester._t_gels."""
    a, x, b = _f64(a), _f64(x), _f64(b)
    den = eps * a.shape[0] * np.linalg.norm(a, 1) ** 2 * max(
        np.linalg.norm(x, 1), 1e-300)
    return float(np.linalg.norm(a.T @ (a @ x - b), 1) / den)


def memory(devices, key):
    return [(d.memory_stats() or {}).get(key) for d in devices]


def timed_run(fn, *args):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


class Compiles:
    """Counts the programs JAX lowers, in any thread (a jit cache miss,
    an AOT compile, an eager op at a new shape): a served request that
    lowers one was not covered by warmup."""

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.n += 1


def _compile(lowered, name):
    t0 = time.perf_counter()
    exe = lowered.compile()
    secs = time.perf_counter() - t0
    emit(compiled=name, compile_s=secs)  # progress, should a run be cut
    return exe, secs


class Verb:
    """One library-verb program: its operands are made on the device
    once and kept on the host (for the check, and to rebuild them for
    the run); its program is lowered now and compiled on the pool, or
    when it runs where there is no pool."""

    def __init__(self, sm, name, verb, host, build, grid=None):
        import jax

        self.name, self.host, self.build = name, host, build
        self.devs = (list(grid.mesh.devices.flat) if grid is not None
                     else jax.devices()[:1])
        self.error = None
        try:
            abstract = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding),
                build())
            t0 = time.perf_counter()
            self.lowered = jax.jit(verb).lower(*abstract)
            self.lower_s = time.perf_counter() - t0
            self.compiled = (sm.pool.submit(_compile, self.lowered, name)
                             if sm.pool else None)
        except Exception as e:  # raised again where its phase runs
            traceback.print_exc()
            self.error = e

    def run(self):
        """(output, row): compile wait, first run, warm run."""
        if self.error is not None:
            raise self.error
        exe, compile_s = (self.compiled.result() if self.compiled
                          else _compile(self.lowered, self.name))
        args = self.build()
        placed = memory(self.devs, "bytes_in_use")
        _, first_s = timed_run(exe, *args)
        out, warm_s = timed_run(exe, *args)
        del args
        return out, dict(lower_s=self.lower_s, compile_s=compile_s,
                         first_run_s=first_s, warm_run_s=warm_s,
                         tpu_custom_call=exe.as_text().count(
                             "tpu_custom_call"),
                         bytes_in_use_placed=placed)


class Smoke:
    def __init__(self, sizes, tiny, workers):
        import jax
        import jax.numpy as jnp

        self.jax, self.jnp = jax, jnp
        self.s = sizes
        self.tiny = tiny
        self.eps = float(jnp.finfo(jnp.float32).eps)
        self.pool = ThreadPoolExecutor(workers) if workers else None
        self.failed = []
        self.compiles = Compiles()

    def phase(self, name, fn, need_kernel=False):
        """Run one phase; its row carries ``checks``, (value, bound)
        pairs that must all hold."""
        t0 = time.perf_counter()
        try:
            row = fn()
        except Exception as e:  # report, go on: one call finds every fault
            traceback.print_exc()
            self.failed.append(name)
            emit(phase=name, ok=False, error=f"{type(e).__name__}: {e}",
                 wall_s=time.perf_counter() - t0)
            return None
        checks = row.pop("checks")
        ok = all(v <= bound for v, bound in checks)
        if need_kernel and not self.tiny and not row.get("tpu_custom_call"):
            ok = False
            row["error"] = "no Pallas kernel in the compiled program"
        if not ok:
            self.failed.append(name)
        emit(phase=name, ok=ok, wall_s=time.perf_counter() - t0,
             **{k: v for k, v in row.items() if not k.startswith("_")})
        return row

    # -- operands (device-made, kept on the host) --------------------------

    def spd(self, n, seed):
        from slate_tpu.matgen import random_spd
        return np.asarray(random_spd(n, dtype=self.jnp.float32, seed=seed))

    def randn(self, m, n, seed):
        from slate_tpu.matgen import generate_matrix
        return np.asarray(generate_matrix("randn", m, n, self.jnp.float32,
                                          seed=seed))

    # -- library verbs -----------------------------------------------------

    def posv(self, grid=None, seed=1, tag=""):
        import slate_tpu as st

        (n, nb), k = self.s["posv"], self.s["nrhs"]
        a, b = self.spd(n, seed), self.randn(n, k, seed + 1)

        def build():
            return (st.hermitian(self.jnp.tril(a), nb=nb,
                                 uplo=st.Uplo.Lower, grid=grid),
                    st.from_dense(b, nb=nb, grid=grid))
        return Verb(self, "posv" + tag, st.posv, (a, b), build, grid)

    def gesv(self, grid=None, seed=3, tag=""):
        import slate_tpu as st

        (n, nb), k = self.s["gesv"], self.s["nrhs"]
        a, b = self.randn(n, n, seed), self.randn(n, k, seed + 1)

        def build():
            return (st.from_dense(a, nb=nb, grid=grid),
                    st.from_dense(b, nb=nb, grid=grid))
        return Verb(self, "gesv" + tag, st.gesv, (a, b), build, grid)

    def gels(self, seed=5):
        import slate_tpu as st

        m, n, nb = self.s["gels"]
        a, b = self.randn(m, n, seed), self.randn(m, self.s["nrhs"],
                                                  seed + 1)

        def build():
            return st.from_dense(a, nb=nb), st.from_dense(b, nb=nb)
        return Verb(self, "gels", st.gels, (a, b), build)

    def solved(self, v):
        """Run a posv/gesv Verb and check it (row keeps ``_x``)."""
        (X, info), row = v.run()
        a, b = v.host
        x = X.to_numpy()
        res = solve_err(a, x, b, self.eps)
        row.update(n=a.shape[0], nrhs=b.shape[1], info=int(info),
                   residual=res, bound=TOL, _x=x,
                   checks=[(res, TOL), (abs(int(info)), 0)])
        return row

    def least_squares(self, v):
        X, row = v.run()
        a, b = v.host
        res = lsq_err(a, X.to_numpy()[:a.shape[1]], b, self.eps)
        row.update(m=a.shape[0], n=a.shape[1], nrhs=b.shape[1],
                   residual=res, bound=TOL, checks=[(res, TOL)])
        return row

    def heev(self, seed=7):
        import slate_tpu as st
        from slate_tpu.matgen import generate_matrix

        n, nb = self.s["heev"]
        a = generate_matrix("heev_arith", n, n, self.jnp.float32,
                            seed=seed, cond=100.0)
        A = st.hermitian(self.jnp.tril(a), nb=nb, uplo=st.Uplo.Lower)
        an = _f64(a)
        del a
        # heev orchestrates stedc from the host: not one jittable
        # program, so the first call carries its compiles
        _, first_s = timed_run(st.heev, A)
        (w, Z), warm_s = timed_run(st.heev, A)
        wn, z = _f64(w), _f64(Z.to_numpy())
        scale = self.eps * n * max(np.abs(wn).max(), 1e-300)
        res = float(np.abs(an @ z - z * wn).max() / scale)
        orth = float(np.abs(z.T @ z - np.eye(n)).max() / (self.eps * n))
        werr = float(np.abs(np.sort(wn) - np.linalg.eigvalsh(an)).max()
                     / scale)
        return dict(n=n, nb=nb, first_call_s=first_s, warm_run_s=warm_s,
                    residual=res, orthogonality=orth, eig_err=werr,
                    bound=TOL,
                    checks=[(res, TOL), (orth, TOL), (werr, TOL)])

    # -- the served path ---------------------------------------------------

    def served(self, mesh=None, dense=None, bs=None, seed=11):
        """Session + Executor: register one chol and one lu operator
        (or the ``dense`` {op: matrix} given), warm them (factor + AOT
        compile), then single-RHS requests (the columns of ``bs``)
        through Executor.submit."""
        import slate_tpu as st
        from slate_tpu.runtime import Executor, Session

        n, nb = self.s["posv" if mesh is not None else "served"]
        if dense is None:
            dense = {"chol": self.spd(n, seed),
                     "lu": self.randn(n, n, seed + 1)}
        if bs is None:
            bs = self.randn(n, self.s["requests"], seed + 100)
        ops, nreq = list(dense), bs.shape[1]
        sess = Session(mesh=mesh)
        handles = {}
        for op, a in dense.items():
            A = (st.hermitian(self.jnp.tril(a), nb=nb, uplo=st.Uplo.Lower)
                 if op == "chol" else st.from_dense(a, nb=nb))
            handles[op] = sess.register(A, op=op)
            del A
        with Executor(sess) as ex:
            t0 = time.perf_counter()
            ex.warmup(list(handles.values()))
            warm_s = time.perf_counter() - t0
            lowered = self.compiles.n
            t0 = time.perf_counter()
            futs = {op: [ex.submit(h, bs[:, j]) for j in range(nreq)]
                    for op, h in handles.items()}
            xs = {op: np.stack([np.asarray(f.result()).reshape(n)
                                for f in fl], axis=1)
                  for op, fl in futs.items()}
            serve_s = time.perf_counter() - t0
            lowered = self.compiles.n - lowered
        per_op = {op: solve_err(dense[op], xs[op], bs, self.eps)
                  for op in ops}
        # the resident programs the Session compiled (warmup's AOT seam)
        kernels = sum(e.as_text().count("tpu_custom_call")
                      for e in getattr(sess, "_compiled", {}).values()
                      if hasattr(e, "as_text"))
        sess.close()
        return dict(n=n, nb=nb, ops=ops, requests_per_op=nreq,
                    warmup_s=warm_s,
                    compile_s=sum(r["compile_s"] for r in sess.compile_log),
                    programs_compiled=len(sess.compile_log),
                    serve_s=serve_s, lowered_while_serving=lowered,
                    residual_max=per_op, bound=TOL,
                    tpu_custom_call=kernels, _xs=xs,
                    checks=[(r, TOL) for r in per_op.values()]
                    + [(lowered, 0)])


def run_one_chip(sm):
    # the verbs' programs are lowered first and compiled in their phase
    posv, gesv, gels = sm.posv(), sm.gesv(), sm.gels()
    sm.phase("served", sm.served, need_kernel=True)
    sm.phase("heev", sm.heev)
    sm.phase("posv", lambda: sm.solved(posv), need_kernel=True)
    sm.phase("gesv", lambda: sm.solved(gesv), need_kernel=True)
    sm.phase("gels", lambda: sm.least_squares(gels), need_kernel=True)


def run_full(sm):
    """The phases the default run cuts, at the bring-up issue's sizes,
    and no other (cheapest compile first)."""
    sm.phase("served", sm.served, need_kernel=True)
    sm.phase("gesv", lambda: sm.solved(sm.gesv()), need_kernel=True)
    # every panel of the 32768-row operand is taller than the QR
    # kernels' cells budget: this program runs the XLA panel bases
    sm.phase("gels", lambda: sm.least_squares(sm.gels()))


def run_four_chips(sm):
    """2x2 grid: sharded posv/gesv and a mesh Session, compared with
    the same seeds on a 1x1 grid on device 0 (the served chol operator
    is posv's matrix, its requests posv's right-hand sides, so the
    1x1 posv answers it too)."""
    import jax
    import slate_tpu as st

    devs = jax.devices()[:4]
    grid = st.ProcessGrid.create(2, 2, devices=devs)
    one = st.ProcessGrid.create(1, 1, devices=devs[:1])
    emit(phase="mesh",
         ids=[[d.id for d in row] for row in grid.mesh.devices],
         coords={d.id: list(getattr(d, "coords", None) or [])
                 for d in devs},
         neighbours=_mesh_is_local(grid.mesh.devices))
    # lowered in order of compile time, the longest first: the pool
    # starts each compile as soon as its program is lowered
    verbs = {(name, g.size): getattr(sm, name)(grid=g, tag=f"_{g.p}x{g.q}")
             for name, g in (("gesv", grid), ("posv", grid),
                             ("gesv", one), ("posv", one))}
    a, b = verbs["posv", 1].host
    served = sm.phase("served_chol_2x2", lambda: sm.served(
        mesh=grid, dense={"chol": a}, bs=b))
    rows = {}
    for name in ("posv", "gesv"):
        def go(name=name):
            r4 = sm.solved(verbs[name, 4])
            r1 = sm.solved(verbs[name, 1])
            rows[name] = r1
            return _compared(sm, r4, r1, r4["_x"], r1["_x"],
                             verbs[name, 1].host[0],
                             ("info", "compile_s", "first_run_s",
                              "warm_run_s", "residual", "tpu_custom_call",
                              "bytes_in_use_placed"))
        sm.phase(f"{name}_2x2", go)
    if served is not None and "posv" in rows:
        sm.phase("served_chol_2x2_vs_posv_1x1", lambda: _compared(
            sm, served, rows["posv"], served["_xs"]["chol"],
            rows["posv"]["_x"], a, ()))


def _compared(sm, r4, r1, x4, x1, a, keys):
    """Both grids' rows side by side, plus their agreement: each column
    pair within twice the solve bound (diff_err)."""
    diff = diff_err(a, x1, x4, sm.eps)
    return {"n": r4["n"], "grid2x2": {k: r4[k] for k in keys},
            "grid1x1": {k: r1[k] for k in keys}, "agreement": diff,
            "agreement_bound": 2 * TOL,
            "checks": [(diff, 2 * TOL)] + (
                r4["checks"] + r1["checks"] if keys else [])}


def _mesh_is_local(devices):
    """True when every pair the mesh axes join sits one ICI hop apart
    (None where the devices report no coordinates, as on the CPU)."""
    arr = np.asarray(devices)
    for ax in (0, 1):
        for x, y in zip(np.take(arr, 0, axis=ax).ravel(),
                        np.take(arr, 1, axis=ax).ravel()):
            cx, cy = getattr(x, "coords", None), getattr(y, "coords", None)
            if not cx or not cy:
                return None
            if sum(abs(p - q) for p, q in zip(cx, cy)) != 1:
                return False
    return True


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true",
                    help="small sizes on whatever device JAX finds "
                         "(the CPU rehearsal)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: the 2x2-grid phases and their 1x1 "
                         "comparison, and no other phase")
    ap.add_argument("--full", action="store_true",
                    help="one chip: gesv, gels and the served operators "
                         "at the sizes the default run cuts, and no "
                         "other phase")
    args = ap.parse_args(argv)
    if args.full and (args.chips != 1 or args.tiny):
        ap.error("--full runs on one chip at full size")

    from slate_tpu.compat.platform import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"# jax {jax.__version__}, {device}, compile cache {cache}",
          flush=True)
    if not args.tiny and device["platform"] != "tpu":
        emit(ok=False, error=f"no TPU: JAX found {device['platform']}",
             device=device)
        return 1
    if len(devs) < args.chips:
        emit(ok=False, error=f"--chips {args.chips} needs "
                             f"{args.chips} devices, have {len(devs)}",
             device=device)
        return 1
    sizes = (TINY if args.tiny else FOUR_CHIPS if args.chips == 4
             else FULL if args.full else ONE_CHIP)
    # threads compiling the verbs beside the main thread: none on one
    # chip (two n=16384 compiles at once overran its host's 40 GiB),
    # three on the 2x2 host (140 GiB; 56 GiB peak measured, PR 21)
    sm = Smoke(sizes, args.tiny, 3 if args.chips == 4 else 0)
    t0 = time.perf_counter()
    try:
        (run_four_chips if args.chips == 4 else run_full if args.full
         else run_one_chip)(sm)
    finally:
        if sm.pool:
            sm.pool.shutdown(wait=True)
    emit(phase="total", wall_s=time.perf_counter() - t0,
         peak_bytes_in_use=memory(devs[:args.chips], "peak_bytes_in_use"),
         host_peak_rss_gib=resource.getrusage(
             resource.RUSAGE_SELF).ru_maxrss / 2 ** 20,
         failed=sm.failed)
    emit(ok=not sm.failed, device=device)
    return 0 if not sm.failed else 1


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SystemExit:
        raise
    except Exception as e:  # the last line stays JSON
        traceback.print_exc()
        print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}"}))
        sys.exit(1)
