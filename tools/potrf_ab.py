#!/usr/bin/env python
"""A/B the potrf inner variants on-chip (round 5).

The round-4 iterative potrf (_potrf_iter) measured SLOWER on the chip
than the round-3 recursion it replaced (218 vs 141 ms/iter at n=16384,
nb=1024) despite doing strictly less redundant work on paper. This
script isolates the cause by timing, with bench.py's slope methodology:

  rec          the 2x2 recursion (_potrf_rec, the r3 default)
  iter         the r4 iterative loop (current default)
  iter_shrink  iterative, but carrying ONLY the shrinking trailing
               block (no full-matrix dynamic_update_slice per step;
               finished panel columns are assembled once at the end) —
               distinguishes "DUS full-array traffic" from "per-step
               kernel latency" as the regression cause
  iter_trsm    the r4 loop with the panel computed by trsm_rec instead
               of trtri_lower_batched + gemm — isolates the batched
               leaf-inverse kernel's cost

Usage: python tools/potrf_ab.py [n] [nb] [variants_csv]
"""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import _per_iter_seconds  # noqa: E402


def _variants():
    from slate_tpu.linalg.cholesky import (_potrf_iter, _potrf_rec,
                                           _tile_chol)
    from slate_tpu.ops import blocked

    def iter_shrink(a, nb, prec):
        s = a.shape[0]
        nt = s // nb
        info = jnp.zeros((), jnp.int32)
        cols = []
        t = a
        for k in range(nt):
            lkk, tinfo = _tile_chol(t[:nb, :nb])
            info = jnp.where((info == 0) & (tinfo > 0), k * nb + tinfo,
                             info).astype(jnp.int32)
            if t.shape[0] == nb:
                cols.append(lkk)
                break
            inv = blocked.trtri_lower_batched(lkk)
            pan = blocked.mm(t[nb:, :nb], jnp.conj(inv).T, prec)
            cols.append(jnp.concatenate([lkk, pan], axis=0))
            t = blocked.herk_lower_rec(t[nb:, nb:], pan, prec=prec)
        padded = [jnp.pad(c, ((s - c.shape[0], 0), (0, 0)))
                  for c in cols]
        return jnp.concatenate(padded, axis=1), info

    def iter_trsm(a, nb, prec):
        s = a.shape[0]
        nt = s // nb
        info = jnp.zeros((), jnp.int32)
        for k in range(nt):
            k0, k1 = k * nb, (k + 1) * nb
            lkk, tinfo = _tile_chol(a[k0:k1, k0:k1])
            info = jnp.where((info == 0) & (tinfo > 0), k0 + tinfo,
                             info).astype(jnp.int32)
            a = jax.lax.dynamic_update_slice(a, lkk, (k0, k0))
            if k1 >= s:
                continue
            pan = blocked.trsm_rec(lkk, a[k1:, k0:k1], left=False,
                                   lower=True, conj_a=True, trans_a=True,
                                   prec=prec, base=nb)
            a = jax.lax.dynamic_update_slice(a, pan, (k1, k0))
            trail = blocked.herk_lower_rec(a[k1:, k1:], pan, prec=prec)
            a = jax.lax.dynamic_update_slice(a, trail, (k1, k1))
        return a, info

    return {"rec": _potrf_rec, "iter": _potrf_iter,
            "iter_shrink": iter_shrink, "iter_trsm": iter_trsm}


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 16384
    nb = int(sys.argv[2]) if len(sys.argv) > 2 else 1024
    names = sys.argv[3].split(",") if len(sys.argv) > 3 else None

    from slate_tpu.matgen import random_spd

    a0 = jnp.tril(random_spd(n, dtype=jnp.float32, seed=3))
    a0 = a0 + n * jnp.eye(n, dtype=jnp.float32)
    plat = jax.devices()[0].platform
    res = {"platform": plat, "n": n, "nb": nb}
    print(f"# platform={plat} n={n} nb={nb}", file=sys.stderr)

    variants = _variants()
    # correctness probe: every variant must factor a small problem to
    # the same residual as the first (run at a probe size so the check
    # is always on — a broken variant must not publish timings)
    np_ = min(n, 2048)
    nbp = min(nb, np_ // 2)
    ap = jnp.tril(random_spd(np_, dtype=jnp.float32, seed=5))
    ap = ap + np_ * jnp.eye(np_, dtype=jnp.float32)
    full = ap + jnp.tril(ap, -1).T
    # the reference residual is ALWAYS the rec variant's — a partial
    # variants_csv must not let a broken variant self-certify
    out, _ = jax.jit(
        lambda x: variants["rec"](x, nbp, "high"))(ap)
    lref = jnp.tril(out)
    ref = float(jnp.linalg.norm(lref @ lref.T - full))
    for name, fn in variants.items():
        if names and name not in names:
            continue
        out, _ = jax.jit(lambda x, f=fn: f(x, nbp, "high"))(ap)
        l = jnp.tril(out)
        r = float(jnp.linalg.norm(l @ l.T - full))
        print(f"# {name}: probe residual {r:.3e}", file=sys.stderr)
        if not (r <= 10 * ref + 1e-30):
            raise SystemExit(f"variant {name} FAILS the probe: "
                             f"residual {r:.3e} vs ref {ref:.3e}")
        def step(c, cs, f=fn):
            out, _ = f(c, nb, "high")
            return c + 1e-30 * out
        t = _per_iter_seconds(step, a0, (), k1=2, k2=6)
        gf = (n ** 3 / 3.0) / 1e9 / t
        res[f"{name}_ms"] = round(t * 1e3, 1)
        res[f"{name}_gflops"] = round(gf, 1)
        print(f"# {name:12s} {t*1e3:8.1f} ms  {gf:9.1f} GFLOP/s",
              file=sys.stderr)

    print(json.dumps(res))


if __name__ == "__main__":
    main()
