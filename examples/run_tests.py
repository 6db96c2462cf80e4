"""Smoke-run all examples + the tester mesh sweep (reference:
examples/run_tests.py — doubles as an API regression test; the --grid
sweep is the `mpirun -np 8 tester` artifact of SURVEY §4, run on the
8-device virtual CPU mesh)."""

import importlib.util
import os
import pathlib
import subprocess
import sys


def _platform_mod():
    """compat/platform.py loaded standalone (keeps jax out of this
    parent process; the children initialize their own backends)."""
    spec = importlib.util.spec_from_file_location(
        "_slate_tpu_platform",
        str(pathlib.Path(__file__).parent.parent / "slate_tpu" / "compat"
            / "platform.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

# the multi-process tester artifact: a 2×4 virtual-mesh sweep over one
# representative routine per family (VERDICT r3 #7 — the reference's
# tester IS the mpirun evidence; examples/mesh_sweep.log records a run)
MESH_SWEEP = [
    sys.executable, "-u", "-m", "slate_tpu.tester",
    "--routine", "gemm,posv,gesv,gels,heev,hetrf,stedc_grid,redistribute",
    "--n", "256", "--nb", "64", "--p", "2", "--q", "4",
]


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    here = pathlib.Path(__file__).parent
    env = dict(os.environ)
    env["PYTHONPATH"] = (str(here.parent) + os.pathsep
                         + env.get("PYTHONPATH", ""))
    fails = 0
    if "--mesh-sweep" in argv or "--all" in argv:
        env_sweep = dict(env)
        env_sweep["JAX_PLATFORMS"] = "cpu"
        flags = env_sweep.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            flags = (flags
                     + " --xla_force_host_platform_device_count=8").strip()
            # unknown XLA_FLAGS abort the process on some jaxlib
            # builds; the probe-gated helper adds the rendezvous-
            # timeout raise only where it exists
            flags += _platform_mod().collective_timeout_flag_if_supported(
                cache_path=str(here.parent / ".xla_flag_probe.json"))
            env_sweep["XLA_FLAGS"] = flags
        print("=== tester mesh sweep (2x4 virtual CPU mesh) ===")
        r = subprocess.run(MESH_SWEEP, cwd=here.parent, env=env_sweep)
        if r.returncode != 0:
            fails += 1
            print("!!! mesh sweep FAILED")
        if "--mesh-sweep" in argv:
            return fails
    env_ex = dict(env)
    # smoke runs target the CPU backend: fast compiles, and the
    # complex-dtype paths in ex03/ex04 (zheev, zgesv) are verified on
    # the CPU (TPU complex support is partial)
    env_ex.setdefault("JAX_PLATFORMS", "cpu")
    for ex in sorted(here.glob("ex*.py")):
        print(f"=== {ex.name} ===")
        r = subprocess.run([sys.executable, str(ex)], cwd=here.parent,
                           env=env_ex)
        if r.returncode != 0:
            fails += 1
            print(f"!!! {ex.name} FAILED")
    # serving-runtime smoke: exercises Session/Executor/metrics end to
    # end and asserts cached-factor serving beats per-request
    # factor+solve (bench_serve.py exits nonzero otherwise)
    print("=== bench_serve.py --smoke ===")
    r = subprocess.run(
        [sys.executable, str(here.parent / "bench_serve.py"), "--smoke"],
        cwd=here.parent, env=env_ex)
    if r.returncode != 0:
        fails += 1
        print("!!! bench_serve --smoke FAILED")
    # many-small-problems smoke (round 10): batched vs per-request
    # req/s rows into a throwaway artifact; exits nonzero unless every
    # batched program is structurally one-program (no per-item
    # factorization custom-call loop in the HLO)
    print("=== bench_serve.py --batched --smoke ===")
    r = subprocess.run(
        [sys.executable, str(here.parent / "bench_serve.py"),
         "--batched", "--smoke", "--batched-out",
         "/tmp/BENCH_r08_smoke.json"],
        cwd=here.parent, env=env_ex)
    if r.returncode != 0:
        fails += 1
        print("!!! bench_serve --batched --smoke FAILED")
    # pod-scale serving smoke (round 11, --all only: the virtual
    # 8-device CPU mesh AOT compiles cost minutes on a small host):
    # mesh-sharded resident serving A/B into a throwaway artifact;
    # exits nonzero unless every row is sharded-resident with a
    # nonzero served-solve collective census (bench_serve.py)
    if "--all" in argv:
        print("=== bench_serve.py --multichip --smoke ===")
        r = subprocess.run(
            [sys.executable, str(here.parent / "bench_serve.py"),
             "--multichip", "--smoke", "--cpu-mesh", "--multichip-out",
             "/tmp/MULTICHIP_r06_smoke.json"],
            cwd=here.parent, env=env_ex)
        if r.returncode != 0:
            fails += 1
            print("!!! bench_serve --multichip --smoke FAILED")
    # mixed-precision serving smoke (round 13): refined-from-bf16 vs
    # full-precision serve into a throwaway artifact; exits nonzero
    # unless every row's structural columns hold (half-byte residents,
    # ~2x residents per budget, zero fallbacks on well-conditioned
    # operators)
    print("=== bench_serve.py --mixed --smoke ===")
    r = subprocess.run(
        [sys.executable, str(here.parent / "bench_serve.py"),
         "--mixed", "--smoke", "--mixed-out",
         "/tmp/BENCH_MIXED_smoke.json"],
        cwd=here.parent, env=env_ex)
    if r.returncode != 0:
        fails += 1
        print("!!! bench_serve --mixed --smoke FAILED")
    # chaos-soak smoke (round 14): the full serving stack under every
    # injectable fault class at once; exits nonzero unless the
    # invariants hold (zero wrong answers, zero lost futures, request
    # conservation, SLO consistency, fleet fold under snapshot loss)
    # AND the same seed reproduces the identical fault schedule
    print("=== tools/chaos_serve.py --smoke ===")
    r = subprocess.run(
        [sys.executable, str(here.parent / "tools" / "chaos_serve.py"),
         "--smoke"],
        cwd=here.parent, env=env_ex)
    if r.returncode != 0:
        fails += 1
        print("!!! chaos_serve --smoke FAILED")
    # overload shedding A/B smoke (round 14): shedding bounds p99 and
    # queue age under 2x sustained overload; the no-shed arm grows
    print("=== bench_serve.py --overload ===")
    r = subprocess.run(
        [sys.executable, str(here.parent / "bench_serve.py"),
         "--overload", "--overload-out", "/tmp/BENCH_OVERLOAD_smoke.json"],
        cwd=here.parent, env=env_ex)
    if r.returncode != 0:
        fails += 1
        print("!!! bench_serve --overload FAILED")
    # tenant-isolation A/B smoke (round 18): the same 2x overload FIFO
    # vs weighted-fair + quotas — isolation must bound the victim
    # tenant's p99 and quota-reject the aggressor's excess while FIFO
    # starves the victim (bench_serve.py exits nonzero otherwise)
    print("=== bench_serve.py --tenants-fair --smoke ===")
    r = subprocess.run(
        [sys.executable, str(here.parent / "bench_serve.py"),
         "--tenants-fair", "--smoke",
         "--fair-out", "/tmp/BENCH_FAIR_smoke.json"],
        cwd=here.parent, env=env_ex)
    if r.returncode != 0:
        fails += 1
        print("!!! bench_serve --tenants-fair FAILED")
    # failover A/B smoke (round 17): kill a fleet member and recover —
    # replication+checkpoint must recover every affected handle with
    # zero refactors while the cold arm pays one per handle (the
    # chaos recovery drill above already exit-gates the fault-injected
    # ladder; this gates the measured A/B artifact)
    print("=== bench_serve.py --failover --smoke ===")
    r = subprocess.run(
        [sys.executable, str(here.parent / "bench_serve.py"),
         "--failover", "--smoke",
         "--failover-out", "/tmp/BENCH_FAILOVER_smoke.json"],
        cwd=here.parent, env=env_ex)
    if r.returncode != 0:
        fails += 1
        print("!!! bench_serve --failover FAILED")
    # spectral serving A/B smoke (round 19): resident eigendecomposition
    # applies vs cold factor-per-request — exits nonzero unless every
    # row serves from ONE warmed two-gemm program with zero new
    # compiles after warmup (the structural claim; speeds are CPU smoke)
    print("=== bench_serve.py --spectral --smoke ===")
    r = subprocess.run(
        [sys.executable, str(here.parent / "bench_serve.py"),
         "--spectral", "--smoke",
         "--spectral-out", "/tmp/BENCH_SPECTRAL_smoke.json"],
        cwd=here.parent, env=env_ex)
    if r.returncode != 0:
        fails += 1
        print("!!! bench_serve --spectral FAILED")
    # incremental-maintenance A/B smoke (round 20): rank-k update /
    # QR row append+delete vs evict+refactor — exits nonzero unless
    # every row serves its mutations with zero refactors and zero new
    # compiles after warmup, and delta checkpoints ship fewer bytes
    # than full ones (the structural claims; speeds are CPU smoke)
    print("=== bench_serve.py --updates --smoke ===")
    r = subprocess.run(
        [sys.executable, str(here.parent / "bench_serve.py"),
         "--updates", "--smoke",
         "--updates-out", "/tmp/BENCH_UPDATE_smoke.json"],
        cwd=here.parent, env=env_ex)
    if r.returncode != 0:
        fails += 1
        print("!!! bench_serve --updates FAILED")
    # tuned-vs-default serving A/B smoke (round 21): both arms resolve
    # through the committed TUNING_r01.json (tuned arm carries config
    # provenance) — exits nonzero unless the tuned arm adds zero new
    # compiles after warmup and both arms answer within tolerance (the
    # structural claims; speedups are CPU smoke, gated only on TPU)
    print("=== bench_serve.py --tuned --smoke ===")
    r = subprocess.run(
        [sys.executable, str(here.parent / "bench_serve.py"),
         "--tuned", "--smoke",
         "--tuned-out", "/tmp/BENCH_TUNED_smoke.json"],
        cwd=here.parent, env=env_ex)
    if r.returncode != 0:
        fails += 1
        print("!!! bench_serve --tuned FAILED")
    # telemetry-history + forecasting smoke (round 23): store-on vs
    # store-off serve arms, the record-path micro, and a synthetic
    # periodic holdout — exits nonzero unless the forecaster detects
    # the true period, beats last-value persistence on the held-out
    # cycle, stays silent on an aperiodic control, and every counter
    # conserves exactly through the store's delta samples
    print("=== bench_serve.py --forecast --smoke ===")
    r = subprocess.run(
        [sys.executable, str(here.parent / "bench_serve.py"),
         "--forecast", "--smoke",
         "--forecast-out", "/tmp/BENCH_FORECAST_smoke.json"],
        cwd=here.parent, env=env_ex)
    if r.returncode != 0:
        fails += 1
        print("!!! bench_serve --forecast FAILED")
    # observability smoke: traced served workload -> Chrome-trace JSON
    # (schema-validated), Prometheus text, SVG, and the /metrics HTTP
    # endpoint (tools/obs_dump.py exits nonzero on any export failure —
    # incl. the round-15 tenant/placement sections: attribution
    # conservation, placement-snapshot schema, the /tenants route,
    # tenant_* prom rows, the 2-process attribution/placement fold,
    # and the round-23 /history + /forecast payloads with exact
    # counter conservation through the store)
    print("=== tools/obs_dump.py --smoke ===")
    r = subprocess.run(
        [sys.executable, str(here.parent / "tools" / "obs_dump.py"),
         "--smoke"],
        cwd=here.parent, env=env_ex)
    if r.returncode != 0:
        fails += 1
        print("!!! obs_dump --smoke FAILED")
    # bench-trajectory gate (round 9): every committed BENCH_*.json must
    # parse against the normalized schema, and no tracked TPU series may
    # end in a regression beyond tolerance (tools/bench_gate.py exits
    # nonzero on either; no jax import — runs in-process-cheap)
    for gate_args in (["--check-schema"], []):
        print(f"=== tools/bench_gate.py {' '.join(gate_args) or '(gate)'}"
              " ===")
        r = subprocess.run(
            [sys.executable, str(here.parent / "tools" / "bench_gate.py")]
            + gate_args, cwd=here.parent, env=env)
        if r.returncode != 0:
            fails += 1
            print("!!! bench_gate FAILED")
    return fails


if __name__ == "__main__":
    sys.exit(main())
