"""tools/bench_gate.py: BENCH-trajectory schema normalization and the
regression gate (ISSUE 5 tentpole c). Pure-host — no jax import; the
gate must stay cheap enough to run in every CI invocation.

Acceptance pins: nonzero exit on an injected regression in a fixture
trajectory, zero on the real committed history, and --check-schema
validating every committed BENCH_*.json.
"""

import importlib.util
import json
import os
import pathlib
import sys

import pytest

_REPO = pathlib.Path(__file__).resolve().parent.parent


def _load_gate():
    spec = importlib.util.spec_from_file_location(
        "bench_gate", str(_REPO / "tools" / "bench_gate.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


gate_mod = _load_gate()


def _bench_artifact(value, platform="tpu", n=8192, **extra):
    return {
        "metric": f"gemm_gflops_per_chip_fp32_n{n}",
        "value": value,
        "unit": "GFLOP/s",
        "vs_baseline": round(value / 700.0, 2),
        "platform": platform,
        **extra,
    }


def _write(dirpath, name, obj):
    with open(os.path.join(dirpath, name), "w") as f:
        json.dump(obj, f)


# -- normalization ----------------------------------------------------------


def test_normalize_all_three_schemas(tmp_path):
    # rounds 1-5 harness wrapper (metrics inside "parsed", platform
    # inferred from the tail's backend probe line)
    _write(tmp_path, "BENCH_r01.json", {
        "n": 1, "cmd": "python bench.py", "rc": 0,
        "tail": "# default backend healthy: platform=tpu\n...",
        "parsed": _bench_artifact(140000.0, platform=None)})
    rec = gate_mod.normalize(str(tmp_path / "BENCH_r01.json"))
    assert rec["kind"] == "bench" and rec["round"] == 1
    assert rec["platform"] == "tpu" and rec["n"] == 8192
    assert rec["metrics"]["value"] == 140000.0

    # bare bench.py --out artifact (round 6+)
    _write(tmp_path, "BENCH_r06.json",
           _bench_artifact(100.0, platform="cpu-fallback", n=512,
                           potrf_gflops=1.5))
    rec = gate_mod.normalize(str(tmp_path / "BENCH_r06.json"))
    assert rec["round"] == 6 and rec["platform"] == "cpu-fallback"
    assert rec["n"] == 512 and rec["metrics"]["potrf_gflops"] == 1.5

    # bench_serve artifact (nested tracked metric via dotted path);
    # must carry EVERY current section (round 14: --check-schema fails
    # stale smoke fixtures)
    serve_art = {
        "bench": "serve", "backend": "cpu", "dtype": "float32",
        "n": 192, "nb": 64, "requests": 48, "max_batch": 16,
        "serve": {"solves_per_sec": 120.0},
        "per_request": {"solves_per_sec": 9.0}, "speedup": 13.3,
        "cost_log": [], "hbm": {}, "slo": {},
        "tenants": _tenants_section(),
        "numerics": _numerics_section(),
        "quotas": _quotas_section(),
        "spectral": _spectral_section(),
        "updates": _updates_section(),
        "tuning": _tuning_section(),
        "incidents": _incidents_section(),
        "forecast": _forecast_section()}
    assert set(gate_mod.SERVE_ARTIFACT_SECTIONS) <= set(serve_art)
    _write(tmp_path, "BENCH_SERVE_smoke.json", serve_art)
    rec = gate_mod.normalize(str(tmp_path / "BENCH_SERVE_smoke.json"))
    assert rec["kind"] == "serve" and rec["platform"] == "cpu"
    assert rec["metrics"]["serve.solves_per_sec"] == 120.0
    assert rec["metrics"]["speedup"] == 13.3

    # a STALE fixture — schema grew a section it lacks — fails
    # loudly (the rounds-12/13 trip class)
    stale = {k: v for k, v in serve_art.items() if k != "slo"}
    _write(tmp_path, "BENCH_SERVE_stale.json", stale)
    with pytest.raises(gate_mod.SchemaError, match="slo"):
        gate_mod.normalize(str(tmp_path / "BENCH_SERVE_stale.json"))


def test_normalize_rejects_unknown_schema(tmp_path):
    _write(tmp_path, "BENCH_r99.json", {"something": "else"})
    with pytest.raises(gate_mod.SchemaError):
        gate_mod.normalize(str(tmp_path / "BENCH_r99.json"))
    (tmp_path / "BENCH_r98.json").write_text("{not json")
    with pytest.raises(gate_mod.SchemaError):
        gate_mod.normalize(str(tmp_path / "BENCH_r98.json"))


def test_failed_round_is_excluded_not_an_error(tmp_path):
    # round 3's rc=1 wrapper (a crashed bench run) must normalize (the
    # history stays schema-clean) but contribute no gated points
    _write(tmp_path, "BENCH_r03.json", {
        "n": 3, "cmd": "python bench.py", "rc": 1,
        "tail": "Traceback ..."})
    rec = gate_mod.normalize(str(tmp_path / "BENCH_r03.json"))
    assert rec["ok"] is False and rec["metrics"] == {}


def test_normalize_legacy_multichip_blob(tmp_path):
    # rounds 1-5 dry-run wrapper: metrics buried in the tail text —
    # residuals come out as informational series (round 11 satellite)
    _write(tmp_path, "MULTICHIP_r05.json", {
        "n_devices": 8, "rc": 0, "ok": True, "skipped": False,
        "tail": "dryrun_multichip(8): mesh 2x4, posv+hemm OK (max "
                "residual 4.77e-07), getrf OK (2.38e-07), gbsv OK "
                "(2.38e-07)\n"})
    (rec,) = gate_mod.normalize_all(str(tmp_path / "MULTICHIP_r05.json"))
    assert rec["kind"] == "multichip_dryrun" and rec["round"] == 5
    assert rec["platform"] == "cpu" and rec["n"] == 8
    assert rec["metrics"]["residual_posv_hemm"] == pytest.approx(4.77e-7)
    assert rec["metrics"]["residual_getrf"] == pytest.approx(2.38e-7)
    # a failed round (the r01 blob) normalizes with no metrics
    _write(tmp_path, "MULTICHIP_r01.json", {
        "n_devices": 8, "rc": 1, "ok": False, "skipped": False,
        "tail": "Traceback ..."})
    (rec,) = gate_mod.normalize_all(str(tmp_path / "MULTICHIP_r01.json"))
    assert rec["ok"] is False and rec["metrics"] == {}


def _numerics_section(state="healthy"):
    """A minimal round-16 serve-artifact numerics section that passes
    gate_mod._check_numerics_section."""
    return {
        "enabled": True,
        "handles": {"1": {"op": "chol", "condest": 350.0,
                          "growth": 1.4, "resid_ewma": 2.1e-7,
                          "state": state}},
        "counts": {"healthy": 1, "degraded": 0, "suspect": 0},
        "counters": {"residual_probes_total": 12.0,
                     "condest_runs_total": 1.0},
        "sample_fraction": 0.25,
        "ok": True,
    }


def _quotas_section():
    """A minimal round-18 serve-artifact quotas section that passes
    gate_mod._check_quotas_section."""
    return {
        "enabled": True,
        "policies": {"policies": {"bench-a": {"weight": 2.0}},
                     "default": None},
        "tenants": {"bench-a": {"resident_bytes": 1024,
                                "residents": 1,
                                "max_resident_bytes": None,
                                "weight": 2.0}},
        "counters": {"quota_rejections_total": 0.0},
    }


def _spectral_section():
    """A minimal round-19 serve-artifact spectral section that passes
    gate_mod._check_spectral_section."""
    return {
        "enabled": True,
        "op": "eig",
        "n": 96,
        "functions": ["solve", "psd_project", "whiten", "truncate"],
        "new_compiles_after_warmup": 0,
        "apply_dot_ops": {"solve": 2, "psd_project": 2,
                          "whiten": 2, "truncate": 2},
        "stage_programs": ["spectral.he2hb", "spectral.hb2td",
                           "spectral.unmtr"],
        "solve_rel_err": 3.1e-6,
        "ok": True,
    }


def _updates_section():
    """A minimal round-20 serve-artifact updates section that passes
    gate_mod._check_updates_section."""
    return {
        "enabled": True,
        "op": "chol",
        "n": 96,
        "nb": 32,
        "k": 2,
        "updates_applied": 2,
        "new_compiles_after_warmup": 0,
        "update_refactors": 0,
        "refactors_during_updates": 0.0,
        "update_flops": 73728.0,
        "solve_rel_err": 4.1e-9,
        "ok": True,
    }


def _tuning_section():
    """A minimal round-21 serve-artifact tuning section that passes
    gate_mod._check_tuning_section."""
    return {
        "enabled": True,
        "op": "chol",
        "n": 32,
        "resolved": "TUNING_r01.json#0[nb=32,inner_blocking=16,"
                    "lookahead=0,wide_panel=32]",
        "table": {"schema": gate_mod.TUNING_SCHEMA,
                  "file": "TUNING_r01.json", "entries": 5,
                  "platform_match": True},
        "new_compiles_after_warmup": 0,
        "solve_rel_err": 9.1e-9,
        "ok": True,
    }


def _incidents_section():
    """A minimal round-22 serve-artifact incidents section that passes
    gate_mod._check_incidents_section (the sample is held to the
    slate_tpu.incident.v1 mirror validator)."""
    return {
        "enabled": True,
        "captured": 1,
        "journal_recorded": 3,
        "journal_digest": "sha256:deadbeef",
        "parity": {"eviction": {"counter": 1.0, "journal": 1.0,
                                "ok": True}},
        "sample": {
            "schema": gate_mod.INCIDENT_SCHEMA,
            "id": "inc-0000-bench_probe", "ts": 1700000000.0,
            "host": "bench", "reason": "bench_probe", "key": "smoke",
            "context": {},
            "journal": {"events": [{"kind": "eviction",
                                    "ts": 1700000000.0, "count": 1}],
                        "counts": {"eviction": 1},
                        "outcome_counts": {}},
            "flight": {"spans": [], "samples": []},
            "metrics": {"counters": {"evictions": 1.0}, "gauges": {}},
            "numerics": None, "quotas": None, "placement": None,
            "cost_log": None, "tuning": None,
        },
        "ok": True,
    }


def _forecast_section():
    """A minimal round-23 serve-artifact forecast section that passes
    gate_mod._check_forecast_section.  The history/forecast payloads
    are built by the REAL store + forecaster — the same stdlib-only
    modules bench_gate file-loads for its validators (sys.modules
    carries them under their fixed names once gate_mod is loaded), so
    the fixture can never drift from the schema it is held to."""
    tmod = sys.modules["slate_tpu_obs_timeseries"]
    fmod = sys.modules["slate_tpu_obs_forecast"]
    t = {"now": 0.0}
    store = tmod.TimeseriesStore(clock=lambda: t["now"])
    for i in range(12):
        t["now"] = float(i)
        store.record_gauge("queue_depth", float(i % 3))
        store.record_counter("solves_total", float(i + 1))
    hist = store.payload()
    fc = fmod.Forecaster(store).payload(horizon_s=10.0)
    cons = {name: {"store": total, "counter": total, "ok": True}
            for name, total in store.counter_totals().items()}
    return {
        "enabled": True,
        "series_count": len(hist["series"]),
        "dropped_series": 0,
        "dropped_samples": 0,
        "conservation": cons,
        "history": hist,
        "forecast": fc,
        "ok": True,
    }


def _tenants_section(conservation_ok=True, rows=None):
    """A minimal round-15 serve-artifact tenants section that passes
    gate_mod._check_tenants_section."""
    if rows is None:
        rows = [{
            "host": "bench", "tenant": "bench-a", "handle": "1",
            "op": "chol", "n": 192, "dtype": "float32",
            "bytes_per_chip": 147456, "heat": 2.5,
            "last_access": 1700000000.0,
            "health": "healthy", "condest": 350.0, "growth": 1.4}]
    return {
        "enabled": True, "halflife_s": 300.0,
        "per_tenant": {"bench-a": {"solve_flops": 1.0}},
        "conservation": {"solve_flops": {
            "per_tenant_sum": 1.0, "global": 1.0,
            "ok": conservation_ok}},
        "conservation_ok": conservation_ok,
        "placement": {"schema": gate_mod.PLACEMENT_SCHEMA,
                      "host": "bench", "rows": rows},
    }


def test_serve_tenants_section_schema(tmp_path):
    """Round 15: --check-schema holds the serve artifact's tenants
    section to the placement row schema — a row missing a key, or a
    placement block with the wrong schema id, fails loudly (the
    stale-fixture class)."""
    base = {
        "bench": "serve", "backend": "cpu", "dtype": "float32",
        "n": 192, "nb": 64, "requests": 48, "max_batch": 16,
        "serve": {"solves_per_sec": 120.0},
        "per_request": {"solves_per_sec": 9.0}, "speedup": 13.3,
        "cost_log": [], "hbm": {}, "slo": {},
        "numerics": _numerics_section(),
        "quotas": _quotas_section(),
        "spectral": _spectral_section(),
        "updates": _updates_section(),
        "tuning": _tuning_section(),
        "incidents": _incidents_section(),
        "forecast": _forecast_section()}
    # a placement row lacking "heat" fails
    bad_row = _tenants_section()
    del bad_row["placement"]["rows"][0]["heat"]
    _write(tmp_path, "BENCH_SERVE_badrow.json",
           dict(base, tenants=bad_row))
    with pytest.raises(gate_mod.SchemaError, match="heat"):
        gate_mod.normalize(str(tmp_path / "BENCH_SERVE_badrow.json"))
    # a wrong placement schema id fails
    bad_schema = _tenants_section()
    bad_schema["placement"]["schema"] = "nope.v0"
    _write(tmp_path, "BENCH_SERVE_badschema.json",
           dict(base, tenants=bad_schema))
    with pytest.raises(gate_mod.SchemaError, match="placement schema"):
        gate_mod.normalize(str(tmp_path / "BENCH_SERVE_badschema.json"))
    # a tenants section without the conservation verdict fails
    no_cons = _tenants_section()
    del no_cons["conservation"]
    _write(tmp_path, "BENCH_SERVE_nocons.json",
           dict(base, tenants=no_cons))
    with pytest.raises(gate_mod.SchemaError, match="conservation"):
        gate_mod.normalize(str(tmp_path / "BENCH_SERVE_nocons.json"))
    # the well-formed section parses
    _write(tmp_path, "BENCH_SERVE_ok.json",
           dict(base, tenants=_tenants_section()))
    rec = gate_mod.normalize(str(tmp_path / "BENCH_SERVE_ok.json"))
    assert rec["kind"] == "serve"


def test_placement_row_keys_mirror_pinned():
    """The jax-free mirror discipline (bench_gate stays standalone,
    the baseline-validator precedent): bench_gate's placement row
    keys and schema id must equal the obs.attribution originals —
    the tenants-section check is only as strong as this equality.
    (The SERVE_ARTIFACT_SECTIONS twin pin lives in test_faults.py.)"""
    from slate_tpu.obs import attribution as attr_mod
    assert tuple(gate_mod.PLACEMENT_ROW_KEYS) == \
        tuple(attr_mod.PLACEMENT_ROW_KEYS)
    assert gate_mod.PLACEMENT_SCHEMA == attr_mod.PLACEMENT_SCHEMA


def _multichip_artifact(solves=300.0, speedup=0.1):
    return {
        "bench": "multichip", "platform": "cpu",
        "forced_host_devices": True, "mesh_shape": [2, 4],
        "n_devices": 8, "ok": True,
        "rows": [{
            "op": "chol", "n": 128, "nb": 32, "dtype": "float32",
            "requests": 32, "ok": True,
            "serve": {"wall_s": 0.1, "solves_per_sec": solves},
            "single_device": {"wall_s": 0.01,
                              "solves_per_sec": solves / speedup},
            "speedup": speedup,
            "sharded_resident": True,
            "solve_collective_census": {"all-gather": 10},
        }],
    }


def _serve_mixed_artifact(solves=100.0, speedup=0.2):
    return {
        "bench": "serve_mixed", "platform": "cpu",
        "dtype": "float32", "factor_dtype": "bfloat16", "ok": True,
        "rows": [{
            "op": "chol", "n": 128, "nb": 32, "requests": 32,
            "dtype": "float32", "factor_dtype": "bfloat16", "ok": True,
            "mixed": {"wall_s": 0.3, "solves_per_sec": solves,
                      "iters_mean": 3.0, "factor_bytes": 32768,
                      "residents_within_budget": 6},
            "full": {"wall_s": 0.06, "solves_per_sec": solves / speedup,
                     "factor_bytes": 65536,
                     "residents_within_budget": 3},
            "speedup": speedup, "factor_bytes_ratio": 0.5,
            "residents_ratio": 2.0, "refine_fallbacks": 0,
        }],
    }


def test_normalize_serve_mixed_rows(tmp_path):
    """Round 13: the BENCH_MIXED_r*.json mixed-serving A/B — one
    serve_mixed record per row, series keyed (op, n, dtype); the
    structural residents_ratio rides as a tracked metric beside the
    solves/sec pair."""
    _write(tmp_path, "BENCH_MIXED_r01.json", _serve_mixed_artifact())
    (rec,) = gate_mod.normalize_all(
        str(tmp_path / "BENCH_MIXED_r01.json"))
    assert rec["kind"] == "serve_mixed" and rec["round"] == 1
    assert rec["op"] == "chol" and rec["n"] == 128
    assert rec["dtype"] == "float32"
    assert rec["metrics"]["mixed.solves_per_sec"] == 100.0
    assert rec["metrics"]["full.solves_per_sec"] == 500.0
    assert rec["metrics"]["residents_ratio"] == 2.0
    # single-object normalize() redirects to normalize_all
    with pytest.raises(gate_mod.SchemaError, match="normalize_all"):
        gate_mod.normalize(str(tmp_path / "BENCH_MIXED_r01.json"))
    # a row missing the structural ratio fails schema validation
    bad = _serve_mixed_artifact()
    del bad["rows"][0]["factor_bytes_ratio"]
    _write(tmp_path, "BENCH_MIXED_r02.json", bad)
    assert gate_mod.check_schema(
        [str(tmp_path / "BENCH_MIXED_r02.json")])
    # discovery picks the family up beside the other artifacts
    assert any(p.endswith("BENCH_MIXED_r01.json")
               for p in gate_mod.discover(str(tmp_path)))


def test_normalize_structured_multichip_rows(tmp_path):
    _write(tmp_path, "MULTICHIP_r06.json", _multichip_artifact())
    (rec,) = gate_mod.normalize_all(str(tmp_path / "MULTICHIP_r06.json"))
    assert rec["kind"] == "multichip_serve" and rec["round"] == 6
    assert rec["op"] == "chol" and rec["n"] == 128
    assert rec["mesh_shape"] == [2, 4]
    assert rec["metrics"]["serve.solves_per_sec"] == 300.0
    assert rec["metrics"]["speedup"] == 0.1
    # single-object normalize() redirects to normalize_all
    with pytest.raises(gate_mod.SchemaError, match="normalize_all"):
        gate_mod.normalize(str(tmp_path / "MULTICHIP_r06.json"))
    # missing row keys are schema errors, not silent drops
    bad = _multichip_artifact()
    del bad["rows"][0]["speedup"]
    _write(tmp_path, "MULTICHIP_r07.json", bad)
    with pytest.raises(gate_mod.SchemaError, match="speedup"):
        gate_mod.normalize_all(str(tmp_path / "MULTICHIP_r07.json"))


def test_multichip_dtype_rows_are_separate_series(tmp_path):
    # one artifact carries f32 AND f64 rows per (op, n); without the
    # dtype series key the much-slower f64 point would gate against
    # the f32 best-prior and fabricate a TPU regression
    def two_dtype(path):
        art = _multichip_artifact(3000.0)
        art["platform"] = "tpu"
        slow = dict(art["rows"][0], dtype="float64",
                    serve={"wall_s": 1.0, "solves_per_sec": 300.0})
        art["rows"].append(slow)
        _write(tmp_path, path, art)
    two_dtype("MULTICHIP_r06.json")
    two_dtype("MULTICHIP_r07.json")
    recs = gate_mod.normalize_all(str(tmp_path / "MULTICHIP_r06.json"))
    assert [r["dtype"] for r in recs] == ["float32", "float64"]
    assert gate_mod.main(["--dir", str(tmp_path)]) == 0


def test_multichip_series_gate_and_informational(tmp_path, capsys):
    # CPU multichip rows never gate (informational, like every CPU
    # smoke series); a TPU-platform regression in the same schema DOES
    _write(tmp_path, "MULTICHIP_r06.json", _multichip_artifact(300.0))
    _write(tmp_path, "MULTICHIP_r07.json", _multichip_artifact(30.0))
    assert gate_mod.main(["--dir", str(tmp_path)]) == 0
    capsys.readouterr()
    tpu6 = _multichip_artifact(300.0)
    tpu7 = _multichip_artifact(30.0)
    tpu6["platform"] = tpu7["platform"] = "tpu"
    _write(tmp_path, "MULTICHIP_r06.json", tpu6)
    _write(tmp_path, "MULTICHIP_r07.json", tpu7)
    rc = gate_mod.main(["--dir", str(tmp_path)])
    summary = json.loads(capsys.readouterr().out.splitlines()[0])
    assert rc == 1 and any(
        r["metric"] == "serve.solves_per_sec"
        for r in summary["regressions"])


# -- the gate ---------------------------------------------------------------


def test_injected_tpu_regression_fails_gate(tmp_path, capsys):
    _write(tmp_path, "BENCH_r01.json",
           _bench_artifact(15000.0, potrf_gflops=5000.0))
    _write(tmp_path, "BENCH_r02.json",
           _bench_artifact(15100.0, potrf_gflops=3000.0))  # -40% potrf
    rc = gate_mod.main(["--dir", str(tmp_path)])
    assert rc == 1
    summary = json.loads(capsys.readouterr().out.splitlines()[0])
    assert summary["ok"] is False
    (reg,) = summary["regressions"]
    assert reg["metric"] == "potrf_gflops" and reg["platform"] == "tpu"
    assert reg["best_prior"] == 5000.0 and reg["last"] == 3000.0


def test_within_tolerance_passes(tmp_path):
    _write(tmp_path, "BENCH_r01.json", _bench_artifact(15000.0))
    _write(tmp_path, "BENCH_r02.json", _bench_artifact(14000.0))  # -6.7%
    assert gate_mod.main(["--dir", str(tmp_path)]) == 0
    # ...and the same drop fails under a tighter tolerance
    assert gate_mod.main(["--dir", str(tmp_path),
                          "--tolerance", "0.05"]) == 1


def test_cpu_drop_is_informational_only(tmp_path, capsys):
    # the documented policy: CPU smoke numbers are dispatch-noise-
    # dominated (PERF_HISTORY.md rounds 6-7) — reported, never gated
    _write(tmp_path, "BENCH_r01.json",
           _bench_artifact(100.0, platform="cpu-fallback", n=512))
    _write(tmp_path, "BENCH_r02.json",
           _bench_artifact(10.0, platform="cpu-fallback", n=512))
    rc = gate_mod.main(["--dir", str(tmp_path)])
    summary = json.loads(capsys.readouterr().out.splitlines()[0])
    assert rc == 0 and summary["ok"] is True
    assert summary["informational_drops"]


def test_series_keyed_by_platform_and_n(tmp_path):
    # a TPU round at n=16384 must NOT gate against an n=8192 round,
    # nor against a CPU round at any size
    _write(tmp_path, "BENCH_r01.json", _bench_artifact(15000.0, n=8192))
    _write(tmp_path, "BENCH_r02.json",
           _bench_artifact(100.0, platform="cpu-fallback", n=8192))
    _write(tmp_path, "BENCH_r03.json", _bench_artifact(900.0, n=16384))
    assert gate_mod.main(["--dir", str(tmp_path)]) == 0


# -- --check-schema ---------------------------------------------------------


def test_check_schema_flags_corrupt_artifact(tmp_path, capsys):
    _write(tmp_path, "BENCH_r01.json", _bench_artifact(1.0))
    assert gate_mod.main(["--dir", str(tmp_path), "--check-schema"]) == 0
    capsys.readouterr()
    (tmp_path / "BENCH_r02.json").write_text('{"bogus": true}')
    assert gate_mod.main(["--dir", str(tmp_path), "--check-schema"]) == 1
    summary = json.loads(capsys.readouterr().out.splitlines()[0])
    assert summary["schema_errors"]


# -- the real committed history (the acceptance pins) -----------------------


def test_real_history_schema_clean():
    paths = gate_mod.discover(str(_REPO))
    # the BENCH rounds since 4 + the serve smoke (the records of the
    # remote-TPU setup of rounds 2–5 are gone with that setup)
    assert len(paths) >= 8
    # round 11: the MULTICHIP family is part of the checked trajectory
    assert any("MULTICHIP_r06" in p for p in paths)
    assert sum("MULTICHIP" in p for p in paths) >= 3
    assert gate_mod.check_schema(paths) == []


def test_real_history_passes_gate(capsys):
    rc = gate_mod.main(["--dir", str(_REPO)])
    summary = json.loads(capsys.readouterr().out.splitlines()[0])
    assert rc == 0 and summary["ok"] is True
    assert summary["rounds"], "trajectory read as empty"
    # the known CPU-smoke noise shows up as informational, proving the
    # platform split actually separated the series
    assert all(r["platform"] not in gate_mod.GATED_PLATFORMS
               for r in summary["informational_drops"])


# -- serve_failover artifact (round 17) -------------------------------------


def _failover_arm(recovery=0.01, refactors=0.0):
    return {"affected_handles": 2, "failover_s": 0.002,
            "recovery_s_max": recovery, "recovery_s_mean": recovery,
            "refactors_after_crash": refactors, "replica_served": 1.0,
            "restored": 1.0, "cold_registered": 0.0,
            "availability": 1.0, "completed": 16,
            "wrong_answers": 0}


def test_normalize_serve_failover_arms(tmp_path):
    art = {"bench": "serve_failover", "platform": "cpu", "n": 32,
           "nb": 16, "handles": 4, "members": 3,
           "arms": {"protected": _failover_arm(),
                    "cold": _failover_arm(0.05, 2.0)},
           "ok": True}
    _write(tmp_path, "BENCH_FAILOVER_r01.json", art)
    recs = gate_mod.normalize_all(
        str(tmp_path / "BENCH_FAILOVER_r01.json"))
    assert [r["op"] for r in recs] == ["cold", "protected"]
    assert all(r["kind"] == "serve_failover" for r in recs)
    cold = next(r for r in recs if r["op"] == "cold")
    assert cold["metrics"]["refactors_after_crash"] == 2.0
    assert cold["metrics"]["recovery_s_max"] == 0.05
    # single-object normalize refuses the multi-row artifact
    with pytest.raises(gate_mod.SchemaError):
        gate_mod.normalize(str(tmp_path / "BENCH_FAILOVER_r01.json"))


def test_serve_failover_missing_arm_rejected(tmp_path):
    art = {"bench": "serve_failover", "platform": "cpu", "n": 32,
           "arms": {"protected": _failover_arm()}, "ok": True}
    _write(tmp_path, "BENCH_FAILOVER_r02.json", art)
    with pytest.raises(gate_mod.SchemaError):
        gate_mod.normalize_all(
            str(tmp_path / "BENCH_FAILOVER_r02.json"))


def _fair_tenant_row(p99=0.02, rejected=10):
    return {"submitted": 40, "completed": 30,
            "quota_rejected": rejected, "reqs_per_sec": 25.0,
            "p50_latency_s": p99 / 2, "p99_latency_s": p99}


def test_normalize_serve_fair_arm_tenant_records(tmp_path):
    """Round 18: the tenant-isolation A/B artifact normalizes to one
    record per (arm, tenant) — arm.tenant in the op series slot, so a
    fair-arm victim series never gates against the fifo-arm one."""
    art = {"bench": "serve_fair", "platform": "cpu", "n": 32,
           "nb": 16, "service_ms": 10.0,
           "arms": {
               "fair": {"tenants": {
                   "victim": _fair_tenant_row(0.02, 0),
                   "aggressor": _fair_tenant_row(0.1, 80)}},
               "fifo": {"tenants": {
                   "victim": _fair_tenant_row(0.3, 0),
                   "aggressor": _fair_tenant_row(0.1, 0)}}},
           "ok": True}
    _write(tmp_path, "BENCH_FAIR_r01.json", art)
    recs = gate_mod.normalize_all(str(tmp_path / "BENCH_FAIR_r01.json"))
    assert sorted(r["op"] for r in recs) == [
        "fair.aggressor", "fair.victim", "fifo.aggressor",
        "fifo.victim"]
    assert all(r["kind"] == "serve_fair" for r in recs)
    fv = next(r for r in recs if r["op"] == "fair.victim")
    assert fv["metrics"]["p99_latency_s"] == 0.02
    assert fv["metrics"]["reqs_per_sec"] == 25.0
    # single-object normalize refuses the multi-row artifact
    with pytest.raises(gate_mod.SchemaError):
        gate_mod.normalize(str(tmp_path / "BENCH_FAIR_r01.json"))
    # missing arm / missing tenant column are rejected
    _write(tmp_path, "BENCH_FAIR_r02.json",
           dict(art, arms={"fair": art["arms"]["fair"]}))
    with pytest.raises(gate_mod.SchemaError):
        gate_mod.normalize_all(str(tmp_path / "BENCH_FAIR_r02.json"))
    bad = {"fair": {"tenants": {"victim": {"submitted": 1}}},
           "fifo": art["arms"]["fifo"]}
    _write(tmp_path, "BENCH_FAIR_r03.json", dict(art, arms=bad))
    with pytest.raises(gate_mod.SchemaError, match="p99|completed"):
        gate_mod.normalize_all(str(tmp_path / "BENCH_FAIR_r03.json"))


def test_fair_metrics_classify_lower_is_better():
    """The per-tenant latency series must enter the baseline
    lower-is-better (a starved victim read as an improvement would
    blind the watchdog); throughput stays higher-is-better."""
    assert gate_mod._direction("p99_latency_s") == "lower"
    assert gate_mod._direction("p50_latency_s") == "lower"
    assert gate_mod._direction("quota_rejected") == "lower"
    assert gate_mod._direction("reqs_per_sec") == "higher"
    assert gate_mod._direction("completed") == "higher"


def test_failover_metrics_classify_lower_is_better():
    """The recovery/failover/refactor columns must enter the baseline
    lower-is-better (a 10x recovery-time rise read as an improvement
    would blind the watchdog — the round-12 _direction discipline)."""
    for m in ("recovery_s_max", "failover_s", "refactors_after_crash"):
        assert gate_mod._direction(m) == "lower"
    assert gate_mod._direction("availability") == "higher"


def test_checkpoint_manifest_validator_paths(tmp_path):
    """The jax-free validator accepts a dict, a manifest path, or a
    checkpoint directory — and flags unreadable/invalid ones."""
    good = {"schema": gate_mod.CHECKPOINT_SCHEMA, "host": "x",
            "generated_at": 1.0, "records": []}
    assert gate_mod.validate_checkpoint_manifest(good) == []
    d = tmp_path / "ck"
    d.mkdir()
    with open(d / "manifest.json", "w") as f:
        json.dump(good, f)
    assert gate_mod.validate_checkpoint_manifest(str(d)) == []
    assert gate_mod.validate_checkpoint_manifest(
        str(d / "manifest.json")) == []
    assert gate_mod.validate_checkpoint_manifest(
        str(tmp_path / "missing")) != []
    assert gate_mod.validate_checkpoint_manifest(
        dict(good, schema="nope")) != []
