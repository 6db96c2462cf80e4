#!/usr/bin/env python
"""Bench-trajectory regression gate over the committed BENCH artifacts.

Seven rounds of ``BENCH_r*.json`` (plus ``BENCH_SERVE_*.json``) sit in
the repo and form a performance trajectory nothing read until now —
regressions were invisible until a human reread PERF.md. This tool:

1. **Normalizes** the three artifact schemas that accumulated across
   rounds into one record shape
   ``{round, source, kind, platform, n, ok, metrics:{name: float}}``:

   * the harness wrapper (rounds 1–5): ``{"n": round, "cmd", "rc",
     "tail", "parsed": {...}}`` — metrics from ``parsed``, platform
     inferred from the tail (the ``platform=tpu`` probe line / the
     CPU-fallback notice) when ``parsed`` lacks it;
   * the bare bench.py artifact (rounds 6+, ``--out``): has a
     ``"metric"`` key; ``n`` parsed out of the metric name;
   * the bench_serve artifact: ``{"bench": "serve", "backend", ...}``;
   * the batched-serving A/B rows (round 10, ``bench_serve.py
     --batched`` → ``BENCH_r08.json``): a JSON LIST of
     ``{"bench": "serve_batched", "platform", "op", "n", "batch",
     "batched": {"reqs_per_sec", ...}, "per_request": {...},
     "speedup"}`` rows — one record per row, series additionally keyed
     by the batch size (a B=10⁴ bucket never gates against a B=10²
     one);
   * the MULTICHIP family (round 11): rounds 1–5 are bare
     ``{n_devices, rc, ok, tail}`` dry-run blobs whose per-driver
     residuals hide in the tail text — parsed out as informational
     series; round 6+ is the structured ``{"bench": "multichip",
     "platform", "mesh_shape", "n_devices", "rows": [...]}`` artifact
     (``bench_serve.py --multichip``) — one ``multichip_serve`` record
     per row, series keyed by (op, n), gating
     serve/single-device solves-per-sec and speedup on TPU platforms.

2. **Gates**: for every tracked metric, series are keyed by
   ``(metric, platform, n)`` — numbers from different backends or
   problem sizes are never compared. The newest gateable round is
   compared against the best prior value in the same series; the gate
   FAILS (exit 1) on a drop beyond the tolerance. Policy (PERF_HISTORY.md
   Round 9): only TPU series gate — the CPU smoke rounds are
   dispatch-noise-dominated by the repo's own repeated measurement
   (PERF_HISTORY.md rounds 6–7 call their CPU totals "a wash") and are
   reported informationally. Default tolerance 10 %.

3. **Summarizes**: one JSON line on stdout — rounds seen, series
   tracked, regressions — machine-greppable trajectory state.

``--check-schema`` validates every committed ``BENCH_*.json`` against
the normalized schema and exits nonzero on any unparseable artifact
(this is why the trajectory read as empty: nothing enforced the
files). Wired into examples/run_tests.py beside tools/obs_dump.py.

``--baseline-out PATH`` (round 12) exports the normalized best-prior
series as a committed ``BASELINE_SERIES.json`` artifact — the single
source of truth the live regression watchdog (slate_tpu/obs/
watchdog.py) loads, so the serving runtime and this gate compare
against literally the same numbers; ``--check-schema`` validates a
committed baseline alongside the raw artifacts.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import List, Optional

# metric name -> where to find it in a bare bench.py artifact; every
# tracked metric is higher-is-better (GFLOP/s, solves/s, speedup)
TRACKED_BENCH = ("value", "potrf_gflops", "getrf_gflops",
                 "getrf_calu_gflops", "geqrf_gflops", "gemm_high_gflops")
TRACKED_SERVE = ("serve.solves_per_sec", "speedup")
TRACKED_SERVE_BATCHED = ("batched.reqs_per_sec", "speedup")
# the round-11 structured multichip rows (mesh-sharded serving A/B);
# collective/census columns are structural evidence, not perf series
TRACKED_MULTICHIP = ("serve.solves_per_sec",
                     "single_device.solves_per_sec", "speedup")
# the round-13 mixed-precision serving A/B (bench_serve.py --mixed →
# BENCH_MIXED_r*.json): refined-from-low-precision vs full-precision
# serve. residents_ratio and factor-bytes columns are structural; the
# solves/sec pair and speedup gate on TPU platforms like every serve
# series (CPU rows are convert-materialization smoke — informational)
TRACKED_SERVE_MIXED = ("mixed.solves_per_sec", "full.solves_per_sec",
                       "speedup", "residents_ratio")
# the round-14 overload A/B (bench_serve.py --overload →
# BENCH_OVERLOAD_r*.json): one record per arm (shed / no_shed);
# p99_latency_s classifies as lower-is-better via _direction
TRACKED_OVERLOAD = ("p99_latency_s", "max_oldest_age_s", "completed")
# the round-17 failover A/B (bench_serve.py --failover →
# BENCH_FAILOVER_r*.json): one record per arm (protected / cold);
# the recovery/failover/refactor columns classify lower-is-better via
# _direction, availability higher
TRACKED_FAILOVER = ("failover_s", "recovery_s_max",
                    "refactors_after_crash", "availability")
# the round-18 tenant-isolation A/B (bench_serve.py --tenants-fair →
# BENCH_FAIR_r*.json): one record per (arm, tenant); the latency
# columns classify lower-is-better via _direction (a per-tenant p99
# series entering the baseline inverted would read starvation as an
# improvement), reqs_per_sec higher
TRACKED_FAIR = ("reqs_per_sec", "p50_latency_s", "p99_latency_s",
                "completed")
# the round-19 resident-spectral A/B (bench_serve.py --spectral →
# BENCH_SPECTRAL_r*.json): one record per op row (eig / svd);
# theta-varying applies from a resident eigendecomposition vs the
# full two-stage decomposition per request. The zero-new-compiles and
# two-gemm apply-census columns are structural evidence, not series.
TRACKED_SPECTRAL = ("resident.applies_per_sec",
                    "cold.applies_per_sec", "speedup")
# the round-20 incremental-maintenance A/B (bench_serve.py --updates →
# BENCH_UPDATE_r*.json): one record per (op, n, k) row — rank-k
# updates / QR row appends served from the resident factor vs a full
# evict+refactor per mutation, k riding the batch series slot. The
# sync.* columns (delta-vs-full replica transfer bytes) classify
# lower-is-better via _direction; the refactor arm's rate is kept in
# the row for reading but NOT tracked as a series (its name would
# collide with the lower-is-better "refactor" classification the
# failover counts rely on). Zero-refactor/zero-compile columns are
# structural evidence, not series.
TRACKED_UPDATE = ("update.updates_per_sec", "speedup",
                  "sync.delta_bytes", "sync.ratio")
# the round-21 tuned-serving A/B (bench_serve.py --tuned →
# BENCH_TUNED_r*.json): default-Session vs tuning-table-Session serve
# of the same resident factor. The compile-count and config-provenance
# columns are structural evidence (validated, never series); the
# solves/sec pair and speedup gate on TPU platforms like every serve
# series (CPU rows are dispatch-noise smoke — informational).
TRACKED_TUNED = ("tuned.solves_per_sec", "default.solves_per_sec",
                 "speedup")
# the round-21 offline-search table itself (tools/autotune.py →
# TUNING_r*.json): each entry's measured score enters the trajectory
# as an informational series keyed by (op, n_max, dtype, platform) —
# the committed table is also the runtime's config source, so
# --check-schema holding it to the schema is what keeps the serving
# seam and this gate reading the same document
TRACKED_TUNING = ("tuned.gflops",)
# the round-23 sensing-substrate A/B (bench_serve.py --forecast →
# BENCH_FORECAST_r*.json): the holdout improvement (forecast MAE vs
# naive-last MAE — higher is better) plus the store cost columns
# (record-path ns/sample and the forced-pump serve overhead — both
# classify lower-is-better via _direction). Period detection and the
# aperiodic control are structural evidence, never series.
TRACKED_FORECAST = ("holdout.improvement",
                    "store.record_ns_per_sample",
                    "serve.overhead_pct")
GATED_PLATFORMS = ("tpu",)

# SHARED with bench_serve.py since round 22 (tools/serve_sections.py,
# stdlib-only — this tool stays jax-import-free; the old hand-synced
# mirror pin is now an import-identity test): every section the serve
# artifact currently carries. --check-schema fails a committed fixture
# missing any of them — the round-12/13 stale-fixture class (schema
# grew a section, fixture silently didn't).


def _load_serve_sections():
    """Same fixed-name module load as bench_serve._load_serve_sections
    (one cached module object -> one shared tuple object)."""
    import importlib.util
    name = "slate_tpu_serve_sections"
    mod = sys.modules.get(name)
    if mod is None:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "serve_sections.py")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return mod


SERVE_ARTIFACT_SECTIONS = _load_serve_sections().SERVE_ARTIFACT_SECTIONS
# mirror of obs/attribution.py PLACEMENT_ROW_KEYS + PLACEMENT_SCHEMA
# (same jax-free duplication discipline as the sections tuple above
# and the baseline validators; tests pin the mirrors equal): the
# round-15 placement-snapshot row shape --check-schema holds the
# committed serve fixture's tenants section to. v2 (round 16) adds
# the numerical-health columns (health/condest/growth, nullable).
PLACEMENT_SCHEMA = "slate_tpu.placement_snapshot.v2"
PLACEMENT_ROW_KEYS = ("host", "tenant", "handle", "op", "n", "dtype",
                      "bytes_per_chip", "heat", "last_access",
                      "health", "condest", "growth")
# mirror of obs/numerics.py HEALTH_STATES (tests pin them equal): the
# vocabulary the round-16 numerics section's states must come from
HEALTH_STATES = ("healthy", "degraded", "suspect")
# mirror of slate_tpu/runtime/checkpoint.py (round 17; same jax-free
# duplication discipline as the placement schema — tests pin the
# mirrors equal and feed both validators the same malformed docs): the
# checkpoint manifest a dead member's failover restores from, held to
# its schema by CI without importing the runtime
CHECKPOINT_SCHEMA = "slate_tpu.checkpoint.v1"
CHECKPOINT_RECORD_KEYS = (
    "handle", "handle_type", "op", "m", "n", "band", "dtype", "nb",
    "tenant", "refine", "mesh", "info", "heat", "last_access",
    "health", "operator", "payload")
CHECKPOINT_BLOB_KEYS = ("blob", "shape", "dtype", "nbytes", "sha256")
# mirror of slate_tpu/tuning/table.py (round 21; the same jax-free
# duplication discipline as the checkpoint/placement mirrors — tests
# pin the schema ids and the config-knob vocabulary equal and feed
# both validators the same malformed docs): the committed tuning
# table the serving runtime resolves configs from, held to its schema
# by CI without importing the runtime
TUNING_SCHEMA = "slate_tpu.tuning_table.v1"
TUNING_CONFIG_KEYS = ("nb", "inner_blocking", "lookahead",
                      "wide_panel", "batch_quantum", "width_quantum")
# mirror of slate_tpu/obs/events.py (round 22; same jax-free
# duplication discipline — tests pin the schema id and key tuple
# equal and feed both validators the same malformed docs): the
# incident-snapshot document the flight recorder publishes, held to
# its schema by --check-schema via the serve artifact's embedded
# sample and any committed incident files
INCIDENT_SCHEMA = "slate_tpu.incident.v1"
INCIDENT_KEYS = (
    "schema", "id", "ts", "host", "reason", "key", "context",
    "journal", "flight", "metrics", "numerics", "quotas", "placement",
    "cost_log", "tuning")
DEFAULT_TOLERANCE = 0.10

# round 23: the timeseries/forecast/capacity validators are NOT
# duplicated — slate_tpu/obs/{timeseries,forecast}.py are stdlib-only
# with no relative imports, so this tool loads the REAL modules by
# file path (the serve_sections discipline: one fixed module name,
# shared with tools/capacity_report.py; the drift pin degenerates to
# an import-identity test on __code__.co_filename).
TIMESERIES_SCHEMA = "slate_tpu.timeseries.v1"
FORECAST_SCHEMA = "slate_tpu.forecast.v1"
CAPACITY_SCHEMA = "slate_tpu.capacity_report.v1"


def _load_by_path(fixed_name: str, *relpath: str):
    import importlib.util
    mod = sys.modules.get(fixed_name)
    if mod is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        path = os.path.join(root, *relpath)
        spec = importlib.util.spec_from_file_location(fixed_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[fixed_name] = mod
        spec.loader.exec_module(mod)
    return mod


validate_timeseries_doc = _load_by_path(
    "slate_tpu_obs_timeseries", "slate_tpu", "obs",
    "timeseries.py").validate_timeseries
validate_forecast_doc = _load_by_path(
    "slate_tpu_obs_forecast", "slate_tpu", "obs",
    "forecast.py").validate_forecast
validate_capacity_doc = _load_by_path(
    "slate_tpu_capacity_report", "tools",
    "capacity_report.py").validate_capacity_report

_N_RE = re.compile(r"_n(\d+)$")
# any committed artifact family named <FAMILY>_r<round>.json (BENCH_,
# MULTICHIP_, BENCH_MIXED_); non-round files (BENCH_SERVE_smoke) get
# round None
_ROUND_RE = re.compile(r"_r(\d+)\.json$")
# the r01–r05 multichip dry-run tails: "posv+hemm OK (max residual
# 4.77e-07), getrf OK (2.38e-07), ..." — the only machine-readable
# signal those rounds recorded (normalized as informational series)
_TAIL_RESID_RE = re.compile(
    r"([\w+]+) OK \((?:max residual )?([0-9.eE+-]+)\)")


class SchemaError(ValueError):
    pass


def _infer_platform_from_tail(tail: str) -> Optional[str]:
    if "CPU fallback" in tail or "cpu-fallback" in tail:
        return "cpu-fallback"
    if "platform=tpu" in tail:
        return "tpu"
    if "platform=cpu" in tail:
        return "cpu"
    return None


def _flat_metrics(parsed: dict, tracked) -> dict:
    out = {}
    for name in tracked:
        cur = parsed
        for part in name.split("."):
            if not isinstance(cur, dict) or part not in cur:
                cur = None
                break
            cur = cur[part]
        if isinstance(cur, (int, float)) and not isinstance(cur, bool):
            out[name] = float(cur)
    return out


def _load(path: str):
    name = os.path.basename(path)
    try:
        with open(path) as f:
            return name, json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise SchemaError(f"{name}: unreadable JSON ({e})")


def normalize(path: str) -> dict:
    """One single-object artifact file -> one normalized record
    (SchemaError when the file fits none of the known schemas; list
    artifacts — the serve_batched row files — go through
    :func:`normalize_all`)."""
    name, obj = _load(path)
    if isinstance(obj, list):
        raise SchemaError(f"{name}: list artifact — use normalize_all")
    if isinstance(obj, dict) and obj.get("bench") in ("multichip",
                                                      "serve_mixed",
                                                      "serve_overload",
                                                      "serve_failover",
                                                      "serve_fair",
                                                      "serve_spectral",
                                                      "serve_update",
                                                      "serve_tuned"):
        raise SchemaError(f"{name}: multi-row {obj['bench']} artifact "
                          "— use normalize_all")
    if isinstance(obj, dict) and obj.get("schema") == TUNING_SCHEMA:
        raise SchemaError(f"{name}: multi-entry tuning table "
                          "— use normalize_all")
    m = _ROUND_RE.search(name)
    rnd = int(m.group(1)) if m else None
    if isinstance(obj, dict) and obj.get("bench") == "chaos":
        return _normalize_chaos(name, obj, rnd)[0]
    return _normalize_obj(name, obj, rnd)


def normalize_all(path: str) -> List[dict]:
    """Every record in one artifact file: a single object yields one
    record, a serve_batched row LIST (or a structured multichip
    artifact's ``rows``) yields one per row."""
    name, obj = _load(path)
    m = _ROUND_RE.search(name)
    rnd = int(m.group(1)) if m else None
    if isinstance(obj, list):
        if not obj:
            raise SchemaError(f"{name}: empty artifact list")
        return [_normalize_obj(f"{name}[{i}]", row, rnd)
                for i, row in enumerate(obj)]
    if isinstance(obj, dict) and obj.get("bench") == "multichip":
        return _normalize_multichip(name, obj, rnd)
    if isinstance(obj, dict) and obj.get("bench") == "serve_mixed":
        return _normalize_serve_mixed(name, obj, rnd)
    if isinstance(obj, dict) and obj.get("bench") == "serve_overload":
        return _normalize_serve_overload(name, obj, rnd)
    if isinstance(obj, dict) and obj.get("bench") == "serve_failover":
        return _normalize_serve_failover(name, obj, rnd)
    if isinstance(obj, dict) and obj.get("bench") == "serve_fair":
        return _normalize_serve_fair(name, obj, rnd)
    if isinstance(obj, dict) and obj.get("bench") == "serve_spectral":
        return _normalize_serve_spectral(name, obj, rnd)
    if isinstance(obj, dict) and obj.get("bench") == "serve_update":
        return _normalize_serve_update(name, obj, rnd)
    if isinstance(obj, dict) and obj.get("bench") == "serve_tuned":
        return _normalize_serve_tuned(name, obj, rnd)
    if isinstance(obj, dict) and obj.get("schema") == TUNING_SCHEMA:
        return _normalize_tuning(name, obj, rnd)
    if isinstance(obj, dict) and obj.get("bench") == "chaos":
        return _normalize_chaos(name, obj, rnd)
    return [_normalize_obj(name, obj, rnd)]


def _normalize_serve_overload(name: str, obj: dict,
                              rnd: Optional[int]) -> List[dict]:
    """The round-14 shedding A/B artifact: {"bench": "serve_overload",
    "platform", "n", "arms": {"shed": {...}, "no_shed": {...}}, "ok"}
    — one record per arm (the arm label rides the ``op`` series-key
    slot so the two arms never gate against each other)."""
    for k in ("platform", "n", "arms", "ok"):
        if k not in obj:
            raise SchemaError(f"{name}: serve_overload artifact "
                              f"missing {k!r}")
    arms = obj["arms"]
    if not isinstance(arms, dict) or set(arms) != {"shed", "no_shed"}:
        raise SchemaError(f"{name}: serve_overload arms must be "
                          "exactly {shed, no_shed}")
    out = []
    for arm, row in sorted(arms.items()):
        for k in ("submitted", "completed", "p99_latency_s",
                  "oldest_age_series_s"):
            if k not in row:
                raise SchemaError(
                    f"{name}[arms.{arm}]: serve_overload arm missing "
                    f"{k!r}")
        out.append({
            "round": rnd, "source": f"{name}[{arm}]",
            "kind": "serve_overload",
            "platform": str(obj["platform"]), "n": int(obj["n"]),
            "op": arm, "ok": bool(obj.get("ok", True)),
            "metrics": _flat_metrics(row, TRACKED_OVERLOAD),
        })
    return out


def _normalize_serve_failover(name: str, obj: dict,
                              rnd: Optional[int]) -> List[dict]:
    """The round-17 failover A/B artifact: {"bench": "serve_failover",
    "platform", "n", "arms": {"protected": {...}, "cold": {...}},
    "ok"} — one record per arm (arm label in the ``op`` series-key
    slot, the serve_overload convention)."""
    for k in ("platform", "n", "arms", "ok"):
        if k not in obj:
            raise SchemaError(f"{name}: serve_failover artifact "
                              f"missing {k!r}")
    arms = obj["arms"]
    if not isinstance(arms, dict) or set(arms) != {"protected", "cold"}:
        raise SchemaError(f"{name}: serve_failover arms must be "
                          "exactly {protected, cold}")
    out = []
    for arm, row in sorted(arms.items()):
        for k in ("affected_handles", "failover_s", "recovery_s_max",
                  "refactors_after_crash", "availability",
                  "wrong_answers"):
            if k not in row:
                raise SchemaError(
                    f"{name}[arms.{arm}]: serve_failover arm missing "
                    f"{k!r}")
        out.append({
            "round": rnd, "source": f"{name}[{arm}]",
            "kind": "serve_failover",
            "platform": str(obj["platform"]), "n": int(obj["n"]),
            "op": arm, "ok": bool(obj.get("ok", True)),
            "metrics": _flat_metrics(row, TRACKED_FAILOVER),
        })
    return out


def _normalize_serve_fair(name: str, obj: dict,
                          rnd: Optional[int]) -> List[dict]:
    """The round-18 tenant-isolation A/B artifact: {"bench":
    "serve_fair", "platform", "n", "arms": {"fair": {"tenants":
    {tenant: {...}}}, "fifo": {...}}, "ok"} — one record per
    (arm, tenant), the arm.tenant pair in the ``op`` series-key slot
    (the serve_overload convention) so a fair-arm victim series never
    gates against the fifo-arm one."""
    for k in ("platform", "n", "arms", "ok"):
        if k not in obj:
            raise SchemaError(f"{name}: serve_fair artifact "
                              f"missing {k!r}")
    arms = obj["arms"]
    if not isinstance(arms, dict) or set(arms) != {"fair", "fifo"}:
        raise SchemaError(f"{name}: serve_fair arms must be exactly "
                          "{fair, fifo}")
    out = []
    for arm, row in sorted(arms.items()):
        tenants = row.get("tenants")
        if not isinstance(tenants, dict) or not tenants:
            raise SchemaError(
                f"{name}[arms.{arm}]: serve_fair arm missing tenants")
        for tenant, trow in sorted(tenants.items()):
            for k in ("submitted", "completed", "quota_rejected",
                      "p99_latency_s", "reqs_per_sec"):
                if k not in trow:
                    raise SchemaError(
                        f"{name}[arms.{arm}.{tenant}]: serve_fair "
                        f"tenant row missing {k!r}")
            out.append({
                "round": rnd, "source": f"{name}[{arm}.{tenant}]",
                "kind": "serve_fair",
                "platform": str(obj["platform"]), "n": int(obj["n"]),
                "op": f"{arm}.{tenant}",
                "ok": bool(obj.get("ok", True)),
                "metrics": _flat_metrics(trow, TRACKED_FAIR),
            })
    return out


def validate_checkpoint_manifest(doc) -> List[str]:
    """Jax-free mirror of slate_tpu/runtime/checkpoint.py's
    ``validate_manifest`` (the placement-schema duplication pattern;
    tests pin the two against the same malformed docs): schema errors
    for one checkpoint manifest, empty list = valid. Accepts a parsed
    dict or a path to a manifest.json / checkpoint directory."""
    if isinstance(doc, str):
        path = doc
        if os.path.isdir(path):
            path = os.path.join(path, "manifest.json")
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            return [f"manifest unreadable ({e})"]
    errs: List[str] = []
    if not isinstance(doc, dict):
        return ["checkpoint manifest is not an object"]
    if doc.get("schema") != CHECKPOINT_SCHEMA:
        errs.append(f"schema != {CHECKPOINT_SCHEMA!r}")
    if not isinstance(doc.get("host"), str) or not doc.get("host"):
        errs.append("host missing/not a string")
    ga = doc.get("generated_at")
    if not isinstance(ga, (int, float)) or isinstance(ga, bool):
        errs.append("generated_at missing/not a number")
    records = doc.get("records")
    if not isinstance(records, list):
        return errs + ["records missing/not a list"]
    for i, rec in enumerate(records):
        if not isinstance(rec, dict):
            errs.append(f"records[{i}]: not an object")
            continue
        for k in CHECKPOINT_RECORD_KEYS:
            if k not in rec:
                errs.append(f"records[{i}]: missing {k!r}")
        if rec.get("handle_type") not in ("str", "int"):
            errs.append(f"records[{i}].handle_type: not 'str'/'int'")
        for k in ("op", "dtype"):
            if k in rec and not isinstance(rec[k], str):
                errs.append(f"records[{i}].{k}: not a string")
        for k in ("m", "n", "band", "nb", "info"):
            v = rec.get(k)
            if v is not None and (not isinstance(v, int)
                                  or isinstance(v, bool)):
                errs.append(f"records[{i}].{k}: not an int")
        mesh = rec.get("mesh")
        if mesh is not None and (not isinstance(mesh, list)
                                 or len(mesh) != 2):
            errs.append(f"records[{i}].mesh: not [p, q] or null")
        for k in ("operator", "payload"):
            errs.extend(_validate_ckpt_node(rec.get(k),
                                            f"records[{i}].{k}"))
    return errs


def _validate_ckpt_node(desc, where: str) -> List[str]:
    """Mirror of checkpoint._validate_node (see
    validate_checkpoint_manifest)."""
    if not isinstance(desc, dict) or "type" not in desc:
        return [f"{where}: not a node descriptor"]
    t = desc["type"]
    if t == "tuple":
        items = desc.get("items")
        if not isinstance(items, list):
            return [f"{where}.items: missing/not a list"]
        errs = []
        for j, d in enumerate(items):
            errs.extend(_validate_ckpt_node(d, f"{where}[{j}]"))
        return errs
    if t in ("eig_factors", "svd_factors"):
        # round-19 spectral nodes: basis matrices nest as full node
        # descriptors, the spectrum is a direct blob
        nested = ("v",) if t == "eig_factors" else ("u", "v")
        spec = "lam" if t == "eig_factors" else "s"
        errs = []
        for field in nested:
            errs.extend(_validate_ckpt_node(desc.get(field),
                                            f"{where}.{field}"))
        b = desc.get(spec)
        if not isinstance(b, dict):
            errs.append(f"{where}.{spec}: missing blob descriptor")
        else:
            for k in CHECKPOINT_BLOB_KEYS:
                if k not in b:
                    errs.append(f"{where}.{spec}: blob missing {k!r}")
        return errs
    blob_fields = {"array": ("a",), "tiled": ("data",),
                   "packed_band": ("ab",), "qr_factors": ("vr", "t")}
    if t not in blob_fields:
        return [f"{where}.type: unknown {t!r}"]
    errs = []
    for field in blob_fields[t]:
        b = desc.get(field)
        if not isinstance(b, dict):
            errs.append(f"{where}.{field}: missing blob descriptor")
            continue
        for k in CHECKPOINT_BLOB_KEYS:
            if k not in b:
                errs.append(f"{where}.{field}: blob missing {k!r}")
    return errs


def _normalize_serve_spectral(name: str, obj: dict,
                              rnd: Optional[int]) -> List[dict]:
    """The round-19 resident-spectral A/B artifact: {"bench":
    "serve_spectral", "platform", "n", "rows": [{op, resident, cold,
    speedup, one_program, ...}], "ok"} — one record per op row (the
    op in its natural series-key slot). A row that stopped being
    structurally one-program (compiles after warmup, or an apply that
    is no longer two gemms) fails schema validation outright — that
    is a broken serving claim, not a slow one."""
    for k in ("platform", "n", "nb", "requests", "rows", "ok"):
        if k not in obj:
            raise SchemaError(f"{name}: serve_spectral artifact "
                              f"missing {k!r}")
    rows = obj["rows"]
    if not isinstance(rows, list) or not rows:
        raise SchemaError(f"{name}: serve_spectral rows missing/empty")
    out = []
    for i, row in enumerate(rows):
        for k in ("op", "n", "resident", "cold", "speedup",
                  "new_compiles_after_warmup", "apply_dot_ops",
                  "census", "max_rel_err", "one_program"):
            if k not in row:
                raise SchemaError(
                    f"{name}[rows.{i}]: serve_spectral row missing "
                    f"{k!r}")
        if row["op"] not in ("eig", "svd"):
            raise SchemaError(f"{name}[rows.{i}]: serve_spectral op "
                              f"{row['op']!r} not eig/svd")
        if not row["one_program"]:
            raise SchemaError(
                f"{name}[rows.{i}]: spectral serving is no longer "
                "one-program (compiles after warmup, or an apply "
                "that is not two gemms)")
        out.append({
            "round": rnd, "source": f"{name}[{row['op']}]",
            "kind": "serve_spectral",
            "platform": str(obj["platform"]), "n": int(row["n"]),
            "op": str(row["op"]), "ok": bool(obj.get("ok", True)),
            "metrics": _flat_metrics(row, TRACKED_SPECTRAL),
        })
    return out


def _normalize_serve_update(name: str, obj: dict,
                            rnd: Optional[int]) -> List[dict]:
    """The round-20 incremental-maintenance A/B artifact: {"bench":
    "serve_update", "platform", "nb", "rows": [{op, n, k, update,
    refactor, speedup, model_flops, sync, ...}], "sync_totals", "ok"}
    — one record per (op, n, k) row, k riding the batch series slot
    (same discipline as serve_batched's B). A row that paid a full
    refactor or a recompile for a served mutation fails schema
    validation outright — that is a broken incremental-maintenance
    claim, not a slow one; so does a delta sync that costs MORE than
    the full re-transfer it exists to undercut."""
    for k in ("platform", "nb", "rows", "sync_totals", "ok"):
        if k not in obj:
            raise SchemaError(f"{name}: serve_update artifact "
                              f"missing {k!r}")
    rows = obj["rows"]
    if not isinstance(rows, list) or not rows:
        raise SchemaError(f"{name}: serve_update rows missing/empty")
    out = []
    for i, row in enumerate(rows):
        for k in ("op", "m", "n", "k", "update", "refactor", "speedup",
                  "model_flops", "sync", "new_compiles_after_warmup",
                  "update_refactors"):
            if k not in row:
                raise SchemaError(
                    f"{name}[rows.{i}]: serve_update row missing {k!r}")
        if row["op"] not in ("chol", "qr"):
            raise SchemaError(f"{name}[rows.{i}]: serve_update op "
                              f"{row['op']!r} not chol/qr")
        if row["update_refactors"] != 0:
            raise SchemaError(
                f"{name}[rows.{i}]: {row['update_refactors']} full "
                "refactors on the served-update path (the happy path "
                "is O(n²k) incremental, never a refactor)")
        if row["new_compiles_after_warmup"] != 0:
            raise SchemaError(
                f"{name}[rows.{i}]: serve_update recorded "
                f"{row['new_compiles_after_warmup']} compiles after "
                "warmup (every rank bucket must be pre-compiled)")
        sync = row["sync"]
        if not isinstance(sync, dict) or "delta_bytes" not in sync \
                or "full_bytes" not in sync:
            raise SchemaError(f"{name}[rows.{i}]: serve_update sync "
                              "split missing delta/full bytes")
        if sync["delta_bytes"] > sync["full_bytes"]:
            raise SchemaError(
                f"{name}[rows.{i}]: delta sync "
                f"({sync['delta_bytes']}B) costs more than the full "
                f"re-transfer ({sync['full_bytes']}B)")
        out.append({
            "round": rnd,
            "source": f"{name}[{row['op']}/n{row['n']}/k{row['k']}]",
            "kind": "serve_update",
            "platform": str(obj["platform"]), "n": int(row["n"]),
            "batch": int(row["k"]), "op": str(row["op"]),
            "ok": bool(row.get("ok", True)),
            "metrics": _flat_metrics(row, TRACKED_UPDATE),
        })
    return out


def _normalize_chaos(name: str, obj: dict,
                     rnd: Optional[int]) -> List[dict]:
    """The round-14 chaos-soak artifact (tools/chaos_serve.py →
    CHAOS_r*.json): schema-validated so a soak whose invariant or
    schedule sections go stale fails --check-schema; never a perf
    series (the invariants are booleans, not trajectories)."""
    for k in ("platform", "seed", "plan", "fault_classes", "phases",
              "invariants", "schedule", "ok"):
        if k not in obj:
            raise SchemaError(f"{name}: chaos artifact missing {k!r}")
    if not isinstance(obj["fault_classes"], list) \
            or not obj["fault_classes"]:
        raise SchemaError(f"{name}: chaos fault_classes missing/empty")
    inv = obj["invariants"]
    for k in ("wrong_answers", "lost_futures", "conservation_ok",
              "slo_consistent", "fleet_fold_ok",
              "schedule_reproducible",
              "noisy_neighbor_isolated", "migration_zero_refactor",
              "recorder_black_box", "forecast_leads_peak"):
        if k not in inv:
            raise SchemaError(f"{name}: chaos invariants missing {k!r}")
    if not isinstance(obj["schedule"], dict) \
            or "digest" not in obj["schedule"]:
        raise SchemaError(f"{name}: chaos schedule.digest missing")
    if "soak" not in obj.get("phases", {}):
        raise SchemaError(f"{name}: chaos phases.soak missing")
    return [{
        "round": rnd, "source": name, "kind": "chaos",
        "platform": str(obj["platform"]), "n": None,
        "ok": bool(obj["ok"]), "metrics": {},
    }]


def _validate_tuning_doc(name: str, obj) -> None:
    """Mirror of slate_tpu/tuning/table.py validate_table (tests pin
    the two validators against the same malformed docs): the committed
    TUNING_r*.json held to its schema without importing the runtime —
    a hand-edited table would otherwise be discovered by a serving
    session resolving garbage, not by CI."""
    if not isinstance(obj, dict):
        raise SchemaError(f"{name}: tuning table is not an object")
    if obj.get("schema") != TUNING_SCHEMA:
        raise SchemaError(f"{name}: schema {obj.get('schema')!r} != "
                          f"{TUNING_SCHEMA!r}")
    entries = obj.get("entries")
    if not isinstance(entries, list) or not entries:
        raise SchemaError(f"{name}: entries missing or empty")
    for i, row in enumerate(entries):
        if not isinstance(row, dict):
            raise SchemaError(f"{name}[entries.{i}]: not an object")
        for k in ("op", "dtype", "platform", "config"):
            if k not in row:
                raise SchemaError(f"{name}[entries.{i}]: missing {k!r}")
        n_max = row.get("n_max")
        if n_max is not None and (not isinstance(n_max, int)
                                  or isinstance(n_max, bool)
                                  or n_max <= 0):
            raise SchemaError(f"{name}[entries.{i}]: n_max must be a "
                              "positive int or null")
        cfg = row["config"]
        if not isinstance(cfg, dict) or not cfg:
            raise SchemaError(f"{name}[entries.{i}]: config missing "
                              "or empty")
        for k, v in cfg.items():
            if k not in TUNING_CONFIG_KEYS:
                raise SchemaError(f"{name}[entries.{i}]: unknown "
                                  f"config knob {k!r}")
            if v is not None and (not isinstance(v, int)
                                  or isinstance(v, bool) or v < 0):
                raise SchemaError(f"{name}[entries.{i}]: config "
                                  f"{k}={v!r} must be a non-negative "
                                  "int or null")


def _normalize_tuning(name: str, obj: dict,
                      rnd: Optional[int]) -> List[dict]:
    """The round-21 committed tuning table (tools/autotune.py →
    TUNING_r*.json): schema-validated (the serving runtime resolves
    configs out of this exact file), each entry's measured search
    score entering the trajectory as an informational series keyed by
    the entry's (op, n_max, dtype, platform)."""
    _validate_tuning_doc(name, obj)
    out = []
    for i, row in enumerate(obj["entries"]):
        score = row.get("score") or {}
        metrics = {}
        if isinstance(score.get("gflops"), (int, float)) \
                and not isinstance(score.get("gflops"), bool):
            metrics["tuned.gflops"] = float(score["gflops"])
        out.append({
            "round": rnd, "source": f"{name}[{i}]", "kind": "tuning",
            "platform": str(row["platform"]),
            "n": row.get("n_max"),
            "op": str(row["op"]),
            "dtype": (None if row["dtype"] in ("*", None)
                      else str(row["dtype"])),
            "ok": True, "metrics": metrics,
        })
    return out


def _normalize_serve_tuned(name: str, obj: dict,
                           rnd: Optional[int]) -> List[dict]:
    """The round-21 tuned-serving A/B artifact: {"bench":
    "serve_tuned", "platform", "table", "rows": [...]} — one
    ``serve_tuned`` record per row, series keyed by the row's
    (op, n, dtype). The compile-count columns are validated
    structural evidence (a tuned arm that compiles on the serve path
    fails schema here, not just the bench's own exit gate)."""
    for k in ("platform", "table", "rows", "ok"):
        if k not in obj:
            raise SchemaError(f"{name}: serve_tuned artifact missing "
                              f"{k!r}")
    if not isinstance(obj["rows"], list) or not obj["rows"]:
        raise SchemaError(f"{name}: serve_tuned artifact with empty "
                          "rows")
    out = []
    for i, row in enumerate(obj["rows"]):
        for k in ("op", "n", "default", "tuned", "speedup", "ok"):
            if k not in row:
                raise SchemaError(
                    f"{name}[rows.{i}]: serve_tuned row missing {k!r}")
        for arm in ("default", "tuned"):
            arm_row = row[arm]
            if not isinstance(arm_row, dict):
                raise SchemaError(f"{name}[rows.{i}]: {arm} arm not "
                                  "an object")
            for k in ("solves_per_sec", "new_compiles_after_warmup",
                      "config"):
                if k not in arm_row:
                    raise SchemaError(
                        f"{name}[rows.{i}]: {arm} arm missing {k!r}")
        out.append({
            "round": rnd, "source": f"{name}[{i}]",
            "kind": "serve_tuned",
            "platform": str(obj["platform"]), "n": int(row["n"]),
            "op": str(row["op"]),
            "dtype": str(row.get("dtype", "")) or None,
            "ok": bool(row.get("ok", True)),
            "metrics": _flat_metrics(row, TRACKED_TUNED),
        })
    return out


def _normalize_serve_mixed(name: str, obj: dict,
                           rnd: Optional[int]) -> List[dict]:
    """The round-13 mixed-precision serving artifact: {"bench":
    "serve_mixed", "platform", "factor_dtype", "rows": [...]} — one
    ``serve_mixed`` record per row, series keyed by the row's
    (op, n, dtype)."""
    for k in ("platform", "factor_dtype", "rows"):
        if k not in obj:
            raise SchemaError(f"{name}: serve_mixed artifact missing "
                              f"{k!r}")
    if not isinstance(obj["rows"], list) or not obj["rows"]:
        raise SchemaError(f"{name}: serve_mixed artifact with empty rows")
    out = []
    for i, row in enumerate(obj["rows"]):
        for k in ("op", "n", "mixed", "full", "speedup",
                  "factor_bytes_ratio"):
            if k not in row:
                raise SchemaError(
                    f"{name}[rows.{i}]: serve_mixed row missing {k!r}")
        out.append({
            "round": rnd, "source": f"{name}[{i}]",
            "kind": "serve_mixed",
            "platform": str(obj["platform"]), "n": int(row["n"]),
            "op": str(row["op"]),
            "dtype": str(row.get("dtype", "")) or None,
            "ok": bool(row.get("ok", True)),
            "metrics": _flat_metrics(row, TRACKED_SERVE_MIXED),
        })
    return out


def _normalize_multichip(name: str, obj: dict,
                         rnd: Optional[int]) -> List[dict]:
    """The round-11 structured multichip artifact: {"bench":
    "multichip", "platform", "mesh_shape", "n_devices", "rows": [...]}
    — one ``multichip_serve`` record per row, series keyed by the
    row's (op, n)."""
    for k in ("platform", "mesh_shape", "n_devices", "rows"):
        if k not in obj:
            raise SchemaError(f"{name}: multichip artifact missing {k!r}")
    if not isinstance(obj["rows"], list) or not obj["rows"]:
        raise SchemaError(f"{name}: multichip artifact with empty rows")
    out = []
    for i, row in enumerate(obj["rows"]):
        for k in ("op", "n", "serve", "single_device", "speedup"):
            if k not in row:
                raise SchemaError(
                    f"{name}[rows.{i}]: multichip row missing {k!r}")
        out.append({
            "round": rnd, "source": f"{name}[{i}]",
            "kind": "multichip_serve",
            "platform": str(obj["platform"]), "n": int(row["n"]),
            "op": str(row["op"]),
            # dtype is part of the series key: the artifact carries
            # f32 AND f64 rows per (op, n), and comparing an f64 round
            # against an f32 best-prior would fabricate a regression
            "dtype": str(row.get("dtype", "")) or None,
            "mesh_shape": list(obj["mesh_shape"]),
            "ok": bool(row.get("ok", True)),
            "metrics": _flat_metrics(row, TRACKED_MULTICHIP),
        })
    return out


def _check_tenants_section(name: str, section) -> None:
    """Validate the round-15 serve-artifact ``tenants`` section:
    per-tenant totals, the conservation verdict, and the embedded
    placement snapshot against the committed row schema (the jax-free
    mirror of obs.attribution.validate_placement_snapshot)."""
    if not isinstance(section, dict):
        raise SchemaError(f"{name}: tenants section is not an object")
    for k in ("enabled", "per_tenant", "conservation",
              "conservation_ok", "placement"):
        if k not in section:
            raise SchemaError(f"{name}: tenants section missing {k!r}")
    if not isinstance(section["per_tenant"], dict):
        raise SchemaError(f"{name}: tenants.per_tenant not an object")
    cons = section["conservation"]
    if not isinstance(cons, dict) or not cons:
        raise SchemaError(f"{name}: tenants.conservation missing/empty")
    for cls, row in cons.items():
        if not isinstance(row, dict) or "ok" not in row:
            raise SchemaError(
                f"{name}: tenants.conservation[{cls!r}] missing 'ok'")
    placement = section["placement"]
    if not isinstance(placement, dict) \
            or placement.get("schema") != PLACEMENT_SCHEMA:
        raise SchemaError(
            f"{name}: tenants.placement schema != {PLACEMENT_SCHEMA!r}")
    rows = placement.get("rows")
    if not isinstance(rows, list):
        raise SchemaError(f"{name}: tenants.placement.rows not a list")
    for i, row in enumerate(rows):
        if not isinstance(row, dict):
            raise SchemaError(
                f"{name}: tenants.placement.rows[{i}] not an object")
        for k in PLACEMENT_ROW_KEYS:
            if k not in row:
                raise SchemaError(
                    f"{name}: tenants.placement.rows[{i}] missing {k!r}")


def _check_numerics_section(name: str, section) -> None:
    """Validate the round-16 serve-artifact ``numerics`` section:
    per-handle health rows whose states come from the committed
    vocabulary, the probe counters, and the exit-gated verdict."""
    if not isinstance(section, dict):
        raise SchemaError(f"{name}: numerics section is not an object")
    for k in ("enabled", "handles", "counters", "ok"):
        if k not in section:
            raise SchemaError(f"{name}: numerics section missing {k!r}")
    handles = section["handles"]
    if not isinstance(handles, dict):
        raise SchemaError(f"{name}: numerics.handles not an object")
    for h, row in handles.items():
        if not isinstance(row, dict) or "state" not in row:
            raise SchemaError(
                f"{name}: numerics.handles[{h!r}] missing 'state'")
        if row["state"] not in HEALTH_STATES:
            raise SchemaError(
                f"{name}: numerics.handles[{h!r}].state "
                f"{row['state']!r} not one of {HEALTH_STATES}")
    if not isinstance(section["counters"], dict):
        raise SchemaError(f"{name}: numerics.counters not an object")


def _check_quotas_section(name: str, section) -> None:
    """Validate the round-18 serve-artifact ``quotas`` section: the
    declared tenant policies, per-tenant resident bytes, and the quota
    counters — a committed fixture whose quota view went missing means
    the bench session's tenant table silently fell off."""
    if not isinstance(section, dict):
        raise SchemaError(f"{name}: quotas section is not an object")
    for k in ("enabled", "tenants"):
        if k not in section:
            raise SchemaError(f"{name}: quotas section missing {k!r}")
    if not section["enabled"]:
        raise SchemaError(f"{name}: quotas section disabled (the bench "
                          "session must carry a tenant table)")
    for k in ("policies", "counters"):
        if k not in section or not isinstance(section[k], dict):
            raise SchemaError(f"{name}: quotas.{k} missing/not an "
                              "object")
    for t, row in section["tenants"].items():
        if not isinstance(row, dict) or "resident_bytes" not in row:
            raise SchemaError(
                f"{name}: quotas.tenants[{t!r}] missing resident_bytes")


def _check_spectral_section(name: str, section) -> None:
    """Validate the round-19 serve-artifact ``spectral`` section: the
    resident-eigendecomposition structural columns — zero new compiles
    across theta-varying serves, the two-gemm dot census of every
    warmed apply program, and the exit-gated verdict. A committed
    fixture whose spectral serving recompiles per theta (or whose
    apply stopped being two gemms) is a broken serving claim."""
    if not isinstance(section, dict):
        raise SchemaError(f"{name}: spectral section is not an object")
    for k in ("enabled", "op", "n", "functions",
              "new_compiles_after_warmup", "apply_dot_ops",
              "stage_programs", "solve_rel_err", "ok"):
        if k not in section:
            raise SchemaError(f"{name}: spectral section missing {k!r}")
    if section["new_compiles_after_warmup"] != 0:
        raise SchemaError(
            f"{name}: spectral section recorded "
            f"{section['new_compiles_after_warmup']} compiles after "
            "warmup (theta must be traced, never a recompile)")
    dots = section["apply_dot_ops"]
    if not isinstance(dots, dict) or not dots:
        raise SchemaError(f"{name}: spectral.apply_dot_ops "
                          "missing/empty")
    for fn, d in dots.items():
        if d != 2:
            raise SchemaError(
                f"{name}: spectral apply {fn!r} lowered to {d} dot "
                "ops (the served apply is exactly two gemms + a "
                "diagonal scale)")
    if not isinstance(section["stage_programs"], list) \
            or not section["stage_programs"]:
        raise SchemaError(f"{name}: spectral.stage_programs "
                          "missing/empty")


def _check_updates_section(name: str, section) -> None:
    """Validate the round-20 serve-artifact ``updates`` section: the
    incremental-maintenance structural columns — every mutation served
    on the O(n²k) path (zero full refactors, zero new compiles after
    warmup), nonzero update flops credited, and the exit-gated
    verdict. A committed fixture whose resident pays a refactor per
    served mutation is a broken incremental-maintenance claim."""
    if not isinstance(section, dict):
        raise SchemaError(f"{name}: updates section is not an object")
    for k in ("enabled", "op", "n", "k", "updates_applied",
              "new_compiles_after_warmup", "update_refactors",
              "refactors_during_updates", "update_flops",
              "solve_rel_err", "ok"):
        if k not in section:
            raise SchemaError(f"{name}: updates section missing {k!r}")
    if section["update_refactors"] != 0 \
            or section["refactors_during_updates"] != 0:
        raise SchemaError(
            f"{name}: updates section recorded a full refactor on the "
            "served-update path (the happy path is incremental)")
    if section["new_compiles_after_warmup"] != 0:
        raise SchemaError(
            f"{name}: updates section recorded "
            f"{section['new_compiles_after_warmup']} compiles after "
            "warmup (the rank bucket must be pre-compiled)")
    if not section["update_flops"] > 0:
        raise SchemaError(f"{name}: updates section credited no "
                          "update flops to the ledger")


def _check_tuning_section(name: str, section) -> None:
    """Validate the round-21 serve-artifact ``tuning`` section: the
    committed-table structural columns — the table loaded, a fresh
    registration resolved its config with provenance, and the warmed
    tuned solve added zero compiles on the serve path. A disabled
    section (no committed table) is valid — the tuning subsystem is
    optional by design — but a PRESENT table that recompiles on the
    serve path is a broken tuning claim."""
    if not isinstance(section, dict):
        raise SchemaError(f"{name}: tuning section is not an object")
    for k in ("enabled", "table", "resolved",
              "new_compiles_after_warmup", "ok"):
        if k not in section:
            raise SchemaError(f"{name}: tuning section missing {k!r}")
    if not section["enabled"]:
        return
    table = section["table"]
    if not isinstance(table, dict) \
            or table.get("schema") != TUNING_SCHEMA:
        raise SchemaError(f"{name}: tuning.table schema != "
                          f"{TUNING_SCHEMA!r}")
    if section["new_compiles_after_warmup"] != 0:
        raise SchemaError(
            f"{name}: tuning section recorded "
            f"{section['new_compiles_after_warmup']} compiles after "
            "warmup (the table must never put compilation back on "
            "the serve path)")


def validate_incident_doc(doc) -> List[str]:
    """Jax-free mirror of ``slate_tpu.obs.events.validate_incident``
    (tests feed both validators the same malformed docs and pin the
    verdicts equal): returns error strings, empty = valid."""
    errs = []
    if not isinstance(doc, dict):
        return [f"incident: not a dict ({type(doc).__name__})"]
    if doc.get("schema") != INCIDENT_SCHEMA:
        errs.append(f"incident: schema {doc.get('schema')!r} != "
                    f"{INCIDENT_SCHEMA!r}")
    for k in INCIDENT_KEYS:
        if k not in doc:
            errs.append(f"incident: missing key {k!r}")
    if errs:
        return errs
    if not isinstance(doc["id"], str) or not doc["id"]:
        errs.append("incident: id must be a nonempty string")
    if not isinstance(doc["ts"], (int, float)):
        errs.append("incident: ts must be a number")
    if not isinstance(doc["reason"], str) or not doc["reason"]:
        errs.append("incident: reason must be a nonempty string")
    j = doc["journal"]
    if not isinstance(j, dict) or "events" not in j or "counts" not in j:
        errs.append("incident: journal must carry events + counts")
    else:
        if not isinstance(j["events"], list):
            errs.append("incident: journal.events must be a list")
        else:
            for i, ev in enumerate(j["events"]):
                if (not isinstance(ev, dict) or not ev.get("kind")
                        or not isinstance(ev.get("ts"), (int, float))
                        or not isinstance(ev.get("count"),
                                          (int, float))):
                    errs.append(f"incident: journal.events[{i}] "
                                "malformed (kind/ts/count)")
                    break
        if not isinstance(j["counts"], dict):
            errs.append("incident: journal.counts must be a dict")
    fl = doc["flight"]
    if (not isinstance(fl, dict)
            or not isinstance(fl.get("spans"), list)
            or not isinstance(fl.get("samples"), list)):
        errs.append("incident: flight must carry spans + samples lists")
    m = doc["metrics"]
    if (not isinstance(m, dict)
            or not isinstance(m.get("counters"), dict)
            or not isinstance(m.get("gauges"), dict)):
        errs.append("incident: metrics must carry counters + gauges")
    return errs


def _check_incidents_section(name: str, section) -> None:
    """Validate the round-22 serve-artifact ``incidents`` section: the
    decision-journal/counter parity verdicts and one embedded sample
    incident held to ``slate_tpu.incident.v1`` by the mirror validator
    above — a committed fixture whose black box stopped recording (or
    whose parity broke) is a broken recorder, not a slow bench."""
    if not isinstance(section, dict):
        raise SchemaError(f"{name}: incidents section is not an object")
    for k in ("enabled", "ok", "captured", "journal_recorded",
              "parity", "sample"):
        if k not in section:
            raise SchemaError(f"{name}: incidents section missing {k!r}")
    if not section["enabled"]:
        raise SchemaError(f"{name}: incidents section disabled (the "
                          "bench session must run its recorder)")
    if not isinstance(section["parity"], dict) or not section["parity"]:
        raise SchemaError(f"{name}: incidents.parity missing/empty")
    bad = [k for k, row in section["parity"].items()
           if not (isinstance(row, dict) and row.get("ok"))]
    if bad:
        raise SchemaError(
            f"{name}: incidents.parity broken for {bad} (journal "
            "count != metric counter delta)")
    errs = validate_incident_doc(section["sample"])
    if errs:
        raise SchemaError(f"{name}: incidents.sample invalid: "
                          + "; ".join(errs))
    if not section["ok"]:
        raise SchemaError(f"{name}: incidents section verdict not ok")


def _check_forecast_section(name: str, section) -> None:
    """Validate the round-23 serve-artifact ``forecast`` section: the
    embedded /history payload held to slate_tpu.timeseries.v1, the
    embedded /forecast payload held to slate_tpu.forecast.v1 (both by
    the REAL validators, file-loaded above), and the exact
    counter-conservation table — a committed fixture whose store lost
    a count (or whose payloads fail their own schemas) is a broken
    sensing substrate, not a slow bench."""
    if not isinstance(section, dict):
        raise SchemaError(f"{name}: forecast section is not an object")
    for k in ("enabled", "ok", "series_count", "dropped_series",
              "dropped_samples", "conservation", "history",
              "forecast"):
        if k not in section:
            raise SchemaError(f"{name}: forecast section missing {k!r}")
    if not section["enabled"]:
        raise SchemaError(f"{name}: forecast section disabled (the "
                          "bench session must run its time-series "
                          "store)")
    errs = validate_timeseries_doc(section["history"])
    errs += validate_forecast_doc(section["forecast"])
    if errs:
        raise SchemaError(f"{name}: forecast payloads invalid: "
                          + "; ".join(errs))
    cons = section["conservation"]
    if not isinstance(cons, dict) or not cons:
        raise SchemaError(f"{name}: forecast.conservation missing/"
                          "empty")
    bad = [k for k, row in cons.items()
           if not (isinstance(row, dict) and row.get("ok"))]
    if bad:
        raise SchemaError(
            f"{name}: forecast.conservation broken for {bad} (store "
            "delta sum != live counter)")
    if not section["ok"]:
        raise SchemaError(f"{name}: forecast section verdict not ok")


def _normalize_obj(name: str, obj, fname_round: Optional[int]) -> dict:
    if not isinstance(obj, dict):
        raise SchemaError(f"{name}: top level is not an object")

    if obj.get("bench") == "serve_forecast":
        for k in ("platform", "n", "serve", "store", "holdout", "ok"):
            if k not in obj:
                raise SchemaError(
                    f"{name}: serve_forecast row missing {k!r}")
        hold = obj["holdout"]
        if not isinstance(hold, dict) or "improvement" not in hold:
            raise SchemaError(f"{name}: serve_forecast holdout "
                              "missing improvement")
        if hold.get("aperiodic_period_s") is not None:
            raise SchemaError(f"{name}: serve_forecast claims a "
                              "period on the aperiodic control")
        return {
            "round": fname_round, "source": name,
            "kind": "serve_forecast",
            "platform": str(obj["platform"]), "n": int(obj["n"]),
            "ok": bool(obj["ok"]),
            "metrics": _flat_metrics(obj, TRACKED_FORECAST),
        }

    if obj.get("schema") == CAPACITY_SCHEMA:
        errs = validate_capacity_doc(obj)
        if errs:
            raise SchemaError(f"{name}: " + "; ".join(errs))
        # planning artifact, never a perf series: schema-gated only
        return {
            "round": fname_round, "source": name, "kind": "capacity",
            "platform": "cpu", "n": None, "ok": True, "metrics": {},
        }

    if obj.get("bench") == "serve_batched":
        for k in ("platform", "op", "n", "batch", "batched",
                  "per_request", "speedup"):
            if k not in obj:
                raise SchemaError(
                    f"{name}: serve_batched row missing {k!r}")
        return {
            "round": fname_round, "source": name, "kind": "serve_batched",
            "platform": str(obj["platform"]), "n": int(obj["n"]),
            "batch": int(obj["batch"]), "op": str(obj["op"]), "ok": True,
            "metrics": _flat_metrics(obj, TRACKED_SERVE_BATCHED),
        }

    if obj.get("bench") == "serve":
        # the FULL current section list, not just the gating keys: a
        # committed fixture that predates a schema addition fails here
        # (regenerate with bench_serve.py --regen-smoke)
        for k in SERVE_ARTIFACT_SECTIONS:
            if k not in obj:
                raise SchemaError(
                    f"{name}: serve artifact missing section {k!r} "
                    "(stale smoke fixture? regenerate with "
                    "bench_serve.py --regen-smoke)")
        _check_tenants_section(name, obj["tenants"])
        _check_numerics_section(name, obj["numerics"])
        _check_quotas_section(name, obj["quotas"])
        _check_spectral_section(name, obj["spectral"])
        _check_updates_section(name, obj["updates"])
        _check_tuning_section(name, obj["tuning"])
        _check_incidents_section(name, obj["incidents"])
        _check_forecast_section(name, obj["forecast"])
        return {
            "round": fname_round, "source": name, "kind": "serve",
            "platform": str(obj["backend"]), "n": int(obj["n"]),
            "ok": True, "metrics": _flat_metrics(obj, TRACKED_SERVE),
        }

    if "n_devices" in obj and "rc" in obj and "bench" not in obj \
            and "cmd" not in obj:
        # rounds 1–5 multichip dry-run blob: {n_devices, rc, ok,
        # skipped, tail} with the per-driver residuals buried in the
        # tail string. Normalized as INFORMATIONAL series (the runs
        # are CPU-forced virtual meshes, and residuals are
        # lower-is-better — they never gate; they exist so the
        # trajectory read covers every committed artifact).
        tail = str(obj.get("tail", ""))
        metrics = {}
        if obj.get("ok"):
            for mm in _TAIL_RESID_RE.finditer(tail):
                key = mm.group(1).replace("+", "_")
                metrics[f"residual_{key}"] = float(mm.group(2))
        return {
            "round": fname_round, "source": name,
            "kind": "multichip_dryrun",
            "platform": _infer_platform_from_tail(tail) or "cpu",
            "n": int(obj["n_devices"]), "ok": bool(obj.get("ok")),
            "metrics": metrics,
        }

    if "cmd" in obj and "rc" in obj:  # rounds 1-5 harness wrapper
        rnd = obj.get("n", fname_round)
        if not isinstance(rnd, int):
            raise SchemaError(f"{name}: wrapper round index missing")
        ok = obj["rc"] == 0
        parsed = obj.get("parsed") or {}
        if ok and "metric" not in parsed:
            raise SchemaError(f"{name}: rc=0 wrapper without parsed "
                              "metrics")
        platform = (parsed.get("platform")
                    or _infer_platform_from_tail(str(obj.get("tail", "")))
                    or "unknown")
        n = None
        mm = _N_RE.search(parsed.get("metric", ""))
        if mm:
            n = int(mm.group(1))
        return {
            "round": rnd, "source": name, "kind": "bench",
            "platform": platform, "n": n, "ok": ok,
            "metrics": _flat_metrics(parsed, TRACKED_BENCH) if ok else {},
        }

    if "metric" in obj and "value" in obj:  # bare bench.py artifact
        mm = _N_RE.search(obj["metric"])
        return {
            "round": fname_round, "source": name, "kind": "bench",
            "platform": str(obj.get("platform", "unknown")),
            "n": int(mm.group(1)) if mm else None,
            "ok": "error" not in obj,
            "metrics": _flat_metrics(obj, TRACKED_BENCH),
        }

    raise SchemaError(f"{name}: matches no known BENCH schema "
                      "(wrapper / bench.py / serve / serve_batched)")


def discover(root: str) -> List[str]:
    paths = (glob.glob(os.path.join(root, "BENCH_r*.json"))
             + glob.glob(os.path.join(root, "BENCH_SERVE*.json"))
             + glob.glob(os.path.join(root, "BENCH_MIXED_r*.json"))
             + glob.glob(os.path.join(root, "BENCH_OVERLOAD_r*.json"))
             + glob.glob(os.path.join(root, "BENCH_FAILOVER_r*.json"))
             + glob.glob(os.path.join(root, "BENCH_FAIR_r*.json"))
             + glob.glob(os.path.join(root, "BENCH_SPECTRAL_r*.json"))
             + glob.glob(os.path.join(root, "BENCH_UPDATE_r*.json"))
             + glob.glob(os.path.join(root, "BENCH_TUNED_r*.json"))
             + glob.glob(os.path.join(root, "TUNING_r*.json"))
             + glob.glob(os.path.join(root, "BENCH_FORECAST_r*.json"))
             + glob.glob(os.path.join(root, "CAPACITY_r*.json"))
             + glob.glob(os.path.join(root, "MULTICHIP_r*.json"))
             + glob.glob(os.path.join(root, "CHAOS_r*.json")))
    # bench_serve writes <stem>.metrics.json / <stem>.prom exposition
    # fixtures beside the headline artifact — different schema, not
    # part of the trajectory
    return sorted(p for p in paths if not p.endswith(".metrics.json"))


def _series_key(rec: dict, metric: str):
    # "batch"/"op" (serve_batched rows) and "dtype" (multichip rows)
    # keep batch-size buckets, operator classes, and dtypes in
    # separate series — None for every other schema
    return (rec["kind"], metric, rec["platform"], rec["n"],
            rec.get("batch"), rec.get("op"), rec.get("dtype"))


def gate(records: List[dict], tolerance: float = DEFAULT_TOLERANCE
         ) -> dict:
    """Compare the newest gateable record of every (metric, platform,
    n) series against the best prior value. Only GATED_PLATFORMS fail
    the gate; other platforms are summarized as informational."""
    series: dict = {}
    for rec in sorted(records,
                      key=lambda r: (r["round"] is None, r["round"] or 0)):
        if not rec["ok"]:
            continue
        for metric, value in rec["metrics"].items():
            series.setdefault(_series_key(rec, metric), []).append(
                {"round": rec["round"], "source": rec["source"],
                 "value": value})
    regressions, informational = [], []
    for key, points in series.items():
        if len(points) < 2:
            continue
        *prior, last = points
        best = max(p["value"] for p in prior)
        if best <= 0:
            continue
        drop = (best - last["value"]) / best
        if drop <= tolerance:
            continue
        row = {
            "kind": key[0], "metric": key[1], "platform": key[2],
            "n": key[3], "batch": key[4], "op": key[5],
            "dtype": key[6],
            "best_prior": best, "last": last["value"],
            "drop_pct": round(100 * drop, 1),
            "last_source": last["source"],
        }
        (regressions if key[2] in GATED_PLATFORMS
         else informational).append(row)
    return {
        "rounds": sorted({r["round"] for r in records
                          if r["round"] is not None}),
        "artifacts": len(records),
        "series": len(series),
        "tolerance": tolerance,
        "regressions": regressions,
        "informational_drops": informational,
        "ok": not regressions,
    }


# -- baseline export (round 12: the watchdog's single source of truth) ------

# schema id shared with slate_tpu/obs/watchdog.py (the consumer); the
# file lives at the repo root as BASELINE_SERIES.json
BASELINE_SCHEMA = "slate_tpu.baseline_series.v1"
BASELINE_FILENAME = "BASELINE_SERIES.json"


def _direction(metric: str) -> str:
    """Per-metric regression direction: every tracked series is
    higher-is-better (GFLOP/s, solves/s, speedup) EXCEPT the
    residual_* informational series parsed off the r01–r05 multichip
    tails (smaller residual = healthier) and anything latency-,
    queue-age-, or recovery-shaped (the round-14 overload and round-17
    failover columns) — classified here so a future artifact exporting
    a latency series cannot silently enter the baseline with an
    inverted direction (the watchdog would then read a 10× p99 rise as
    an improvement). The round-20 ``sync.*`` columns (delta-vs-full
    replica transfer bytes and their ratio) are transfer COSTS —
    lower-is-better by the same rule, as are the round-23 forecast
    columns (holdout MAE, store overhead pct, record-path ns/sample
    — error and cost, not throughput)."""
    if metric.startswith("residual_") or metric.startswith("sync.") \
            or "latency" in metric \
            or "age_s" in metric or "recovery" in metric \
            or "failover" in metric or "refactor" in metric \
            or "quota" in metric or "mae" in metric \
            or "overhead" in metric or "ns_per_sample" in metric:
        return "lower"
    return "higher"


def baseline_series(records: List[dict],
                    tolerance: float = DEFAULT_TOLERANCE) -> dict:
    """Normalized records -> the BASELINE_SERIES document: one row per
    (kind, metric, platform, n, batch, op, dtype) series with its
    best-prior value — what ``gate`` compares against, exported as a
    committed artifact so ``obs.watchdog`` loads ONE source of truth
    instead of re-deriving it from nine artifact schemas at runtime."""
    series: dict = {}
    for rec in sorted(records,
                      key=lambda r: (r["round"] is None, r["round"] or 0)):
        if not rec["ok"]:
            continue
        for metric, value in rec["metrics"].items():
            series.setdefault(_series_key(rec, metric), []).append(
                {"round": rec["round"], "source": rec["source"],
                 "value": value})
    rows = []
    for key, points in series.items():
        kind, metric, platform, n, batch, op, dtype = key
        values = [p["value"] for p in points]
        direction = _direction(metric)
        best = max(values) if direction == "higher" else min(values)
        rows.append({
            "kind": kind, "metric": metric, "platform": platform,
            "n": n, "batch": batch, "op": op, "dtype": dtype,
            "direction": direction, "best": best,
            "last": values[-1], "points": len(points),
            "rounds": sorted({p["round"] for p in points
                              if p["round"] is not None}),
            "sources": sorted({p["source"] for p in points}),
        })
    rows.sort(key=lambda r: tuple("" if v is None else str(v)
                                  for v in (r["metric"], r["platform"],
                                            r["n"], r["batch"], r["op"],
                                            r["dtype"])))
    return {
        "schema": BASELINE_SCHEMA,
        "tolerance": tolerance,
        "gated_platforms": list(GATED_PLATFORMS),
        "rounds": sorted({r["round"] for r in records
                          if r["round"] is not None}),
        "series": rows,
    }


def validate_baseline_file(path: str):
    """Schema-check a committed BASELINE_SERIES.json (raises
    SchemaError) — ``--check-schema`` covers the baseline artifact like
    every BENCH/MULTICHIP file, so a hand-edited or stale-schema
    baseline fails CI instead of silently blinding the watchdog."""
    name, obj = _load(path)
    if not isinstance(obj, dict) or obj.get("schema") != BASELINE_SCHEMA:
        raise SchemaError(f"{name}: schema != {BASELINE_SCHEMA!r}")
    rows = obj.get("series")
    if not isinstance(rows, list) or not rows:
        raise SchemaError(f"{name}: series missing or empty")
    for i, row in enumerate(rows):
        for k in ("metric", "platform", "best", "direction"):
            if k not in row:
                raise SchemaError(f"{name}[series.{i}]: missing {k!r}")
        if row["direction"] not in ("higher", "lower"):
            raise SchemaError(f"{name}[series.{i}]: bad direction "
                              f"{row['direction']!r}")
        if not isinstance(row["best"], (int, float)) \
                or isinstance(row["best"], bool):
            raise SchemaError(f"{name}[series.{i}]: non-numeric best")


def check_schema(paths: List[str]) -> List[str]:
    """Validate every artifact; returns error strings (empty = clean)."""
    errors = []
    for path in paths:
        try:
            normalize_all(path)
        except SchemaError as e:
            errors.append(str(e))
    return errors


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--dir", default=None,
                   help="artifact directory (default: the repo root, "
                        "i.e. this file's parent's parent)")
    p.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE,
                   help="max fractional drop vs the best prior round "
                        f"(default {DEFAULT_TOLERANCE})")
    p.add_argument("--check-schema", action="store_true",
                   help="only validate artifact schemas (exit 1 on any "
                        "unparseable BENCH_*.json; a committed "
                        "BASELINE_SERIES.json is validated too)")
    p.add_argument("--baseline-out", default=None, metavar="PATH",
                   help="export the normalized best-prior series as a "
                        "BASELINE_SERIES.json artifact (the single "
                        "source of truth obs/watchdog.py loads) and "
                        "exit")
    args = p.parse_args(argv)
    root = args.dir or os.path.join(os.path.dirname(
        os.path.abspath(__file__)), os.pardir)
    paths = discover(root)
    if not paths:
        print(json.dumps({"ok": False,
                          "error": f"no BENCH_*.json under {root}"}))
        return 1
    errors = check_schema(paths)
    baseline_file = os.path.join(root, BASELINE_FILENAME)
    # an invalid committed baseline must not block --baseline-out:
    # that flag is the only tool that can REGENERATE the file (a
    # schema bump would otherwise chicken-and-egg the operator into
    # hand-deleting the artifact)
    if os.path.exists(baseline_file) and not args.baseline_out:
        try:
            validate_baseline_file(baseline_file)
        except SchemaError as e:
            errors.append(str(e))
    if args.check_schema:
        print(json.dumps({"checked": len(paths)
                          + int(os.path.exists(baseline_file)),
                          "schema_errors": errors, "ok": not errors}))
        return 0 if not errors else 1
    if errors:
        print(json.dumps({"ok": False, "schema_errors": errors}))
        return 1
    records = [rec for p_ in paths for rec in normalize_all(p_)]
    if args.baseline_out:
        doc = baseline_series(records, tolerance=args.tolerance)
        with open(args.baseline_out, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
            f.write("\n")
        print(json.dumps({"baseline_out": args.baseline_out,
                          "series": len(doc["series"]),
                          "rounds": doc["rounds"], "ok": True}))
        return 0
    summary = gate(records, tolerance=args.tolerance)
    print(json.dumps(summary, sort_keys=True))
    for row in summary["regressions"]:
        bat = f", B={row['batch']}" if row.get("batch") else ""
        print(f"!!! regression: {row['metric']} "
              f"[{row['platform']}, n={row['n']}{bat}] "
              f"{row['best_prior']:.1f} -> {row['last']:.1f} "
              f"(-{row['drop_pct']}%, {row['last_source']})",
              file=sys.stderr)
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
