#!/usr/bin/env python
"""Observability smoke: serve a small workload, dump every export.

Drives the full obs surface end to end — tracing ON through
Session/Batcher/Executor, the legacy SVG timeline, the Prometheus
text exposition, and the HTTP endpoint — then writes the artifacts:

  <out-dir>/trace.json    Chrome-trace/Perfetto JSON of the span tree
  <out-dir>/metrics.prom  Prometheus text (same bytes as GET /metrics)
  <out-dir>/metrics.json  Metrics snapshot JSON
  <out-dir>/costs.json    per-shape cost rows (model flops, XLA
                          bytes-accessed, temp/peak HBM, collective
                          census) + bytes ledger + roofline join
  <out-dir>/trace.svg     legacy SVG timeline (utils.trace)
  <out-dir>/slo.json      /slo burn-rate payload (round 12)
  <out-dir>/watchdog.json live-vs-baseline reports: the real committed
                          history (must be quiet) AND an injected-
                          latency fixture (must flag)
  <out-dir>/fleet.json    2-process aggregation of the run's snapshot
  <out-dir>/fleet.prom    fleet-level Prometheus text (host labels)
  <out-dir>/fleet_trace.json  2-process combined Chrome trace
  <out-dir>/history.json  /history telemetry time-series payload
  <out-dir>/forecast.json /forecast load/heat forecast payload

Exit status is nonzero if the Chrome JSON fails schema validation
(obs.validate_chrome_trace: required keys, monotone ts, span nesting),
if the span tree is disconnected, if the HTTP endpoint serves the
wrong payloads, if the round-9 cost exports are missing/incomplete
(empty cost_log, absent Prometheus bytes/HBM sections, or a mesh run
that credited zero collective bytes), if any round-23 section fails
(the /history payload rejecting its own validator, a served run whose
store recorded no series, forecast output that fails schema, counter
conservation through the store broken, or a 2-process history fold
whose counter totals are not exactly double), or if any round-12
section
fails: /slo payload without computed burn rates, lifecycle-stage
histograms or backpressure gauges missing, the watchdog flagging the
real committed history (or NOT flagging the injected regression),
``padding_waste_flops`` zero on a deliberately under-occupied bucket
or nonzero at full occupancy, any round-13 mixed-refinement assert
(``refine_iterations``/``refine_converged_total``/
``refine_fallbacks_total`` rows absent or zero where a served mixed
workload must move them, the ledger missing the ``serve.refine``
useful-vs-refinement split, or a forced non-convergent solve that
fails to fall back to a correct working-precision answer), or a
2-process aggregation whose counters are not bit-exactly double the
single-process snapshot — wired into examples/run_tests.py as the obs
smoke.

Usage: python tools/obs_dump.py [--smoke] [--out-dir DIR]
                                [--n N] [--nb NB] [--requests R]
"""

import argparse
import json
import os
import sys
import tempfile
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from slate_tpu.compat.platform import (  # noqa: E402
    collective_timeout_flag_if_supported)

# On the CPU backend, run the smoke on an 8-way virtual-device mesh so
# the MESH-driver cost telemetry (parallel.summa collective bytes —
# round 9 acceptance) is exercised; must land in XLA_FLAGS before jax
# initializes. The rendezvous-timeout raise is probe-gated exactly like
# tests/conftest.py (unknown XLA_FLAGS abort some jaxlib builds).
if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
    _flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in _flags:
        _flags = (_flags + " --xla_force_host_platform_device_count=8"
                  ).strip()
        _flags += collective_timeout_flag_if_supported(
            cache_path=os.path.join(os.path.dirname(__file__), os.pardir,
                                    ".xla_flag_probe.json"))
        os.environ["XLA_FLAGS"] = _flags

import numpy as np  # noqa: E402


def run(out_dir, n=96, nb=32, requests=12, slow_threshold=None):
    import jax

    import slate_tpu as st
    from slate_tpu import obs
    from slate_tpu.runtime import Executor, Session
    from slate_tpu.utils import trace as legacy_trace

    os.makedirs(out_dir, exist_ok=True)
    fails = []

    tracer = obs.Tracer(slow_threshold=slow_threshold)
    tracer.on()
    legacy_trace.Trace.clear()
    legacy_trace.Trace.on()

    rng = np.random.default_rng(5)
    spd = rng.standard_normal((n, n))
    spd = spd @ spd.T + n * np.eye(n)
    A = st.hermitian(np.tril(spd), nb=nb, uplo=st.Uplo.Lower)

    # round 18: a declared tenant table through the smoke — the quota
    # gauges, the /tenants "quotas" section, and the fair-share
    # deficit gauges below are exit-gated
    from slate_tpu.runtime import TenantPolicy
    sess = Session(tracer=tracer, tenant_policies={
        "tenant-a": TenantPolicy(weight=2.0),
        "tenant-b": TenantPolicy(weight=1.0,
                                 max_resident_bytes=64 << 20)})
    # round 12: SLO tracking on — default objectives PLUS the round-16
    # residual objective, so the sampled probes below feed a
    # residual-kind burn rate the /slo payload must evaluate
    from slate_tpu.obs.slo import Objective, default_objectives
    sess.enable_slo(default_objectives() + (
        Objective("sampled_residual", "residual", 0.99,
                  threshold_s=1e-2),))
    # round 16: numerical-health telemetry with a probe-every-solve
    # sampler (deterministic) — the handle_health gauges, /numerics
    # payload, and probe counters below are exit-gated
    sess.enable_numerics(sample_fraction=1.0, sample_seed=12)
    # round 15: tenant attribution on BEFORE any traffic (the
    # conservation check below compares per-tenant sums against the
    # session-lifetime global counters, so every credited event must
    # be attributed)
    sess.enable_attribution()
    # round 22: flight recorder + decision journal + incident capture
    # on from the FIRST request (journal/counter parity below is
    # absolute equality, so the recorder must predate any reflex)
    sess.enable_recorder(incident_dir=os.path.join(out_dir,
                                                   "incidents"))
    # round 23: telemetry history on before any traffic (interval 0 so
    # every explicit pump records — the smoke is pump-driven, not
    # wall-clock-throttled); the /history, /forecast, and 2-process
    # fold sections below are exit-gated
    store = sess.enable_timeseries(interval_s=0.0)
    h = sess.register(A, op="chol", tenant="tenant-a")
    srv = sess.serve_obs()  # opt-in HTTP endpoint, ephemeral port
    try:
        bs = [rng.standard_normal(n) for _ in range(requests)]
        with Executor(sess, max_batch=4, max_wait=1e-3) as ex:
            ex.warmup([h])
            futs = [ex.submit(h, b) for b in bs]
            xs = []
            for f in futs:
                xs.append(f.result(timeout=120))
                sess.pump_timeseries()  # per-result history samples
        resid = max(float(np.abs(spd @ x - b).max()) / n
                    for x, b in zip(xs, bs))
        if not resid < 1e-2:
            fails.append(f"serving residual too large: {resid}")

        # -- mesh-driver cost telemetry (round 9) ---------------------
        # one explicitly-scheduled SUMMA gemm over a 2x2 grid: its
        # compiled program's collective census must land in the bytes
        # ledger (the acceptance's "collective bytes for at least one
        # mesh driver"). Skipped (honestly) below 4 devices.
        mesh_ran = False
        if len(jax.devices()) >= 4:
            from slate_tpu.core.grid import ProcessGrid
            from slate_tpu.parallel.summa import gemm_summa

            g = ProcessGrid.create(2, 2)
            Ag = st.from_dense(rng.standard_normal((n, n)), nb=nb, grid=g)
            Bg = st.from_dense(rng.standard_normal((n, n)), nb=nb, grid=g)
            Cg = st.zeros(n, n, nb, Ag.dtype, grid=g)
            out = gemm_summa(1.0, Ag, Bg, 0.0, Cg)
            gres = float(np.abs(out.to_numpy()
                                - Ag.to_numpy() @ Bg.to_numpy()).max()) / n
            if not gres < 1e-2:
                fails.append(f"summa residual too large: {gres}")
            mesh_ran = True

        # -- exports --------------------------------------------------
        spans = tracer.spans()
        trace_path = os.path.join(out_dir, "trace.json")
        obs.write_chrome_trace(spans, trace_path)
        with open(trace_path) as f:
            errs = obs.validate_chrome_trace(json.load(f))
        if errs:
            fails.append(f"chrome-trace schema: {errs[:3]}")

        # connectedness: every parent_id resolves to a recorded span
        ids = {s.span_id for s in spans}
        dangling = [s for s in spans
                    if s.parent_id is not None and s.parent_id not in ids]
        if dangling:
            fails.append(f"span tree disconnected: {len(dangling)} orphans")
        if not any(s.name == "serve.batch" for s in spans):
            fails.append("no serve.batch span recorded")
        if not any(s.kind == "request" for s in spans):
            fails.append("no request spans recorded")

        sess.metrics.to_json(os.path.join(out_dir, "metrics.json"))
        prom = obs.render_prometheus(sess.metrics)
        with open(os.path.join(out_dir, "metrics.prom"), "w") as f:
            f.write(prom)
        if "slate_tpu_solves_total" not in prom:
            fails.append("prometheus text missing solves_total")
        # round-9 sections: bytes/collective ledgers + HBM gauges
        for needle in ("slate_tpu_driver_bytes_total",
                       "slate_tpu_collective_bytes_total",
                       "slate_tpu_peak_hbm_bytes",
                       "slate_tpu_resident_bytes"):
            if needle not in prom:
                fails.append(f"prometheus text missing {needle}")

        # -- cost exports (round 9): per-shape rows + ledgers ---------
        bytes_snap = obs.costs.BYTES.snapshot()
        costs_doc = {
            "cost_log": sess.cost_log,
            "bytes_ledger": bytes_snap,
            "roofline": obs.roofline.roofline_report(),
        }
        with open(os.path.join(out_dir, "costs.json"), "w") as f:
            json.dump(costs_doc, f, indent=2, sort_keys=True)
            f.write("\n")
        # schema: every AOT-compiled shape exports the full cost row
        if not sess.cost_log:
            fails.append("cost_log empty: AOT seam harvested nothing")
        for row in sess.cost_log:
            for k in ("op", "what", "shape", "model_flops",
                      "bytes_accessed", "temp_bytes", "peak_bytes",
                      "collective_bytes"):
                if k not in row:
                    fails.append(f"cost_log row missing {k!r}")
                    break
        if mesh_ran:
            summa_ops = [op for op in bytes_snap["per_op"]
                         if op.startswith("parallel.summa")]
            if not summa_ops:
                fails.append("mesh driver credited no bytes-ledger op")
            elif not any(bytes_snap["per_op"][op]["collective_bytes"] > 0
                         for op in summa_ops):
                fails.append("mesh driver recorded zero collective bytes")

        svg = legacy_trace.Trace.finish(os.path.join(out_dir, "trace.svg"))
        if svg is None:
            fails.append("SVG timeline empty (span bridge broken)")

        # -- SLO payload (round 12) -----------------------------------
        slo_payload = sess.slo.evaluate()
        with open(os.path.join(out_dir, "slo.json"), "w") as f:
            json.dump(slo_payload, f, indent=2, sort_keys=True)
            f.write("\n")
        objs = slo_payload.get("objectives", [])
        if not objs:
            fails.append("/slo payload has no objectives")
        req_rows = [o for o in objs if o["kind"] in ("latency",
                                                     "error_rate")]
        if not any(w["burn_rate"] is not None
                   for o in req_rows for w in o["windows"]):
            fails.append("slo: no burn rate computed over the served "
                         "traffic")
        if "slate_tpu_slo_burn_rate" not in obs.render_prometheus(
                sess.metrics, ledger=False, bytes_ledger=False):
            fails.append("prometheus text missing slo burn-rate gauges")

        # lifecycle stages + backpressure (tentpole c / satellite 1)
        hists = sess.metrics.snapshot()["histograms"]
        for stage in ("stage_queue_wait", "stage_batch_form",
                      "stage_dispatch", "stage_device_execute",
                      "stage_reply"):
            if not hists.get(stage, {}).get("count"):
                fails.append(f"lifecycle stage histogram {stage} empty")
            elif not (hists[stage].get("exemplar") or {}).get("trace_id"):
                fails.append(f"stage {stage}: no exemplar trace-id")
        gsnap = sess.metrics.snapshot()["gauges"]
        for g in ("queue_depth", "queued_buckets", "oldest_request_age_s",
                  "max_bucket_backlog", "inflight_batches"):
            if g not in gsnap:
                fails.append(f"backpressure gauge {g} missing")

        # -- watchdog: real history quiet, injected regression flagged -
        wd = obs.Watchdog(metrics=sess.metrics, tracer=tracer)
        wd.watch_session(sess, platform=jax.default_backend(), n=n)
        # replay every committed series at its own best: on a CPU host
        # the anomalies list is empty BY POLICY (cpu never gates), so
        # the meaningful quiet-check is matched-every-series with zero
        # informational drops — a drop would mean the baseline
        # disagrees with itself
        baseline_doc = obs.watchdog.load_baseline()
        for row in baseline_doc["series"]:
            wd.observe(row["metric"], row["best"], row["platform"],
                       n=row["n"], op=row["op"], batch=row["batch"],
                       dtype=row["dtype"], kind=row["kind"])
        real_rep = wd.check()
        if real_rep["anomalies"] or real_rep["informational"]:
            fails.append("watchdog flagged the real committed history: "
                         f"{(real_rep['anomalies'] or real_rep['informational'])[:2]}")
        if real_rep["matched"] < len(baseline_doc["series"]):
            fails.append(
                f"watchdog matched only {real_rep['matched']} of "
                f"{len(baseline_doc['series'])} committed series")
        injected = {
            "schema": "slate_tpu.baseline_series.v1", "tolerance": 0.10,
            "series": [{"kind": "serve", "metric": "request_latency_p99",
                        "platform": "tpu", "n": n, "batch": None,
                        "op": None, "dtype": None, "direction": "lower",
                        "best": 1e-6}],
        }
        wd2 = obs.Watchdog(baseline=injected, metrics=sess.metrics,
                           tracer=tracer)
        # the injected-latency fixture: live p99 orders of magnitude
        # above the synthetic committed best MUST flag
        lat = sess.metrics.snapshot()["histograms"]["request_latency"]
        wd2.observe("request_latency_p99", max(lat["p99"], 1e-3), "tpu",
                    n=n, kind="serve")
        inj_rep = wd2.check()
        if not inj_rep["anomalies"]:
            fails.append("watchdog missed the injected latency "
                         "regression")
        if not any(s.name == "watchdog.anomaly" for s in tracer.spans()):
            fails.append("no watchdog.anomaly trace event recorded")
        with open(os.path.join(out_dir, "watchdog.json"), "w") as f:
            json.dump({"real_history": real_rep, "injected": inj_rep},
                      f, indent=2, sort_keys=True)
            f.write("\n")

        # -- padding-waste ledger (tentpole c acceptance) ---------------
        # 3 distinct lu_small operators -> pow2 bucket 4 -> one padded
        # lane of REAL flops; a 4-of-4 bucket must credit exactly 0
        rng2 = np.random.default_rng(7)
        under = Session()
        hs = [under.register(rng2.standard_normal((16, 16))
                             + 16 * np.eye(16), op="lu_small")
              for _ in range(3)]
        under.solve_small_batched(hs, [rng2.standard_normal((16, 1))
                                       for _ in hs])
        if not under.metrics.get("padding_waste_flops") > 0:
            fails.append("padding_waste_flops == 0 on an under-occupied "
                         "bucket")
        full = Session()
        hf = [full.register(rng2.standard_normal((16, 16))
                            + 16 * np.eye(16), op="lu_small")
              for _ in range(4)]
        full.solve_small_batched(hf, [rng2.standard_normal((16, 1))
                                      for _ in hf])
        if full.metrics.get("padding_waste_flops") != 0:
            fails.append("padding_waste_flops != 0 at full occupancy")
        if "slate_tpu_padding_waste_flops" not in obs.render_prometheus(
                under.metrics, ledger=False, bytes_ledger=False):
            fails.append("prometheus text missing padding_waste_flops")
        if obs.flops.LEDGER.snapshot()["per_op"].get(
                "padding.waste", 0) <= 0:
            fails.append("process ledger has no padding.waste op")

        # -- mixed-precision refinement telemetry (round 13) ------------
        # a served refined workload must surface: the refine_iterations
        # histogram, the converged counter, the useful-vs-refinement
        # ledger split (serve.refine beside serve.solve), and — from a
        # deliberately non-convergent operator — the counted fallback
        # that still returns a correct solve
        from slate_tpu.refine import RefinePolicy
        rng3 = np.random.default_rng(9)
        mbase = rng3.standard_normal((48, 48)).astype(np.float32)
        mspd = mbase @ mbase.T + 48 * np.eye(48, dtype=np.float32)
        msess = Session()
        mh = msess.register(
            st.hermitian(np.tril(mspd), nb=16, uplo=st.Uplo.Lower),
            op="chol", refine=RefinePolicy(factor_dtype="bfloat16"))
        mb = rng3.standard_normal(48).astype(np.float32)
        mx = msess.solve(mh, mb)
        if not float(np.abs(mspd @ mx - mb).max()) / 48 < 1e-2:
            fails.append("served mixed solve residual too large")
        msnap = msess.metrics.snapshot()
        if not msnap["histograms"].get("refine_iterations",
                                       {}).get("count"):
            fails.append("refine_iterations histogram empty after a "
                         "served mixed solve")
        if not msnap["counters"].get("refine_converged_total"):
            fails.append("refine_converged_total not incremented")
        mprom = obs.render_prometheus(msess.metrics)
        for needle in ("slate_tpu_refine_iterations",
                       "slate_tpu_refine_converged_total",
                       "slate_tpu_refine_flops_total"):
            if needle not in mprom:
                fails.append(f"prometheus text missing {needle}")
        lsnap = obs.flops.LEDGER.snapshot()["per_op"]
        if lsnap.get("serve.refine", 0) <= 0:
            fails.append("process ledger has no serve.refine op (the "
                         "useful-vs-refinement split)")
        # forced non-convergence: an impossible tolerance -> counted
        # fallback through a working-precision refactor, answer still
        # correct
        fh = msess.register(
            st.hermitian(np.tril(mspd), nb=16, uplo=st.Uplo.Lower),
            op="chol",
            refine=RefinePolicy(factor_dtype="bfloat16", max_iters=2,
                                tol=1e-14))
        fx = msess.solve(fh, mb)
        if not float(np.abs(mspd @ fx - mb).max()) / 48 < 1e-2:
            fails.append("refine fallback returned a wrong solve")
        if msess.metrics.get("refine_fallbacks_total") != 1:
            fails.append("refine_fallbacks_total != 1 after a forced "
                         "non-convergent solve")
        if "slate_tpu_refine_fallbacks_total" not in \
                obs.render_prometheus(msess.metrics, ledger=False,
                                      bytes_ledger=False):
            fails.append("prometheus text missing "
                         "refine_fallbacks_total")

        # -- tenant attribution + placement (round 15) ------------------
        # a second tenant's small operator joins the session so the
        # ledger has ≥2 tenants; then: bit-exact conservation per
        # counter class, a schema-valid placement snapshot, the
        # /tenants route, tenant_* prom sections — all exit-gating
        from slate_tpu.obs.attribution import (
            CLASSES, validate_placement_snapshot)
        hb = sess.register(rng.standard_normal((16, 16))
                           + 16 * np.eye(16), op="lu_small",
                           tenant="tenant-b")
        for _ in range(3):
            sess.solve(hb, rng.standard_normal(16))
        att_snap = sess.attribution.snapshot()
        if set(att_snap["tenants"]) < {"tenant-a", "tenant-b"}:
            fails.append("attribution missing a registered tenant: "
                         f"{sorted(att_snap['tenants'])}")
        for cls, counter in CLASSES.items():
            cells = att_snap["totals"].get(cls, 0.0)
            glob = sess.metrics.get(counter)
            if cells != glob:
                fails.append(
                    f"attribution conservation broken for {cls}: "
                    f"per-tenant sum {cells!r} != global {glob!r}")
        placement = sess.placement_snapshot()
        perrs = validate_placement_snapshot(placement)
        if perrs:
            fails.append(f"placement snapshot schema: {perrs[:3]}")
        if not placement["rows"]:
            fails.append("placement snapshot has no resident rows")
        if not any(r["heat"] > 0 for r in placement["rows"]):
            fails.append("placement snapshot rows carry no heat")
        with open(os.path.join(out_dir, "placement.json"), "w") as f:
            json.dump(placement, f, indent=2, sort_keys=True)
            f.write("\n")
        with open(os.path.join(out_dir, "tenants.json"), "w") as f:
            json.dump(sess.tenants_payload(), f, indent=2,
                      sort_keys=True)
            f.write("\n")
        tprom = obs.render_prometheus(sess.metrics, ledger=False,
                                      bytes_ledger=False,
                                      attribution=sess.attribution)
        for needle in ("slate_tpu_tenant_solve_flops_total",
                       'tenant="tenant_b"', "slate_tpu_handle_heat"):
            if needle not in tprom:
                fails.append(f"prometheus text missing {needle}")
        # 2-process fold of the attribution cells + placement rows:
        # counters double bit-exactly, the folded per-tenant rows sum
        # to the folded globals, per-host placement rows survive
        att_fleet = obs.aggregate.merge_attribution_snapshots(
            [att_snap := sess.attribution.snapshot(), att_snap])
        msnap0 = sess.metrics.snapshot()
        for cls, counter in CLASSES.items():
            folded = att_fleet["totals"].get(cls, 0.0)
            want = 2 * msnap0["counters"].get(counter, 0.0)
            if folded != want:
                fails.append(
                    f"fleet attribution conservation broken for {cls}:"
                    f" {folded!r} != 2x global {want!r}")
        pl_fleet = obs.aggregate.merge_placement_snapshots(
            [placement, dict(placement, host="other")])
        if len(pl_fleet["rows"]) != 2 * len(placement["rows"]):
            fails.append("fleet placement fold lost rows")
        if "tenant-a" not in pl_fleet["per_tenant"]:
            fails.append("fleet placement rollup missing tenant-a")

        # -- tenant quotas + weighted-fair dispatch (round 18) ----------
        # the session carries a declared TenantTable: the /tenants
        # payload must expose the quota view, the per-tenant quota
        # gauges must be in the Prometheus text, and a two-tenant
        # ready snapshot pumped through a Batcher must engage the
        # deficit scheduler (fair_share_deficit gauges published)
        tpay = sess.tenants_payload()
        q = tpay.get("quotas", {})
        if not q.get("enabled"):
            fails.append("/tenants payload missing an enabled quotas "
                         "section")
        if "tenant-a" not in q.get("tenants", {}):
            fails.append("quotas section missing tenant-a resident row")
        from slate_tpu.runtime import Batcher
        qbat = Batcher(sess, max_batch=4, max_wait=3600.0)
        qfuts = ([qbat.submit(h, rng.standard_normal(n))
                  for _ in range(4)]
                 + [qbat.submit(hb, rng.standard_normal(16),
                                tenant="tenant-b")
                    for _ in range(4)])
        qbat.flush()
        for f in qfuts:
            f.result(timeout=120)
        qprom = obs.render_prometheus(sess.metrics, ledger=False,
                                      bytes_ledger=False)
        for needle in ("slate_tpu_tenant_quota_resident_bytes_tenant_a",
                       "slate_tpu_fair_share_deficit_tenant_"):
            if needle not in qprom:
                fails.append(f"prometheus text missing {needle}")

        # -- numerical-health telemetry (round 16) ----------------------
        # the served SPD workload above ran with a probe-every-solve
        # sampler and factor-time condest: the handle_health gauge
        # rows must be in the Prometheus text, the /numerics payload
        # must carry the handle's signals (healthy — the operand is
        # well-conditioned by construction), the probe/condest
        # counters must have moved, and the residual SLO objective
        # must have computed a burn rate over the probe stream
        npay = sess.numerics_payload()
        with open(os.path.join(out_dir, "numerics.json"), "w") as f:
            json.dump(npay, f, indent=2, sort_keys=True)
            f.write("\n")
        if not npay.get("enabled") or not npay.get("handles"):
            fails.append("numerics payload empty after a served probed "
                         "workload")
        else:
            hrow = next(iter(npay["handles"].values()))
            if hrow["state"] != "healthy":
                fails.append("well-conditioned operand classified "
                             f"{hrow['state']!r}, not healthy")
            if not hrow.get("condest"):
                fails.append("numerics payload missing the factor-time "
                             "condest")
            if not hrow.get("resid_count"):
                fails.append("numerics payload recorded no sampled "
                             "residuals")
        ncnt = npay.get("counters", {})
        for c in ("residual_probes_total", "condest_runs_total",
                  "condest_solves_total"):
            if not ncnt.get(c):
                fails.append(f"numerics counter {c} did not move")
        nprom = obs.render_prometheus(sess.metrics, ledger=False,
                                      bytes_ledger=False)
        for needle in ("slate_tpu_handle_health",
                       "slate_tpu_sampled_residual",
                       "slate_tpu_residual_probes_total"):
            if needle not in nprom:
                fails.append(f"prometheus text missing {needle}")
        if obs.flops.LEDGER.snapshot()["per_op"].get(
                "numerics.condest", 0) <= 0:
            fails.append("process ledger has no numerics.condest op "
                         "(probe work must be credited, not free)")
        slo_rows2 = sess.slo.evaluate()["objectives"]
        rrow = next((o for o in slo_rows2
                     if o["kind"] == "residual"), None)
        if rrow is None:
            fails.append("/slo payload missing the residual objective")
        elif not any(w["burn_rate"] is not None for w in rrow["windows"]):
            fails.append("residual SLO objective computed no burn rate "
                         "over the probe stream")

        # -- 2-process aggregation (tentpole d) -------------------------
        # same-snapshot fold: the acceptance's bit-exactness check —
        # merging a snapshot with itself must exactly double every
        # counter (and the combined trace must stay schema-valid)
        snap = sess.metrics.snapshot()
        fleet = obs.aggregate.aggregate_processes(
            [snap, snap], flop_snaps=[obs.flops.LEDGER.snapshot()] * 2,
            bytes_snaps=[obs.costs.BYTES.snapshot()] * 2,
            hosts=["proc0", "proc1"])
        merged = fleet["metrics"]["counters"]
        for k2, v2 in snap["counters"].items():
            if merged.get(k2) != 2 * v2:
                fails.append(f"aggregation not bit-exact for {k2}: "
                             f"{merged.get(k2)} != 2*{v2}")
                break
        obs.aggregate.write_fleet(
            fleet, json_path=os.path.join(out_dir, "fleet.json"),
            prom_path=os.path.join(out_dir, "fleet.prom"))
        with open(os.path.join(out_dir, "fleet.prom")) as f:
            fprom = f.read()
        if 'host="proc1"' not in fprom:
            fails.append("fleet prometheus missing host-labeled gauges")
        with open(trace_path) as f:
            one_trace = json.load(f)
        combined = obs.combine_process_traces([one_trace, one_trace],
                                              ["proc0", "proc1"])
        cerrs = obs.validate_chrome_trace(combined)
        if cerrs:
            fails.append(f"combined fleet trace invalid: {cerrs[:2]}")
        pids = {e.get("pid") for e in combined["traceEvents"]}
        if not (pids & set(range(0, 3))) or not (pids & set(range(100,
                                                                 103))):
            fails.append("combined trace pids not namespaced per process")
        with open(os.path.join(out_dir, "fleet_trace.json"), "w") as f:
            json.dump(combined, f, indent=1)
            f.write("\n")

        # -- checkpoint round trip (round 17) ---------------------------
        # the served session's resident state checkpoints to disk, the
        # manifest validates under BOTH the runtime validator and the
        # jax-free bench_gate mirror, a fresh session restores it with
        # ZERO refactors and a bit-identical solve, the restored heat/
        # health carry over, and the checkpoint-derived placement doc
        # folds as a partial host — all exit-gating
        from slate_tpu.runtime import Session as _Session
        from slate_tpu.runtime.checkpoint import validate_manifest
        ckpt_dir = os.path.join(out_dir, "checkpoint")
        manifest = sess.checkpoint(ckpt_dir)
        if not manifest["records"]:
            fails.append("checkpoint wrote no resident records")
        import importlib.util as _ilu
        _bg_spec = _ilu.spec_from_file_location(
            "_bench_gate", os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "bench_gate.py"))
        _bg = _ilu.module_from_spec(_bg_spec)
        _bg_spec.loader.exec_module(_bg)
        for which, errs2 in (
                ("runtime", validate_manifest(manifest)),
                ("bench_gate mirror",
                 _bg.validate_checkpoint_manifest(ckpt_dir))):
            if errs2:
                fails.append(f"checkpoint manifest failed the {which} "
                             f"validator: {errs2[:2]}")
        b_ck = rng.standard_normal(n).astype(np.float64)
        # both comparison solves run UNPROBED (numerics off for the
        # reference, sample_fraction=0 for the restored twin): the
        # fused probe program is a different executable than the plain
        # solve, and the bit-identity claim is plain-vs-plain
        saved_nm, sess.numerics = sess.numerics, None
        x_pre = sess.solve(h, b_ck)
        sess.numerics = saved_nm
        rsess = _Session()
        rsess.enable_attribution()
        rsess.enable_numerics(sample_fraction=0.0)
        rsumm = rsess.restore(ckpt_dir)
        if set(rsumm["restored"]) != {r2["handle"] for r2
                                      in manifest["records"]}:
            fails.append(f"restore summary incomplete: {rsumm}")
        x_post = rsess.solve(h, b_ck)
        if np.asarray(x_pre).tobytes() != np.asarray(x_post).tobytes():
            fails.append("restored resident's solve is not "
                         "bit-identical to the pre-checkpoint solve")
        if rsess.metrics.get("factors_total") != 0:
            fails.append("restore refactored (warm restart must not)")
        if not rsess.attribution.heat(h) > 0:
            fails.append("restored handle carried no heat")
        if rsess.numerics.health(h) is None:
            fails.append("restored handle carried no health state")
        # partial-host fold: the checkpoint stands in for a crashed
        # host whose live snapshot is gone
        part = obs.aggregate.placement_from_checkpoint(manifest,
                                                       host="dead0")
        pl_part = obs.aggregate.merge_placement_snapshots(
            [placement, part])
        if pl_part.get("partial_hosts") != ["dead0"]:
            fails.append("partial-host placement fold did not mark "
                         f"the checkpoint host: {pl_part.get('partial_hosts')}")
        if not any(r2["host"] == "dead0" for r2 in pl_part["rows"]):
            fails.append("partial-host fold lost the checkpoint rows")
        att_part = obs.aggregate.merge_attribution_snapshots(
            [sess.attribution.snapshot(), None])
        if att_part.get("partial_processes") != 1:
            fails.append("attribution fold did not count the partial "
                         "host")

        # -- decision journal + incidents (round 22) --------------------
        # an explicit eviction gives the journal a decision whose
        # counter parity is absolute (recorder predates the register),
        # and a probe incident drives the crash-safe capture path
        sess.evict(h)
        jp = sess.recorder.journal.payload()
        if not jp["events"]:
            fails.append("decision journal empty after an explicit "
                         "evict")
        if jp["counts"].get("eviction") != sess.metrics.get("evictions"):
            fails.append("journal eviction count != evictions counter: "
                         f"{jp['counts'].get('eviction')} != "
                         f"{sess.metrics.get('evictions')}")
        sess.recorder.incident("obs_dump_probe", key="smoke", handle=h)
        ip = sess.recorder.incidents.payload()
        if not ip["incidents"]:
            fails.append("probe incident was not captured")
        else:
            ierrs = obs.validate_incident(ip["incidents"][-1])
            if ierrs:
                fails.append(f"captured incident schema: {ierrs[:3]}")
        idir = os.path.join(out_dir, "incidents")
        on_disk = ([f2 for f2 in os.listdir(idir) if f2.endswith(".json")]
                   if os.path.isdir(idir) else [])
        if not on_disk:
            fails.append("incident capture published no on-disk "
                         "snapshot")
        # 2-process journal fold: counts conserved exactly, events
        # host-labeled (the fleet view of "why did N processes shed")
        jf = obs.aggregate.merge_journal_payloads([jp, jp],
                                                  hosts=["p0", "p1"])
        for k3, v3 in jp["counts"].items():
            if jf["counts"].get(k3) != 2 * v3:
                fails.append(f"journal fold not exact for {k3}: "
                             f"{jf['counts'].get(k3)} != 2*{v3}")
                break
        if jf.get("recorded") != 2 * jp["recorded"]:
            fails.append("journal fold lost recorded totals")
        if jp["events"] and not all(e3.get("host") in ("p0", "p1")
                                    for e3 in jf["events"]):
            fails.append("journal fold events not host-labeled")
        iflt = obs.aggregate.merge_incident_payloads([ip, ip],
                                                     hosts=["p0", "p1"])
        if len(iflt["incidents"]) != 2 * len(ip["incidents"]):
            fails.append("incident fold dropped incidents")

        # -- telemetry history + forecasting (round 23) -----------------
        # the served run above pumped a sample per completed request:
        # the /history payload must self-validate and carry series, the
        # /forecast payload must self-validate, every counter series
        # total must equal the live counter EXACTLY (delta-storage
        # conservation), and a 2-process fold must double the counter
        # totals bit-exactly with host-labeled series
        sess.pump_timeseries(force=True)
        hist = store.payload()
        terrs = obs.validate_timeseries(hist)
        if terrs:
            fails.append(f"/history payload schema: {terrs[:3]}")
        if not hist["series"]:
            fails.append("history store recorded no series over a "
                         "served run")
        with open(os.path.join(out_dir, "history.json"), "w") as f:
            json.dump(hist, f, indent=2, sort_keys=True)
            f.write("\n")
        fc = sess.forecaster.payload(horizon_s=60.0, k=8,
                                     max_series=64, points_limit=8)
        ferrs = obs.validate_forecast(fc)
        if ferrs:
            fails.append(f"/forecast payload schema: {ferrs[:3]}")
        with open(os.path.join(out_dir, "forecast.json"), "w") as f:
            json.dump(fc, f, indent=2, sort_keys=True)
            f.write("\n")
        ctot = store.counter_totals()
        if not ctot:
            fails.append("history store tracked no counter series")
        csnap = sess.metrics.snapshot()["counters"]
        for nm, total in ctot.items():
            if total != csnap.get(nm, 0.0):
                fails.append("history counter conservation broken for "
                             f"{nm}: store {total!r} != live "
                             f"{csnap.get(nm)!r}")
                break
        ts_fleet = obs.aggregate.merge_timeseries_payloads(
            [hist, hist], hosts=["p0", "p1"])
        for nm, total in ts_fleet.get("counter_totals", {}).items():
            if total != 2 * ctot.get(nm, 0.0):
                fails.append("history fold counter totals not exact "
                             f"for {nm}: {total!r} != 2*{ctot.get(nm)!r}")
                break
        if hist["series"] and not any(
                k4.startswith(("p0:", "p1:"))
                for k4 in ts_fleet["series"]):
            fails.append("history fold series not host-labeled")

        # -- HTTP endpoint --------------------------------------------
        for path, needle in (("/metrics", "slate_tpu_solves_total"),
                             ("/healthz", '"status": "ok"'),
                             ("/trace.json", "traceEvents"),
                             ("/slo", '"objectives"'),
                             ("/numerics", '"handles"'),
                             ("/journal", '"slate_tpu.journal.v1"'),
                             ("/incidents",
                              '"slate_tpu.incidents.v1"'),
                             ("/history",
                              '"slate_tpu.timeseries.v1"'),
                             ("/forecast",
                              '"slate_tpu.forecast.v1"')):
            body = urllib.request.urlopen(srv.url(path),
                                          timeout=10).read().decode()
            if needle not in body:
                fails.append(f"GET {path}: missing {needle!r}")
    finally:
        sess.close_obs()
        tracer.off()
        legacy_trace.Trace.off()

    summary = {
        "out_dir": out_dir,
        "spans": len(tracer.spans()),
        "requests": requests,
        "schema_errors": 0 if not fails else fails,
        "ok": not fails,
    }
    print(json.dumps(summary))
    for msg in fails:
        print(f"!!! {msg}", file=sys.stderr)
    return 0 if not fails else 1


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--smoke", action="store_true",
                   help="tiny CPU run into a temp dir (CI wiring)")
    p.add_argument("--out-dir", default="obs_dump")
    p.add_argument("--n", type=int, default=96)
    p.add_argument("--nb", type=int, default=32)
    p.add_argument("--requests", type=int, default=12)
    p.add_argument("--slow-ms", type=float, default=None,
                   help="slow-request log threshold (milliseconds)")
    args = p.parse_args(argv)
    out_dir = args.out_dir
    if args.smoke and out_dir == "obs_dump":
        out_dir = tempfile.mkdtemp(prefix="slate_tpu_obs_")
    thr = args.slow_ms * 1e-3 if args.slow_ms is not None else None
    return run(out_dir, n=args.n, nb=args.nb, requests=args.requests,
               slow_threshold=thr)


if __name__ == "__main__":
    sys.exit(main())
