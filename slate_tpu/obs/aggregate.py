"""Pod-scale telemetry aggregation: N processes, one fleet view.

The reference merges per-rank trace buffers and counter payloads
post-hoc (each MPI rank writes its own Trace/counter stream; rank 0
stitches the SVG and sums the counters). Our serving analog: every
process exports a Metrics snapshot, flop/bytes ledger snapshots, and a
Chrome trace; this module merges them —

* **counters summed exactly** (plain float addition — merging two
  copies of the same snapshot doubles every counter bit-exactly,
  which is the aggregation acceptance test);
* **histograms merged**: counts and sums add, min/max take the
  extremes, the merged mean is recomputed, and the merged p50/p99 are
  the count-weighted mean of the per-process quantiles (an
  approximation — exact fleet quantiles need the raw samples, which
  snapshots deliberately do not ship; documented in PERF_HISTORY.md Round 12);
  the worst-valued exemplar survives;
* **gauges labeled per host** (a fleet has one resident_bytes per
  chip, not one sum; summable gauges are ALSO aggregated under
  ``fleet_*`` names so capacity totals stay one query);
* **derived headline rates recomputed** from the merged counters with
  the same formulas ``runtime.Metrics._derive`` uses (pinned equal by
  test — this module cannot import the runtime without dragging jax
  into the obs layer, so the formulas are mirrored, not shared);
* **traces combined keyed by trace-id** (obs.merge.
  ``combine_process_traces``): per-process pid namespaces, span
  identities prefixed with the host label so two processes' span id
  counters cannot collide in one Perfetto load.

Everything here is pure snapshot-in/snapshot-out (stdlib-only,
jax-free): the processes can be 8 hosts of a pod or one host's
bench + serve jobs — aggregation is the same fold either way.
"""

from __future__ import annotations

import json
from typing import Dict, List, Optional, Sequence

from .merge import combine_process_traces  # re-export (fleet surface)

__all__ = [
    "combine_process_traces", "merge_attribution_snapshots",
    "merge_bytes_snapshots",
    "merge_flop_snapshots", "merge_histograms",
    "merge_incident_payloads", "merge_journal_payloads",
    "merge_metrics_snapshots", "merge_placement_snapshots",
    "merge_quota_payloads", "merge_timeseries_payloads",
    "aggregate_processes", "placement_from_checkpoint",
    "render_fleet_prometheus", "write_fleet",
]

# gauges that are meaningfully summable across processes (capacity
# totals); everything else (headroom, per-chip charges, burn rates)
# only makes sense per host
_SUMMABLE_GAUGES = ("resident_bytes_total", "resident_bytes",
                    "peak_hbm_bytes", "queue_depth", "inflight_batches")


def _hosts(n: int, hosts: Optional[Sequence[str]]) -> List[str]:
    if hosts is None:
        return [f"proc{i}" for i in range(n)]
    if len(hosts) != n:
        raise ValueError(f"{n} snapshots but {len(hosts)} host labels")
    return list(hosts)


def merge_histograms(snaps: Sequence[dict]) -> dict:
    """Merge per-process Histogram.snapshot() dicts (module
    docstring); empty input -> empty-histogram shape."""
    count = sum(int(s.get("count", 0)) for s in snaps)
    total = sum(float(s.get("sum", 0.0)) for s in snaps)
    mins = [s["min"] for s in snaps if s.get("min") is not None]
    maxs = [s["max"] for s in snaps if s.get("max") is not None]
    out = {
        "count": count,
        "sum": total,
        "min": min(mins) if mins else None,
        "max": max(maxs) if maxs else None,
        "mean": (total / count) if count else None,
    }
    for q in ("p50", "p99"):
        num = den = 0.0
        for s in snaps:
            c = int(s.get("count", 0))
            if c and s.get(q) is not None:
                num += c * float(s[q])
                den += c
        out[q] = (num / den) if den else 0.0
    exemplars = [s.get("exemplar") for s in snaps if s.get("exemplar")]
    out["exemplar"] = (max(exemplars, key=lambda e: e.get("value", 0.0))
                       if exemplars else None)
    return out


def _derive(counters: dict, hists: dict) -> dict:
    """Mirror of runtime.Metrics._derive over MERGED counters (see
    module docstring for why it is mirrored, and the pin test)."""
    hits = counters.get("cache_hits", 0.0)
    misses = counters.get("cache_misses", 0.0)
    total = hits + misses
    solve_seconds = hists.get("solve_latency", {}).get("sum", 0.0)
    solves = counters.get("solves_total", 0.0)
    flops = counters.get("solve_flops_total", 0.0)
    return {
        "cache_hit_rate": hits / total if total else 0.0,
        "solves_per_sec": (solves / solve_seconds
                           if solve_seconds > 0 else 0.0),
        "gflops": (flops / solve_seconds / 1e9
                   if solve_seconds > 0 else 0.0),
    }


def merge_metrics_snapshots(snaps: Sequence[dict],
                            hosts: Optional[Sequence[str]] = None) -> dict:
    """N ``Metrics.snapshot()`` dicts -> one fleet snapshot (module
    docstring). The result renders through
    ``exposition.render_prometheus`` unchanged; per-host gauges ride in
    ``gauges_per_host`` (``render_fleet_prometheus`` emits them with
    ``host=`` labels)."""
    snaps = list(snaps)
    labels = _hosts(len(snaps), hosts)
    counters: Dict[str, float] = {}
    for s in snaps:
        for k, v in s.get("counters", {}).items():
            counters[k] = counters.get(k, 0.0) + v
    hist_names = sorted({k for s in snaps for k in s.get("histograms", {})})
    hists = {name: merge_histograms(
        [s["histograms"][name] for s in snaps
         if name in s.get("histograms", {})]) for name in hist_names}
    gauges_per_host = {label: dict(s.get("gauges", {}))
                       for label, s in zip(labels, snaps)}
    # round 23: the per-host gauge rows keep their host label AND
    # their set-time stamps — a fleet reader can tell a fresh value
    # from one last true minutes before the scrape
    gauge_ts_per_host = {label: dict(s.get("gauge_ts", {}))
                         for label, s in zip(labels, snaps)}
    fleet_gauges = {}
    for g in _SUMMABLE_GAUGES:
        vals = [s["gauges"][g] for s in snaps if g in s.get("gauges", {})]
        if vals:
            fleet_gauges[f"fleet_{g}"] = sum(vals)
    return {
        "hosts": labels,
        "processes": len(snaps),
        "uptime_s": max((s.get("uptime_s", 0.0) for s in snaps),
                        default=0.0),
        "counters": counters,
        "histograms": hists,
        "gauges": fleet_gauges,
        "gauges_per_host": gauges_per_host,
        "gauge_ts_per_host": gauge_ts_per_host,
        "derived": _derive(counters, hists),
    }


def _merge_keyed_sums(snaps: Sequence[dict], key: str) -> Dict[str, dict]:
    """Union per-op/per-kind tables, summing every numeric field."""
    out: Dict[str, dict] = {}
    for s in snaps:
        for op, row in s.get(key, {}).items():
            dst = out.setdefault(op, {})
            if isinstance(row, dict):
                for k, v in row.items():
                    dst[k] = dst.get(k, 0) + v
            else:  # flop ledger per_op: bare floats
                dst["value"] = dst.get("value", 0.0) + row
    return out


def merge_flop_snapshots(snaps: Sequence[dict]) -> dict:
    """N ``FlopLedger.snapshot()`` dicts -> one (totals/per-op/calls
    summed)."""
    out = {"flops_total": sum(s.get("flops_total", 0.0) for s in snaps),
           "per_op": {}, "calls": {}}
    for s in snaps:
        for op, v in s.get("per_op", {}).items():
            out["per_op"][op] = out["per_op"].get(op, 0.0) + v
        for op, c in s.get("calls", {}).items():
            out["calls"][op] = out["calls"].get(op, 0) + c
    return out


def merge_bytes_snapshots(snaps: Sequence[dict]) -> dict:
    """N ``BytesLedger.snapshot()`` dicts -> one."""
    return {
        "bytes_total": sum(s.get("bytes_total", 0.0) for s in snaps),
        "collective_bytes_total": sum(
            s.get("collective_bytes_total", 0.0) for s in snaps),
        "per_op": _merge_keyed_sums(snaps, "per_op"),
        "per_collective": _merge_keyed_sums(snaps, "per_collective"),
    }


def merge_attribution_snapshots(snaps: Sequence[dict]) -> dict:
    """N ``AttributionLedger.snapshot()`` dicts -> one fleet
    attribution view: per-(tenant, handle) cells summed per counter
    class, tenant and global totals recomputed from the merged cells
    (sorted order). Every increment lives on the dyadic grid
    (obs/attribution.py), so these sums are exact and the fleet's
    per-tenant rows still sum bit-exactly to the fleet's folded
    global counters — the conservation invariant survives the fold,
    including under a round-14 ``snapshot_drop`` (a dropped process
    loses its metrics AND attribution snapshots together, so both
    sides of the invariant shrink consistently). ``heat`` is summed
    across processes (a replicated handle's fleet heat is its total
    access rate — the replication signal); ``last_access`` takes the
    newest.

    **Partial hosts (round 17):** ``None`` entries — a host inside the
    crash window whose live attribution snapshot is gone while its
    checkpoint survives — are tolerated: they are skipped (their cells
    died with the process, exactly like their global counters did, so
    conservation over the SURVIVING snapshots still holds) and counted
    in ``partial_processes``. Before this, only the all-or-nothing
    ``snapshot_drop`` case (both sides absent) was pinned."""
    raw = list(snaps)  # a generator must not be consumed before the
    snaps = [s for s in raw if s]  # "processes" count below
    partial = len(raw) - len(snaps)
    tenants: Dict[str, dict] = {}
    halflife = None
    for s in snaps:
        if halflife is None:
            halflife = s.get("halflife_s")
        for tenant, trow in s.get("tenants", {}).items():
            dst = tenants.setdefault(tenant, {"totals": {},
                                              "handles": {}})
            for h, hrow in trow.get("handles", {}).items():
                cell = dst["handles"].setdefault(h, {})
                for cls, v in hrow.items():
                    if cls == "last_access":
                        prev = cell.get("last_access")
                        if v is not None and (prev is None or v > prev):
                            cell["last_access"] = v
                    else:
                        cell[cls] = cell.get(cls, 0.0) + v
    totals: Dict[str, float] = {}
    for tenant in sorted(tenants):
        trow = tenants[tenant]
        for h in sorted(trow["handles"]):
            for cls, v in trow["handles"][h].items():
                if cls in ("last_access", "heat"):
                    continue
                trow["totals"][cls] = trow["totals"].get(cls, 0.0) + v
                totals[cls] = totals.get(cls, 0.0) + v
    return {
        "schema": "slate_tpu.attribution.v1",
        "fleet": True,
        "processes": len(snaps),
        "partial_processes": partial,
        "halflife_s": halflife,
        "tenants": tenants,
        "totals": totals,
    }


def placement_from_checkpoint(manifest: dict,
                              host: Optional[str] = None) -> dict:
    """A checkpoint manifest (runtime/checkpoint.py,
    ``slate_tpu.checkpoint.v1``) -> a placement-snapshot-SHAPED doc for
    the fleet fold: the crash-window bridge. When a process dies its
    live ``placement_snapshot()`` is gone, but its last checkpoint
    records the same per-resident rows (op/n/dtype/bytes/heat/health),
    so the fold need not go blind on that host — the derived doc is
    marked ``"partial": True`` and ``merge_placement_snapshots``
    surfaces it under ``partial_hosts``. ``bytes_per_chip`` for a mesh
    resident is the checkpoint's TOTAL gathered bytes (the checkpoint
    is placement-independent); live rows stay the per-chip truth."""
    host = host or str(manifest.get("host", "checkpoint"))
    rows = []
    for rec in manifest.get("records", []):
        if not isinstance(rec, dict):
            continue
        payload_bytes = _node_nbytes(rec.get("payload"))
        health = rec.get("health") or {}
        hrep = (repr(str(rec.get("handle")))
                if rec.get("handle_type") == "str"
                else str(rec.get("handle")))
        rows.append({
            "host": host,
            "tenant": str(rec.get("tenant") or "default"),
            "handle": hrep,
            "op": str(rec.get("op", "")),
            "n": int(rec.get("n", 0)),
            "dtype": str(rec.get("dtype", "")),
            "bytes_per_chip": int(payload_bytes),
            "heat": float(rec.get("heat") or 0.0),
            "last_access": rec.get("last_access"),
            "health": health.get("state"),
            "condest": health.get("condest"),
            "growth": health.get("growth"),
        })
    return {
        "schema": "slate_tpu.placement_snapshot.v2",
        "host": host,
        "generated_at": manifest.get("generated_at"),
        "partial": True,
        "rows": rows,
    }


def _node_nbytes(desc) -> int:
    """Total blob bytes under one checkpoint node descriptor (pure
    manifest walk — this module is stdlib-only, so the byte count
    comes from the recorded ``nbytes`` fields, not numpy)."""
    if not isinstance(desc, dict):
        return 0
    if desc.get("type") == "tuple":
        return sum(_node_nbytes(d) for d in desc.get("items", []))
    total = 0
    for v in desc.values():
        if isinstance(v, dict) and "nbytes" in v:
            total += int(v.get("nbytes", 0) or 0)
    return total


def merge_placement_snapshots(docs: Sequence[dict]) -> dict:
    """N ``Session.placement_snapshot()`` documents -> the fleet
    placement input (ROADMAP item 1): every host's resident rows in
    one row set (each row already carries its host label) plus a
    per-tenant rollup — resident bytes, total heat, handle count per
    tenant across the fleet — the numbers a quota/placement policy
    reads first. Rows sort by (tenant, heat desc) so the hottest
    handles lead each tenant's slice."""
    docs = [d for d in docs if d]  # round 17: tolerate absent hosts
    rows = []
    hosts = []
    partial_hosts = []
    for doc in docs:
        h = doc.get("host", f"proc{len(hosts)}")
        hosts.append(h)
        if doc.get("partial"):
            # round 17: a checkpoint-derived doc for a host inside the
            # crash window (live snapshot gone, checkpoint survives) —
            # its rows join the fold, labeled so a placement policy
            # can discount their staleness
            partial_hosts.append(h)
        rows.extend(dict(r) for r in doc.get("rows", []))
    rows.sort(key=lambda r: (str(r.get("tenant", "")),
                             -float(r.get("heat", 0.0) or 0.0),
                             str(r.get("handle", ""))))
    per_tenant: Dict[str, dict] = {}
    for r in rows:
        t = per_tenant.setdefault(str(r.get("tenant", "")), {
            "resident_bytes": 0.0, "heat": 0.0, "handles": 0,
            "suspect_handles": 0, "hosts": set()})
        t["resident_bytes"] += float(r.get("bytes_per_chip", 0.0) or 0.0)
        t["heat"] += float(r.get("heat", 0.0) or 0.0)
        t["handles"] += 1
        if r.get("health") == "suspect":
            # round 16: health-aware placement — a suspect resident is
            # never a replication candidate however hot it runs
            t["suspect_handles"] += 1
        t["hosts"].add(str(r.get("host", "")))
    for t in per_tenant.values():
        t["hosts"] = sorted(t["hosts"])
    return {
        "schema": "slate_tpu.fleet_placement.v1",
        "hosts": hosts,
        "processes": len(docs),
        "partial_hosts": partial_hosts,
        "rows": rows,
        "per_tenant": per_tenant,
    }


def merge_quota_payloads(snaps: Sequence[dict]) -> dict:
    """N ``Session.quotas_payload()`` dicts -> one fleet quota view
    (round 18): per-tenant resident bytes/counts summed across hosts
    (the fleet-wide share a capacity planner bills against) and the
    quota counters folded — ``None``/disabled entries tolerated (a
    host without a tenant table simply contributes nothing, the
    partial-host discipline)."""
    snaps = [s for s in snaps if s and s.get("enabled")]
    tenants: Dict[str, dict] = {}
    counters: Dict[str, float] = {}
    for s in snaps:
        for t, row in s.get("tenants", {}).items():
            dst = tenants.setdefault(t, {"resident_bytes": 0.0,
                                         "residents": 0,
                                         "max_resident_bytes": None})
            dst["resident_bytes"] += float(row.get("resident_bytes",
                                                   0.0) or 0.0)
            dst["residents"] += int(row.get("residents", 0) or 0)
            sub = row.get("max_resident_bytes")
            if sub is not None:
                # the fleet-wide sub-budget is the per-host budget
                # summed (each host enforces its own share)
                dst["max_resident_bytes"] = (
                    (dst["max_resident_bytes"] or 0) + sub)
        for k, v in s.get("counters", {}).items():
            counters[k] = counters.get(k, 0.0) + v
    return {
        "enabled": bool(snaps),
        "processes": len(snaps),
        "tenants": tenants,
        "counters": counters,
    }


def merge_journal_payloads(payloads: Sequence[dict],
                           hosts: Optional[Sequence[str]] = None
                           ) -> dict:
    """N ``DecisionJournal.payload()`` docs -> one fleet decision
    timeline (round 22): every ring event host-labeled and merged
    into ONE ts-ordered stream, per-kind / per-(kind, outcome) counts
    summed exactly (the conservation invariant: fleet count(kind) ==
    sum of per-process counts — merging two copies of one journal
    doubles every count bit-exactly, same as the metrics fold)."""
    labels = _hosts(len(payloads), hosts)
    events: List[dict] = []
    counts: Dict[str, float] = {}
    outcome_counts: Dict[str, float] = {}
    recorded = dropped = 0
    for label, p in zip(labels, payloads):
        if not p:
            continue
        for ev in p.get("events", ()):
            row = dict(ev)
            row["host"] = label
            events.append(row)
        for k, v in p.get("counts", {}).items():
            counts[k] = counts.get(k, 0.0) + v
        for k, v in p.get("outcome_counts", {}).items():
            outcome_counts[k] = outcome_counts.get(k, 0.0) + v
        recorded += int(p.get("recorded", 0))
        dropped += int(p.get("dropped", 0))
    # ts-ordered; (host, seq) breaks clock ties deterministically
    events.sort(key=lambda e: (e.get("ts", 0.0), e.get("host", ""),
                               e.get("seq", 0)))
    return {
        "schema": "slate_tpu.journal.fleet.v1",
        "processes": len(payloads),
        "hosts": labels,
        "recorded": recorded,
        "dropped": dropped,
        "counts": counts,
        "outcome_counts": outcome_counts,
        "events": events,
    }


def merge_timeseries_payloads(payloads: Sequence[dict],
                              hosts: Optional[Sequence[str]] = None
                              ) -> dict:
    """N ``TimeseriesStore.payload()`` docs -> one fleet history view
    (round 23): every member's series kept host-labeled under
    ``"<host>:<name>"`` (a fleet has one queue-depth history per
    member, not one mush), drop accounting summed, and every COUNTER
    series' lifetime sum folded into ``counter_totals`` by plain float
    addition — the round-12 conservation discipline: merging two
    copies of one payload doubles every counter total bit-exactly,
    and the fleet total equals the sum of the members' cumulative
    counters. ``None`` entries (a host inside the crash window)
    are tolerated and counted ``partial_processes``."""
    raw = list(payloads)
    labels = _hosts(len(raw), hosts)
    series: Dict[str, dict] = {}
    counter_totals: Dict[str, float] = {}
    dropped_series = dropped_samples = 0
    partial = 0
    for label, p in zip(labels, raw):
        if not p:
            partial += 1
            continue
        for name, row in p.get("series", {}).items():
            labeled = dict(row)
            labeled["host"] = label
            series[f"{label}:{name}"] = labeled
            if row.get("kind") == "counter":
                counter_totals[name] = (counter_totals.get(name, 0.0)
                                        + float(row.get("total_sum",
                                                        0.0)))
        dropped_series += int(p.get("dropped_series", 0))
        dropped_samples += int(p.get("dropped_samples", 0))
    return {
        "schema": "slate_tpu.timeseries.fleet.v1",
        "processes": len(raw),
        "partial_processes": partial,
        "hosts": labels,
        "dropped_series": dropped_series,
        "dropped_samples": dropped_samples,
        "series": series,
        "counter_totals": counter_totals,
    }


def merge_incident_payloads(payloads: Sequence[dict],
                            hosts: Optional[Sequence[str]] = None
                            ) -> dict:
    """N ``IncidentCapture.payload()`` docs -> one fleet incident
    timeline: every incident labeled with its process host (the
    document's own ``host`` field is preserved — the label records
    which FOLD slot it came from), ts-ordered, capture totals
    summed."""
    labels = _hosts(len(payloads), hosts)
    incidents: List[dict] = []
    captured = 0
    for label, p in zip(labels, payloads):
        if not p:
            continue
        for doc in p.get("incidents", ()):
            row = dict(doc)
            row["fold_host"] = label
            incidents.append(row)
        captured += int(p.get("captured", 0))
    incidents.sort(key=lambda d: (d.get("ts", 0.0),
                                  d.get("fold_host", ""),
                                  d.get("id", "")))
    return {
        "schema": "slate_tpu.incidents.fleet.v1",
        "processes": len(payloads),
        "hosts": labels,
        "captured": captured,
        "incidents": incidents,
    }


def aggregate_processes(metric_snaps: Sequence[dict],
                        flop_snaps: Optional[Sequence[dict]] = None,
                        bytes_snaps: Optional[Sequence[dict]] = None,
                        hosts: Optional[Sequence[str]] = None,
                        attribution_snaps: Optional[Sequence[dict]] = None,
                        placement_docs: Optional[Sequence[dict]] = None,
                        quota_payloads: Optional[Sequence[dict]] = None
                        ) -> dict:
    """One fleet document: merged metrics (+ ledgers, tenant
    attribution, placement snapshots, and quota payloads when
    given)."""
    doc = {"fleet": True,
           "metrics": merge_metrics_snapshots(metric_snaps, hosts)}
    if flop_snaps is not None:
        doc["flops"] = merge_flop_snapshots(flop_snaps)
    if bytes_snaps is not None:
        doc["bytes"] = merge_bytes_snapshots(bytes_snaps)
    if attribution_snaps is not None:
        doc["attribution"] = merge_attribution_snapshots(attribution_snaps)
    if placement_docs is not None:
        doc["placement"] = merge_placement_snapshots(placement_docs)
    if quota_payloads is not None:
        doc["quotas"] = merge_quota_payloads(quota_payloads)
    return doc


def render_fleet_prometheus(fleet: dict, prefix: str = "slate_tpu") -> str:
    """Prometheus text of an ``aggregate_processes`` document: the
    merged counters/histograms/derived through the standard renderer
    (process-local ledger sections disabled — the fleet ledgers are
    rendered from the MERGED snapshots below), then per-host gauges
    with ``host=`` labels."""
    from .exposition import _num, _san, render_prometheus
    merged = fleet["metrics"]
    text = render_prometheus(merged, prefix=prefix, ledger=False,
                             bytes_ledger=False)
    lines = [text.rstrip("\n")]
    for host in merged["hosts"]:
        gauges = merged["gauges_per_host"].get(host, {})
        for k in sorted(gauges):
            name = f"{prefix}_{_san(k)}"
            lines.append(f'{name}{{host="{_san(host)}"}} '
                         f"{_num(gauges[k])}")
    if "flops" in fleet:
        lines.append(f"# TYPE {prefix}_fleet_driver_flops_total counter")
        lines.append(f"{prefix}_fleet_driver_flops_total "
                     f"{_num(fleet['flops']['flops_total'])}")
    if "bytes" in fleet:
        lines.append(f"# TYPE {prefix}_fleet_driver_bytes_total counter")
        lines.append(f"{prefix}_fleet_driver_bytes_total "
                     f"{_num(fleet['bytes']['bytes_total'])}")
        lines.append(
            f"# TYPE {prefix}_fleet_collective_bytes_total counter")
        lines.append(f"{prefix}_fleet_collective_bytes_total "
                     f"{_num(fleet['bytes']['collective_bytes_total'])}")
    if "attribution" in fleet:
        # round 15: the fleet's per-tenant rollup, through the SAME
        # renderer the single-process /metrics route uses
        from .exposition import render_tenant_sections
        lines.extend(render_tenant_sections(fleet["attribution"],
                                            prefix=f"{prefix}_fleet"))
    if "placement" in fleet:
        lines.append(f"# TYPE {prefix}_fleet_tenant_resident_bytes gauge")
        lines.append(f"# TYPE {prefix}_fleet_tenant_heat gauge")
        pt = fleet["placement"].get("per_tenant", {})
        for tenant in sorted(pt):
            lines.append(
                f'{prefix}_fleet_tenant_resident_bytes'
                f'{{tenant="{_san(tenant)}"}} '
                f"{_num(pt[tenant]['resident_bytes'])}")
            lines.append(
                f'{prefix}_fleet_tenant_heat{{tenant="{_san(tenant)}"}} '
                f"{_num(pt[tenant]['heat'])}")
    if fleet.get("quotas", {}).get("enabled"):
        # round 18: the fleet quota rollup — per-tenant resident bytes
        # against the summed sub-budgets plus the folded quota
        # counters (rollups only; handle cardinality stays in JSON —
        # the round-15 discipline)
        q = fleet["quotas"]
        lines.append(
            f"# TYPE {prefix}_fleet_tenant_quota_resident_bytes gauge")
        for tenant in sorted(q.get("tenants", {})):
            row = q["tenants"][tenant]
            lines.append(
                f'{prefix}_fleet_tenant_quota_resident_bytes'
                f'{{tenant="{_san(tenant)}"}} '
                f"{_num(row['resident_bytes'])}")
            if row.get("max_resident_bytes") is not None:
                lines.append(
                    f'{prefix}_fleet_tenant_quota_max_resident_bytes'
                    f'{{tenant="{_san(tenant)}"}} '
                    f"{_num(row['max_resident_bytes'])}")
        for k in sorted(q.get("counters", {})):
            lines.append(f"# TYPE {prefix}_fleet_{_san(k)} counter")
            lines.append(
                f"{prefix}_fleet_{_san(k)} {_num(q['counters'][k])}")
    return "\n".join(lines) + "\n"


def write_fleet(fleet: dict, json_path: Optional[str] = None,
                prom_path: Optional[str] = None) -> dict:
    """Persist one fleet view (JSON and/or Prometheus text)."""
    if json_path is not None:
        with open(json_path, "w") as f:
            json.dump(fleet, f, indent=2, sort_keys=True)
            f.write("\n")
    if prom_path is not None:
        with open(prom_path, "w") as f:
            f.write(render_fleet_prometheus(fleet))
    return fleet
