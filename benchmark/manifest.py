"""Resolve a cell of ``BENCHMARK.json`` to the files that define it.

Everything that belongs to one configuration, traffic mix, verb or
per-layer metric sits in a file of its own, found by the name that the
manifest or a data file gives:

- the configuration: the ``file`` of its ``configs`` entry (sizes,
  options, and the names below);
- its operand: ``benchmark/operands/<config.operand>.py``, whose
  ``make(key, n, dtype)`` builds the n×n operand on the device;
- its verb: ``benchmark/verbs/<config.verb>.py``: how the operand is
  handed to the program, the call, and the call's work from its shapes;
- its plain reference: ``benchmark/checks/<config.check>.py``, whose
  ``compare(a, x, b, dtype)`` gives one number per answer;
- the traffic mix: ``benchmark/traffic/<traffic>.json``, parameters,
  read by the loop its ``kind`` names: ``benchmark/loops/<kind>.py``,
  whose ``run(cell, seed, seconds, tracer, ...)`` returns an ``Outcome``;
- a per-layer metric: ``benchmark/layers/<metric>.py``, whose
  ``read(ctx)`` returns the number or None where it finds nothing;
- the limits that decide ``correct``: ``benchmark/limits/<cell>.json``,
  each with the readings it was set from.

A later cell, mix, operand, verb, check or metric is new files and new
manifest entries; no file here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import types

MANIFEST = "BENCHMARK.json"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    loop: types.ModuleType  # loops/<traffic kind>.py
    operand: types.ModuleType  # operands/<config operand>.py
    verb: types.ModuleType  # verbs/<config verb>.py
    check: types.ModuleType  # checks/<config check>.py
    end_to_end: list  # the manifest's metric entries this cell reports
    per_layer: list
    readers: dict  # per-layer metric name -> read(ctx)
    limits: dict  # compared number -> its limit


def load(root: str) -> dict:
    with open(os.path.join(root, MANIFEST)) as f:
        return json.load(f)


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def path(root: str, kind: str, name: str, ext: str = ".py") -> str:
    """``benchmark/<kind>/<name><ext>`` under ``root``."""
    return os.path.join(root, "benchmark", kind, name + ext)


def module(root: str, kind: str, name: str) -> types.ModuleType:
    """``benchmark/<kind>/<name>.py`` loaded as a module of its own."""
    where = path(root, kind, name)
    if not os.path.exists(where):
        raise FileNotFoundError(f"no {kind} named {name!r}: {where}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_')}", where)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(where: str) -> dict:
    with open(where) as f:
        return json.load(f)


def assemble(root: str, config: str, traffic: str, name: str = "",
             chips: int = 1, m: dict | None = None) -> Cell:
    """A cell of ``config`` under ``traffic``. ``name`` finds the limits
    and the manifest's metrics; a pair that is no cell of the manifest
    (a sweep, the readings of a cell to come) gets neither."""
    m = load(root) if m is None else m
    entry = {c["name"]: c for c in m["configs"]}.get(config)
    cfg = _json(os.path.join(root, entry["file"]) if entry
                else path(root, "configs", config, ".json"))
    mix = _json(path(root, "traffic", traffic, ".json"))
    lim = path(root, "limits", name, ".json")
    limits = ({k: v["limit"] for k, v in _json(lim).items()}
              if name and os.path.exists(lim) else {})
    listed = any(w["name"] == name for w in m["workloads"])
    per_layer = [p for p in m["per_layer"] if listed and reports(p, name)]
    return Cell(
        name=name or f"{config}.{traffic}", chips=chips, config=cfg,
        traffic=mix, loop=module(root, "loops", mix["kind"]),
        operand=module(root, "operands", cfg["operand"]),
        verb=module(root, "verbs", cfg["verb"]),
        check=module(root, "checks", cfg["check"]),
        end_to_end=[e for e in m["end_to_end"]
                    if listed and reports(e, name)],
        per_layer=per_layer,
        readers={p["name"]: module(root, "layers", p["name"]).read
                 for p in per_layer},
        limits=limits)


def cell(root: str, name: str) -> Cell:
    m = load(root)
    work = {w["name"]: w for w in m["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in {MANIFEST}; have "
                       f"{sorted(work)}")
    w = work[name]
    return assemble(root, w["config"], w["traffic"], name=name,
                    chips=int(w["chips"]), m=m)
