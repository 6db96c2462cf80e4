"""CPU tests of the benchmark harness: the manifest, the trace reduction,
the schedule, the yardstick, the device check, a rehearsal of each mix,
and that the check of ``correct`` fails on each fault a cell can have.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import json
import os
import re
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark import data, drive, manifest, scopes, trace, work  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
M = manifest.load(ROOT)
CELLS = [w["name"] for w in M["workloads"]]
# the served mix, which no cell of the manifest runs yet: its loop,
# readers and limits are kept ready for one
SERVED = ("chol_spd_n16384_f32", "served_poisson", "chol_n16384.served_poisson")


def _line(text):
    return text if text and "\t" not in text and "\n" not in text else None


# -- the manifest ----------------------------------------------------------

def test_manifest_shape():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in M["paths"])
    names = ([c["name"] for c in M["configs"]] + CELLS
             + [m["name"] for m in M["end_to_end"] + M["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert os.path.getsize(os.path.join(ROOT, manifest.MANIFEST)) < 64 << 10


def test_configs_resolve():
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(M["paths"][0] + "/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert _line(c["why"]) and _line(c["source"])
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200
        assert any(w["config"] == c["name"] for w in M["workloads"])


@pytest.mark.parametrize("name", CELLS)
def test_workload_resolves(name):
    w = {w["name"]: w for w in M["workloads"]}[name]
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200 and _line(w["why"])
    cell = manifest.cell(ROOT, name)
    assert callable(cell.loop.run) and callable(cell.operand.make)
    assert callable(cell.verb.call) and callable(cell.check.compare)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])
    assert cell.limits and all(isinstance(v, (int, float))
                               for v in cell.limits.values())


def test_metrics_rules():
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four <= max(1, len(M["workloads"]) // 2)
    for m in M["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert {m["name"]: m["bound"] for m in M["end_to_end"]}["setup_s"] \
        <= 0.25
    layers = {}
    for m in M["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert UNIT.match(m["unit"]) and _line(m["layer"])
        assert set(m["workloads"]) <= set(CELLS)
        assert os.path.exists(manifest.path(ROOT, "layers", m["name"]))
        layers.setdefault(m["layer"], []).append(m["name"])
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def _later_cell(root):
    """Files of a cell of a new kind, as a later PR would add them: a
    configuration naming a new operand, verb and check, a mix of a new
    kind with its loop, a per-layer metric and the cell's limits."""
    files = {
        "configs/dd_cfg.json": json.dumps({
            "name": "dd_cfg", "verb": "dense_solve", "operand": "dd",
            "check": "abs_error", "n": 48, "nb": 16, "nrhs": 2,
            "dtype": "float32", "reduced": {}}),
        "operands/dd.py": (
            "import jax, jax.numpy as jnp\n"
            "def make(key, n, dtype):\n"
            "    return (jax.random.normal(key, (n, n), dtype)\n"
            "            + n * jnp.eye(n, dtype=dtype))\n"),
        "verbs/dense_solve.py": (
            "import jax.numpy as jnp\n"
            "def wrap(a, nb):\n    return a\n"
            "def call(a, b):\n    return jnp.linalg.solve(a, b)\n"
            "def cost(n, k, itemsize):\n"
            "    return 2 * n ** 3 / 3 + 2 * n * n * k, itemsize * n * n\n"),
        "checks/abs_error.py": (
            "import numpy as np\n"
            "def compare(a, x, b, dtype):\n"
            "    a, x, b = (np.asarray(v, np.float64) for v in (a, x, b))\n"
            "    return np.abs(a @ x - b).max(axis=0)\n"),
        "traffic/one_call.json": json.dumps({"kind": "one_call"}),
        "loops/one_call.py": (
            "import time\n"
            "import numpy as np\n"
            "from benchmark import data, drive\n"
            "def run(cell, seed, seconds, tracer, rehearse=False,\n"
            "        control=False, held=None):\n"
            "    cfg = cell.config\n"
            "    n = int(cfg['n'])\n"
            "    a, bs = data.operands(seed, cell.operand.make, n,\n"
            "                          int(cfg['nrhs']), 1, cfg['dtype'])\n"
            "    t = time.perf_counter()\n"
            "    x = cell.verb.call(cell.verb.wrap(a, cfg['nb']), bs[0])\n"
            "    err = cell.check.compare(a, x, bs[0], cfg['dtype'])\n"
            "    return drive.Outcome(\n"
            "        t_window=t, values={'calls': 1.0}, attempted=1,\n"
            "        failed=0, compared={'error_max': float(err.max())},\n"
            "        context={'work': cell.verb.cost(n, 2, 4)},\n"
            "        diagnostics={'memory_peak_bytes': None})\n"),
        "layers/dd_gflop.py": (
            "def read(ctx):\n    return ctx['work'][0] / 1e9\n"),
        "limits/dd.one_call.json": json.dumps(
            {"error_max": {"limit": 1e-3}}),
    }
    for rel, text in files.items():
        f = root / "benchmark" / rel
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(text)
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "dd_cfg",
                     "file": "benchmark/configs/dd_cfg.json"}],
        "workloads": [{"name": "dd.one_call", "config": "dd_cfg",
                       "traffic": "one_call", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "calls", "unit": "calls"}],
        "per_layer": [{"name": "dd_gflop", "unit": "GFLOP",
                       "workloads": ["dd.one_call"]}]}))


def test_a_later_cell_is_files_and_entries(tmp_path):
    """A cell of a new kind (its loop, operand, verb, check, metric and
    limits) loads from files of its own and runs through the harness,
    with no edit to a file that is there."""
    _later_cell(tmp_path)
    cell = manifest.cell(str(tmp_path), "dd.one_call")
    assert cell.limits == {"error_max": 1e-3}
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "calls"]
    import jax

    args = types.SimpleNamespace(seed=2 ** 31 + 3, seconds=0.1, trace=0)
    got = bench_run.measure(cell, args, jax.devices()[:1], rehearse=True)
    assert got["correct"] is True and got["attempted"] == 1
    assert got["compared"]["error_max"]["limit"] == 1e-3
    out = cell.loop.run(cell, 1, 0.1, trace.NO_TRACE)
    assert cell.readers["dd_gflop"](out.context) == pytest.approx(
        (2 * 48 ** 3 / 3 + 2 * 48 * 48 * 2) / 1e9)


# -- the yardstick ---------------------------------------------------------

def test_flop_counts_by_hand():
    assert work.potrf_flops(6) == 72.0  # 6³/3
    assert work.getrf_flops(6) == 144.0  # 2·6³/3
    assert work.solve_flops(4, 3) == 96.0  # 2·4²·3
    posv = manifest.module(ROOT, "verbs", "posv")
    gesv = manifest.module(ROOT, "verbs", "gesv")
    assert posv.cost(6, 2, 4) == (72.0 + 144.0, 4 * (21.0 + 24.0))
    assert gesv.cost(6, 2, 4) == (144.0 + 144.0, 4 * (36.0 + 24.0))


def test_peaks_unknown_device_is_an_error():
    v5e = work.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")
    t, bound = work.roofline_s(197e12, 1.0, v5e)
    assert t == 1.0 and bound == "compute"
    t, bound = work.roofline_s(1.0, 819e9, v5e)
    assert t == 1.0 and bound == "memory"


# -- the trace reduction, on a small excerpt reduced by hand ---------------

def _profile(planes):
    def ev(e):
        return types.SimpleNamespace(name=e[0], start_ns=e[1],
                                     duration_ns=e[2])
    return types.SimpleNamespace(planes=[types.SimpleNamespace(
        name=p["name"], lines=[types.SimpleNamespace(
            name=ln["name"], events=[ev(e) for e in ln["events"]])
            for ln in p["lines"]]) for p in planes])


@pytest.fixture(scope="module")
def small():
    with open(os.path.join(HERE, "small_trace.json")) as f:
        rec = json.load(f)
    module, names, custom = trace.hlo_index(rec["hlo"])
    assert module == "jit_posv" and custom == {"custom-call.3"}
    return trace.reduce_xspace(_profile(rec["planes"]),
                               {module: (names, custom)})


def test_busy_union_and_idle_share(small):
    # device op intervals, µs: [1200,2200] (while and its two children),
    # [2500,4500], [6200,6500], [6600,7000], [7500,10000], [10400,10600]
    assert small.window_s == pytest.approx(0.010)
    assert small.busy_s == pytest.approx(6400e-6)
    assert small.idle_share == pytest.approx(0.36)


def test_scope_attribution_and_custom_calls(small):
    # self times, µs: while 1000-300-400 = 300 (unscoped), fusion.2
    # 2×300 and the kernel 2×400 (panel), convolution 2000+2500
    # (trail), copy 200 (no metadata: unscoped)
    by = scopes.seconds_by_kind(small.scope_s)
    assert by["panel"] == pytest.approx(1400e-6)
    assert by["trail"] == pytest.approx(4500e-6)
    assert by["unscoped"] == pytest.approx(500e-6)
    assert small.custom_s == pytest.approx(800e-6)
    assert small.modules == {"jit_posv": [pytest.approx(3300e-6),
                                          pytest.approx(4400e-6)]}
    ctx = {"trace": small, "program": "jit_posv"}
    assert scopes.per_call_ms(ctx, "panel") == pytest.approx(0.7)
    assert scopes.per_call_ms(ctx, "trail") == pytest.approx(2.25)


def test_idle_gaps_named_by_host(small):
    gaps = small.gaps
    assert gaps[0] == (pytest.approx(1700e-6),
                       "idle host/TransferFromDevice")
    assert gaps[1] == (pytest.approx(500e-6), "bench.call")
    assert [g[0] for g in gaps] == sorted((g[0] for g in gaps),
                                          reverse=True)
    assert len(gaps) == 7
    bd = small.breakdown()
    assert bd["device_ops"][0] == [
        "jit(posv)/potrf_l#_trail_rest/dot_general", pytest.approx(4500e-6)]
    assert len(bd["idle_gaps"]) == 7


def test_layer_readers_on_the_excerpt(small):
    cell = manifest.cell(ROOT, "chol_n16384.factor_solve")
    ctx = {"trace": small, "program": "jit_posv",
           "work": cell.verb.cost(1024, 16, 4),
           "device_kind": "TPU v5 lite"}
    got = {name: read(ctx) for name, read in cell.readers.items()}
    assert got["device_idle_pct.factor"] == pytest.approx(36.0)
    assert got["panel_chain_ms"] == pytest.approx(0.7)
    assert got["trailing_update_ms"] == pytest.approx(2.25)
    assert got["pallas_kernel_ms"] == pytest.approx(0.4)
    flops, nbytes = cell.verb.cost(1024, 16, 4)
    least = max(flops / 197e12, nbytes / 819e9)
    assert got["factor_roofline"] == pytest.approx(
        100 * least / 3850e-6)


def test_served_readers():
    """The served mix's readers, on the Session's metrics before and
    after a window and on a trace whose solve program is known by name:
    a helper program that runs more often does not take its place."""
    read = {m: manifest.module(ROOT, "layers", m).read
            for m in ("served_batch_mean", "served_dispatch_ms",
                      "solve_program_ms", "device_idle_pct.served")}
    before = {"histograms": {"batch_size": {"count": 2, "sum": 10.0},
                             "stage_dispatch": {"count": 2, "sum": 0.004}}}
    after = {"histograms": {"batch_size": {"count": 6, "sum": 70.0},
                            "stage_dispatch": {"count": 6, "sum": 0.010}}}
    tr = types.SimpleNamespace(
        modules={"jit_serve_chol_solve": [0.1, 0.3],
                 "jit_pad": [0.001] * 5},
        window_s=2.0, idle_share=0.25)
    ctx = {"before": before, "after": after, "trace": tr, "factor": "chol"}
    assert read["served_batch_mean"](ctx) == pytest.approx(15.0)
    assert read["served_dispatch_ms"](ctx) == pytest.approx(1.5)
    assert read["solve_program_ms"](ctx) == pytest.approx(200.0)
    assert read["device_idle_pct.served"](ctx) == pytest.approx(25.0)
    assert read["solve_program_ms"](dict(ctx, factor="lu")) is None
    assert read["served_batch_mean"](dict(ctx, after=before)) is None


def test_self_times_nest():
    # a contains b and c; c contains d; e stands alone
    ev = [(0, 10), (1, 3), (5, 4), (6, 1), (20, 2)]
    assert trace.self_times(ev) == [3, 3, 3, 1, 2]
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]


# -- the schedule ----------------------------------------------------------

def test_poisson_schedule_fixed_by_seed():
    a = data.poisson_offsets(2 ** 31 + 77, 300.0, 20.0)
    b = data.poisson_offsets(2 ** 31 + 77, 300.0, 20.0)
    c = data.poisson_offsets(5, 300.0, 20.0)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert len(a) == len(c) == 6000
    # every seed has the same gaps, in another order: the gaps between
    # arrivals and the one that wraps round to the window's end
    def gaps(x):
        return np.sort(np.append(np.diff(x), 20.0 - x[-1]))
    assert np.allclose(gaps(a), gaps(c))
    assert a[0] == 0.0 and a[-1] < 20.0 and np.all(np.diff(a) > 0)
    gaps = np.diff(a)
    assert gaps.mean() == pytest.approx(1 / 300.0, rel=0.01)
    assert np.std(gaps) / gaps.mean() == pytest.approx(1.0, rel=0.1)


def test_requests_timed_from_due_time(monkeypatch):
    """A server that stalls: requests due during the stall wait for it,
    and their latency counts the wait, though the generator is on time."""
    from slate_tpu.runtime import Session

    cell = manifest.assemble(ROOT, *SERVED[:2], name=SERVED[2])
    cell.traffic = dict(cell.traffic, rate_per_s=50.0)
    solve, calls = Session.solve, []

    def stalled(self, handle, b, **kw):
        calls.append(1)
        if len(calls) == 3:  # the first live batch after warm-up
            import time
            time.sleep(0.4)
        return solve(self, handle, b, **kw)

    monkeypatch.setattr(Session, "solve", stalled)
    out = cell.loop.run(cell, 11, 1.0, trace.NO_TRACE, rehearse=True)
    assert out.failed == 0 and out.diagnostics["late_p99_ms"] < 50
    assert out.values["served_p99_ms"] > 200


def test_nearest_rank():
    v = np.arange(1, 101, dtype=float)
    assert drive.nearest_rank(v, 0.5) == 50.0
    assert drive.nearest_rank(v, 0.99) == 99.0
    assert drive.nearest_rank([3.0], 0.99) == 3.0


# -- the device check and the rehearsals -----------------------------------

def _run(*args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "benchmark/run.py", *args],
                          cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=600)


def test_cpu_is_refused_without_rehearsal():
    r = _run("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert r.returncode == 2 and r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_benchmark_alone_prints_no_result(tmp_path):
    """Without the program beside it the run fails and prints nothing."""
    import shutil

    shutil.copy(os.path.join(ROOT, manifest.MANIFEST), tmp_path)
    for p in M["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run([sys.executable, *M["command"][1:], "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0 and r.stdout.strip() == ""


@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_last_line(name):
    r = _run("--workload", name, "--seed", str(2 ** 31 + 5), "--seconds",
             "1", "--trace", "0", "--rehearse")
    assert r.returncode == 0, r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert list(last)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(
        last)
    assert last["correct"] is True and last["metrics"] == {}
    assert "not a chip measurement" in last["rehearsal"]
    assert last["device"]["platform"] == "cpu"
    assert r.stderr.strip().splitlines()[-1].startswith("compared ")


@pytest.mark.parametrize("tool", ["sweep", "readings"])
def test_chip_tools_rehearse(tool):
    """The tools that set a served rate and a cell's limits run their
    whole path on the CPU at tiny sizes."""
    if tool == "sweep":
        args = ["--config", SERVED[0], "--traffic", SERVED[1], "--rates",
                "20,40", "--seconds", "0.5"]
    else:
        args = ["--workload", CELLS[0], "--seeds", "1,2",
                "--control-seeds", "3", "--seconds", "0.2"]
    r = subprocess.run([sys.executable, f"benchmark/{tool}.py", *args,
                        "--rehearse"], cwd=ROOT, capture_output=True,
                       text=True, timeout=600,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
    rows = [json.loads(x) for x in r.stdout.strip().splitlines()]
    if tool == "sweep":
        assert [x["rate_per_s"] for x in rows] == [20.0, 40.0]
        assert all(x["failed"] == 0 and x["sustained"] for x in rows)
    else:
        got = rows[-1]["readings"]["residual_max"]
        assert got["program_seeds"] == 2 and got["control_seeds"] == 1
        assert 0 < got["lower"] < 0.012


# -- correct comes out false on each fault a cell can have -----------------

def _alter_x(X, how):
    import dataclasses
    import jax.numpy as jnp

    d = X.data
    n, k = X.shape
    if how == "altered":
        d = d.at[n // 2, k - 1].add(1.0)
    elif how == "half":
        d = d.at[:, : k // 2].set(0.0)
    return dataclasses.replace(X, data=d)


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("name", ["chol_n16384.factor_solve",
                                  "lu_n16384.factor_solve"])
def test_closed_loop_fault_fails(monkeypatch, name, fault):
    import slate_tpu as st

    cell = manifest.cell(ROOT, name)
    verb = getattr(st, cell.config["verb"])

    def broken(A, B, opts):
        X, info = verb(A, B, opts)
        return (B if fault == "unchanged" else _alter_x(X, fault)), info

    monkeypatch.setattr(st, cell.config["verb"], broken)
    out = cell.loop.run(cell, 7, 0.3, trace.NO_TRACE, rehearse=True)
    assert not all(out.compared[k] <= v for k, v in cell.limits.items())


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered",
                                   "dropped"])
def test_open_loop_fault_fails(monkeypatch, fault):
    from slate_tpu.runtime import Session

    cell = manifest.assemble(ROOT, *SERVED[:2], name=SERVED[2])
    cell.traffic = dict(cell.traffic, drain_s=1.0)
    solve, calls = Session.solve, []

    def broken(self, handle, b, **kw):
        x = np.array(solve(self, handle, b, **kw))
        b = np.asarray(b)
        calls.append(1)
        if len(calls) <= 2:  # warm-up, before the window
            return x
        if fault == "unchanged":
            return b.copy()
        if fault == "altered":
            x[x.shape[0] // 2, ...] += 1.0
        elif fault == "half":
            x[..., : (x.shape[-1] + 1) // 2] = 0.0
        elif fault == "dropped":
            return x[:, : x.shape[1] // 2] if x.ndim == 2 else x[:0]
        return x

    monkeypatch.setattr(Session, "solve", broken)
    out = cell.loop.run(cell, 8, 0.5, trace.NO_TRACE, rehearse=True)
    assert not all(out.compared[k] <= v for k, v in cell.limits.items())


def test_control_fails_the_limit(monkeypatch):
    """The control, the program's one-pass bfloat16 trailing updates:
    on the CPU, where a matmul's precision does nothing, each product's
    operands are rounded to bfloat16 as one pass on the chip rounds
    them; on the chip the control runs as it is."""
    import jax
    import jax.numpy as jnp
    from slate_tpu.ops import blocked

    if jax.devices()[0].platform == "cpu":
        mm = blocked.mm

        def one_pass(a, b, prec=None):
            if prec == "default":
                a = a.astype(jnp.bfloat16).astype(a.dtype)
                b = b.astype(jnp.bfloat16).astype(b.dtype)
            return mm(a, b, prec)

        monkeypatch.setattr(blocked, "mm", one_pass)
    for name in ("chol_n16384.factor_solve", "lu_n16384.factor_solve"):
        cell = manifest.cell(ROOT, name)
        prog = cell.loop.run(cell, 9, 0.3, trace.NO_TRACE, rehearse=True)
        ctrl = cell.loop.run(cell, 9, 0.3, trace.NO_TRACE, rehearse=True,
                             control=True)
        limit = cell.limits["residual_max"]
        assert prog.compared["residual_max"] <= limit < \
            ctrl.compared["residual_max"], (name, prog.compared,
                                             ctrl.compared)
