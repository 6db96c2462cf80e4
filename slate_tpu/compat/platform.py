"""Process-level JAX setup shared by the program entry points.

``enable_compile_cache`` places JAX's persistent compilation cache for
the entry points that drive the chip (chip_smoke.py, bench.py,
bench_serve.py, the tester CLI); the library itself never turns the
cache on, and neither do the tests. ``xla_flag_supported`` probes
whether this jaxlib accepts an XLA flag before anyone sets it.
"""

from __future__ import annotations

import os

# <repo>/.jax_cache (listed in .gitignore): a fixed path, so a second
# run in the same checkout finds what the first one compiled
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its path.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache goes to the fixed
    ``<repo>/.jax_cache``. Call before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR


_FLAG_PROBE: dict = {}

# the rendezvous-timeout raise (see tests/conftest.py for the root
# cause) — single-sourced here so the three probe call sites (conftest,
# examples/run_tests.py, __graft_entry__) cannot drift apart
COLLECTIVE_TIMEOUT_FLAG = \
    "--xla_cpu_collective_call_terminate_timeout_seconds=600"


def collective_timeout_flag_if_supported(cache_path: str | None = None
                                         ) -> str:
    """" --xla_cpu_collective_call_terminate_timeout_seconds=600" when
    this jaxlib accepts it (probed, cached), else "". Append directly
    to an XLA_FLAGS string."""
    if xla_flag_supported(COLLECTIVE_TIMEOUT_FLAG, cache_path=cache_path):
        return " " + COLLECTIVE_TIMEOUT_FLAG
    return ""


def _jaxlib_version() -> str:
    try:
        from jaxlib.version import __version__
        return __version__
    except Exception:
        return "unknown"


def xla_flag_supported(flag: str, timeout: float = 120.0,
                       cache_path: str | None = None) -> bool:
    """True when this jaxlib's XLA accepts ``flag`` in XLA_FLAGS.

    XLA ABORTS the whole process on unknown XLA_FLAGS entries
    (parse_flags_from_env.cc "Unknown flags"), and the flag set varies
    across jaxlib builds — e.g. the bundled jaxlib dropped
    --xla_cpu_collective_call_terminate_timeout_seconds, which used to
    kill every test process at CPU-client creation. The probe builds a
    throwaway CPU client in a subprocess with ONLY ``flag`` set, so the
    abort (if any) happens where it can be observed instead of taking
    down the caller.

    The probe costs a few seconds (subprocess jax import + CPU client),
    so results are cached per flag per process, and — when
    ``cache_path`` is given — persisted as JSON keyed by jaxlib
    version, making it a one-time cost per environment instead of
    per-startup blocking work (callers: tests/conftest.py,
    examples/run_tests.py, __graft_entry__)."""
    cached = _FLAG_PROBE.get(flag)
    if cached is not None:
        return cached
    import json

    key = f"{_jaxlib_version()}:{flag}"
    store = {}
    if cache_path:
        try:
            with open(cache_path) as f:
                store = json.load(f)
        except Exception:
            store = {}
        if key in store:
            _FLAG_PROBE[flag] = bool(store[key])
            return _FLAG_PROBE[flag]
    import subprocess
    import sys

    env = dict(os.environ)
    env["XLA_FLAGS"] = flag
    env["JAX_PLATFORMS"] = "cpu"
    code = "import jax; jax.devices()"
    # only DEFINITIVE outcomes are persisted: success, or XLA's
    # "Unknown flags" abort signature. A transient failure (probe
    # timeout on a loaded box, unrelated crash) skips the flag for this
    # process only — persisting it would permanently disable a
    # supported flag for the whole environment.
    persist = False
    try:
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, timeout=timeout)
        if r.returncode == 0:
            cached = persist = True
        else:
            cached = False
            persist = b"Unknown flags" in (r.stderr or b"")
    except Exception:
        cached = False
    _FLAG_PROBE[flag] = cached
    if cache_path and persist:
        try:
            store[key] = cached
            with open(cache_path, "w") as f:
                json.dump(store, f)
        except Exception:
            pass  # read-only checkout: fall back to per-process caching
    return cached
