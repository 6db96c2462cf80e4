"""Test configuration: 8 virtual CPU devices.

The reference tests multi-node behavior by actually running
``mpirun -np N`` (SURVEY §4). Our analog: an 8-device virtual CPU mesh via
--xla_force_host_platform_device_count, so every collective/sharding path
runs in CI without a TPU pod — the same trick the driver's
dryrun_multichip uses. Single-device degeneracy is tested with 1×1 grids.
"""

import importlib.util
import os

# load compat/platform.py standalone (importing the slate_tpu package
# here would initialize jax before XLA_FLAGS is finalized)
_spec = importlib.util.spec_from_file_location(
    "_slate_tpu_platform",
    os.path.join(os.path.dirname(__file__), os.pardir, "slate_tpu",
                 "compat", "platform.py"))
_platform = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_platform)

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
_probe_cache = os.path.join(os.path.dirname(__file__), os.pardir,
                            ".xla_flag_probe.json")
if "xla_cpu_collective_call_terminate_timeout_seconds" not in flags:
    # ROOT CAUSE of the round-2 intermittent hard-crash: XLA CPU
    # cross-module collectives rendezvous with a 40 s termination
    # timeout and ABORT the process ("Exiting to ensure a consistent
    # program state", rendezvous.cc) when any virtual device's thread is
    # starved past it — which happens under CPU oversubscription (other
    # test processes / BLAS threads). Reproduced deliberately in round 3
    # by running the suite next to a busy bench process. Raise the
    # timeout so a loaded CI box degrades to slow instead of crashing.
    # GUARDED by a support probe: jaxlib builds that dropped this flag
    # ABORT on unknown XLA_FLAGS (parse_flags_from_env.cc), which used
    # to kill the entire suite at CPU-client creation.
    flags = (flags + _platform.collective_timeout_flag_if_supported(
        cache_path=_probe_cache))
os.environ["XLA_FLAGS"] = flags
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402

# Round-2 advisor: a 1-in-4 interpreter hard-crash was seen running
# test_compat.py + test_distribution.py in one process. Root-caused in
# round 3 to the XLA CPU collective rendezvous termination timeout (see
# the XLA_FLAGS comment above); the timeout is raised now. Keep a
# persistent faulthandler trace armed so any new crash class leaves a
# full C-level stack in tests/.faulthandler.log.
import faulthandler  # noqa: E402

_fh_log = open(os.path.join(os.path.dirname(__file__),
                            ".faulthandler.log"), "w")
faulthandler.enable(file=_fh_log, all_threads=True)

# Round-12 stall forensics: the round-11 futex-stall class (XLA:CPU
# collective rendezvous starved under host load) hangs the suite until
# the tier-1 harness's `timeout -k` SIGKILLs it — leaving NO evidence
# of where the threads sat. Arm a dump-traceback watchdog below the
# 870 s tier-1 budget so a stalled run writes every thread's Python
# stack into tests/.faulthandler.log BEFORE the kill (repeat=True: a
# run that stalls twice dumps twice). Tunable/disable-able via env
# (0 disables) for interactive long runs; cancelled on clean session
# finish so post-suite teardown never dumps spuriously.
_STALL_DUMP_S = float(os.environ.get("SLATE_TPU_TIER1_STALL_DUMP_S",
                                     "780"))
if _STALL_DUMP_S > 0:
    faulthandler.dump_traceback_later(_STALL_DUMP_S, repeat=True,
                                      file=_fh_log, exit=False)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: large/expensive cases excluded from the tier-1 "
        "budget (run explicitly with -m slow)")


def pytest_sessionfinish(session, exitstatus):
    # a finished (even failed) session is not a stall
    faulthandler.cancel_dump_traceback_later()


@pytest.fixture(scope="session")
def devices():
    d = jax.devices()
    assert len(d) >= 8, f"expected 8 virtual devices, got {len(d)}"
    return d


@pytest.fixture(scope="session")
def grid2x2():
    from slate_tpu.core.grid import ProcessGrid
    return ProcessGrid.create(2, 2)


@pytest.fixture(scope="session")
def grid2x4():
    from slate_tpu.core.grid import ProcessGrid
    return ProcessGrid.create(2, 4)


@pytest.fixture(scope="session")
def grid1x1():
    from slate_tpu.core.grid import ProcessGrid
    return ProcessGrid.create(1, 1)
