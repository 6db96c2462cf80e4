"""What every loop of ``benchmark/loops/`` shares: the outcome it
returns, the sizes of a rehearsal, the configuration's options, the
sample of answers kept for the check, the device's memory peak, a
tail by nearest rank, and the count of lowerings in the window.

A loop's ``run(cell, seed, seconds, tracer, rehearse=False,
control=False, held=None)`` makes its data from the seed, lowers and
warms the cell's programs (set-up), drives the system for ``seconds``,
checks the answers with the cell's plain reference and returns an
``Outcome``. ``control`` runs the configuration's control in the
program's place (``readings.py``); ``held`` keeps compiled programs
from one call to the next in one process. The program is imported
inside the functions, after ``run.py`` has placed the compile cache.
"""

from __future__ import annotations

import dataclasses

import numpy as np

# sizes of a CPU rehearsal, which prints no metric; the mix and every
# other setting stay as the cell has them
REHEARSAL = {"n": 256, "nb": 64}


@dataclasses.dataclass
class Outcome:
    t_window: float  # host clock at the first measured call
    values: dict  # end-to-end metric name -> number
    attempted: int
    failed: int
    compared: dict  # name -> number, held to the cell's limit of that name
    context: dict  # what the per-layer readers take
    diagnostics: dict


def sizes(config: dict, rehearse: bool) -> tuple[int, int]:
    if rehearse:
        return REHEARSAL["n"], REHEARSAL["nb"]
    return int(config["n"]), int(config["nb"])


def options(config: dict, control: bool):
    """The configuration's Options, or its control's: the program's own
    lower-precision path (``config["control"]``)."""
    from slate_tpu.core.types import Options

    return Options(**(config["control"]["options"] if control
                      else config["options"]))


class Reservoir:
    """A uniform sample of fixed size from a stream of unknown length,
    drawn from the seed (Algorithm R); the rest is let go as it comes."""

    def __init__(self, size: int, rng: np.random.Generator):
        self.size, self.rng, self.seen, self.items = size, rng, 0, []

    def offer(self, item):
        if len(self.items) < self.size:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.size:
                self.items[j] = item
        self.seen += 1


def peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def nearest_rank(values, q: float) -> float:
    """The q-quantile (0 < q ≤ 1) by nearest rank over all values."""
    s = np.sort(np.asarray(values, dtype=np.float64))
    return float(s[max(0, int(np.ceil(q * len(s))) - 1)])


class Lowerings:
    """Counts the programs JAX lowers from now on, in any thread
    (chip_smoke.py's ``Compiles``): a call in the window that lowers one
    was not covered by set-up."""

    def __init__(self):
        import jax.monitoring

        self.n, self.on = 0, True
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.on and event == \
                "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.n += 1

    def stop(self) -> int:
        self.on = False
        return self.n
