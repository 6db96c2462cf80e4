"""Operands and requests made from ``--seed``.

The operands are made on the device, in the type they are solved in, by
one jitted call: the n×n operand by the configuration's generator
(``benchmark/operands/<name>.py``, ``make(key, n, dtype)``) and blocks
of right-hand sides. Host-side draws (served requests, samples, arrival
order) come from numpy's SeedSequence, which takes a seed of any size.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    """A host generator for one named use of ``seed``."""
    return np.random.default_rng([int(seed), int(stream)])


def device_key(seed: int):
    hi, lo = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(hi)), int(lo))


@functools.partial(jax.jit,
                   static_argnames=("make", "n", "k", "blocks", "dtype"))
def _operands(key, make, n: int, k: int, blocks: int, dtype):
    ka, kb = jax.random.split(key)
    return make(ka, n, dtype), jax.random.normal(kb, (blocks, n, k), dtype)


def operands(seed: int, make, n: int, k: int, blocks: int, dtype: str):
    """(A, Bs): the n×n operand that ``make`` builds and ``blocks``
    right-hand sides of k columns each, on the default device."""
    return _operands(device_key(seed), make, n, k, blocks, jnp.dtype(dtype))


def request_rhs(seed: int, count: int, n: int, dtype: str) -> np.ndarray:
    """(count, n): one right-hand side per served request."""
    return rng(seed, 1).standard_normal((count, n), dtype=np.dtype(dtype))


def poisson_offsets(seed: int, rate: float, seconds: float) -> np.ndarray:
    """Due times, in seconds from the window's start, of an open loop at
    ``rate`` requests per second. Every seed gets the same gaps, the
    exponential quantiles at (i + ½)/N for N = rate·seconds, in an order
    drawn from the seed: the same load, arranged differently."""
    count = max(1, int(round(rate * seconds)))
    q = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-q) / rate
    gaps *= seconds / gaps.sum()
    order = rng(seed, 2).permutation(count)
    return np.cumsum(gaps[order]) - gaps[order][0]
