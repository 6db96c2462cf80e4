"""A dense random operand with independent standard normal entries."""

import jax


def make(key, n: int, dtype):
    return jax.random.normal(key, (n, n), dtype)
