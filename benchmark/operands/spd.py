"""A symmetric positive definite operand: A = G·Gᵀ/n + I, G standard
normal, formed in float32 at full precision and symmetrised before it is
cast. Its eigenvalues lie in about [1, 5] (Marchenko–Pastur, plus the
identity), so every seed gives a well-conditioned SPD matrix."""

import jax
import jax.numpy as jnp


def make(key, n: int, dtype):
    g = jax.random.normal(key, (n, n), jnp.float32)
    a = jnp.matmul(g, g.T, precision="highest") / n + jnp.eye(n)
    return (0.5 * (a + a.T)).astype(dtype)
