"""posv: Cholesky factor-and-solve of an SPD operand, ``st.posv``; its
factorization is the Session's ``chol``."""

from benchmark import work

FACTOR = "chol"  # the op a Session registers this verb's operand under


def wrap(a, nb: int):
    """The operand as the verb takes it: its lower triangle, Hermitian."""
    import jax.numpy as jnp
    import slate_tpu as st

    return st.hermitian(jnp.tril(a), nb=nb, uplo=st.Uplo.Lower)


def call(A, B, opts):
    import slate_tpu as st

    return st.posv(A, B, opts)


def cost(n: int, k: int, itemsize: int) -> tuple[float, float]:
    """(flops, bytes) of one call: potrf and a k-column solve; the lower
    triangle of A and B read, X written."""
    return (work.potrf_flops(n) + work.solve_flops(n, k),
            itemsize * (n * (n + 1) / 2.0 + 2.0 * n * k))
