"""gesv: LU with partial pivoting and a solve, ``st.gesv``; its
factorization is the Session's ``lu``."""

from benchmark import work

FACTOR = "lu"  # the op a Session registers this verb's operand under


def wrap(a, nb: int):
    """The operand as the verb takes it: the whole matrix, tiled."""
    import slate_tpu as st

    return st.from_dense(a, nb=nb)


def call(A, B, opts):
    import slate_tpu as st

    return st.gesv(A, B, opts)


def cost(n: int, k: int, itemsize: int) -> tuple[float, float]:
    """(flops, bytes) of one call: getrf and a k-column solve; A and B
    read, X written."""
    return (work.getrf_flops(n) + work.solve_flops(n, k),
            itemsize * (n * n + 2.0 * n * k))
