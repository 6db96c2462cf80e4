"""Device time per call of the triangular sweeps' batched inverses of
their diagonal blocks: the ops under ``trsm_diag_inv``
(``ops/blocked.py``) in whichever phase holds them, in ms; in the factor
cells a subset of ``solve_sweep_ms``. None where the program has none: a
sweep whose recursion leaves each invert their own block."""

from benchmark import phases

SCOPE = "trsm_diag_inv"


def read(ctx):
    return phases._per_call(ctx, lambda scope: SCOPE in scope.split("/"))
