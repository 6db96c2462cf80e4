"""Device time per call of the row interchanges: the ops under a
``row_swap`` scope (``ops/blocked.py``, ``linalg/lu.py``) in whichever
phase holds them, in ms; None where the program has none. A gather
that XLA fuses into a gemm takes the fusion root's ``op_name``, so
this counts the interchanges left standing as ops of their own."""

from benchmark import phases


def read(ctx):
    return phases.row_swap_ms(ctx)
