"""Device time per call in the drivers' layout work: the ops under
``potrf_prologue``/``getrf_prologue``, ``potrf_epilogue``/
``getrf_epilogue`` and each step's ``<verb>_l<k>_load`` and
``<verb>_l<k>_store`` (``linalg/cholesky.py``, ``linalg/lu.py``), in
ms; None where the program has no such scope."""

from benchmark import phases


def read(ctx):
    return phases.layer_ms(ctx, "layout")
