"""Device time per call in the triangular solves: the ops under
``potrs_fwd``/``potrs_bwd`` (``linalg/cholesky.py``) or
``getrs_fwd``/``getrs_bwd`` (``linalg/lu.py``), the ``blocked.trsm_rec``
sweeps with their operands' canonicalization, pad and row permute, in
ms; None where the program has no such scope."""

from benchmark import phases


def read(ctx):
    return phases.layer_ms(ctx, "solve")
