"""The plain reference of a linear solve: an answer X is correct when it
solves A·X = B.

Each column is held to the LAPACK tester's scaled residual
‖b − A·x‖₁ / (ε·n·‖A‖₁·‖x‖₁), computed here in float64 with numpy from
the operands the benchmark made, so nothing of the program takes part.
(SLATE's ``test/test_posv.cc`` and ``test/test_gesv.cc`` check the same
quantity; ``chip_smoke.py`` computes it the same way.)
"""

import numpy as np


def compare(a, x, b, dtype: str) -> np.ndarray:
    """Per column of x (n×k) and b (n×k): the scaled residual, with ε of
    ``dtype``. A column that is not finite reads inf."""
    a = np.asarray(a, dtype=np.float64)
    x = np.asarray(x, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    eps = float(np.finfo(np.dtype(dtype)).eps)
    r = b - a @ x
    den = eps * a.shape[1] * float(np.linalg.norm(a, 1)) * np.maximum(
        np.abs(x).sum(axis=0), 1e-300)
    out = np.abs(r).sum(axis=0) / den
    out[~np.isfinite(x).all(axis=0) | ~np.isfinite(out)] = np.inf
    return out
