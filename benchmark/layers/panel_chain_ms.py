"""Device time per call in the drivers' panel chain: the ops whose
innermost driver scope is ``<verb>_l<k>_tile`` or ``<verb>_l<k>_panel``
(``_lookahead`` included), from ``linalg/cholesky.py`` and
``linalg/lu.py``, in ms."""

from benchmark import scopes


def read(ctx):
    return scopes.per_call_ms(ctx, "panel")
