"""Device time per call in the trailing updates: the ops whose innermost
driver scope is ``<verb>_l<k>_trail*`` (the blocked updates of
``ops/blocked.py``), in ms."""

from benchmark import scopes


def read(ctx):
    return scopes.per_call_ms(ctx, "trail")
