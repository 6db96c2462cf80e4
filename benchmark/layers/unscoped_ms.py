"""Device time per call in no phase of ``benchmark/phases.py``: ops
under no step, solve or layout scope, and the copies and fusions that
XLA inserts with no ``op_name``, in ms."""

from benchmark import phases


def read(ctx):
    return phases.layer_ms(ctx, "unscoped")
