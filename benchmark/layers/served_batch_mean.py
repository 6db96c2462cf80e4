"""Requests per dispatched batch over the window, from the Batcher's
``batch_size`` histogram (``runtime/batching.py``): its sum over its
count, the difference of the Session's metrics across the window."""


def read(ctx):
    b = ctx["before"]["histograms"].get("batch_size", {})
    a = ctx["after"]["histograms"].get("batch_size", {})
    count = a.get("count", 0) - b.get("count", 0)
    if count <= 0:
        return None
    return (a["sum"] - b.get("sum", 0.0)) / count
