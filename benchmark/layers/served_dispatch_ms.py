"""Mean of the Session's ``stage_dispatch`` span over the window: the
host time from a batch's solve call to its program being enqueued
(``runtime/session.py``), in ms."""


def read(ctx):
    b = ctx["before"]["histograms"].get("stage_dispatch", {})
    a = ctx["after"]["histograms"].get("stage_dispatch", {})
    count = a.get("count", 0) - b.get("count", 0)
    if count <= 0:
        return None
    return 1e3 * (a["sum"] - b.get("sum", 0.0)) / count
