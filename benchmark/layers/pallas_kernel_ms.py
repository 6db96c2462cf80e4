"""Device time per call of the Mosaic custom calls (the Pallas kernels
of ``ops/pallas_ops.py``), in ms; 0 where the verb's program holds
none."""


def read(ctx):
    tr = ctx["trace"]
    runs = tr.modules.get(ctx["program"]) if tr else None
    if not runs:
        return None
    return 1e3 * tr.custom_s / len(runs)
