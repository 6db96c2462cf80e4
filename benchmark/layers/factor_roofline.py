"""The verb's share of its roofline: the least time the chip could take
for the call (the larger of its FLOPs over the bf16 peak and its bytes
over HBM bandwidth: the verb's ``cost`` and ``peaks.json``) over the
device time of one execution of the verb's program, in %."""

from benchmark import work


def read(ctx):
    runs = ctx["trace"].modules.get(ctx["program"]) if ctx["trace"] else None
    if not runs:
        return None
    flops, nbytes = ctx["work"]
    least_s, _ = work.roofline_s(flops, nbytes,
                                 work.peaks(ctx["device_kind"]))
    return 100.0 * least_s / (sum(runs) / len(runs))
