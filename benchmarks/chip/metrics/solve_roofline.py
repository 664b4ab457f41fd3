"""The least time of one solve on the chip (``work.py``: the larger of
its bytes over HBM bandwidth and its flops over peak) as a share of the
device time of one solve in the traced window: the time in which an
operation ran on the device (the trace's busy time) over the solves."""
import work


def read(ctx):
    trace, solves, peaks = ctx.get("trace"), ctx.get("solves"), \
        ctx.get("peaks")
    if trace is None or not solves or peaks is None \
            or trace["busy_s"] <= 0:
        return None
    least, _bound = work.least_time_s(ctx["work"], peaks)
    return least / (trace["busy_s"] / solves) * 100.0
