"""The least time of one solve on the cell's chips as a share of their
device time per solve: ``work.py``'s least time on one chip (the larger
of the solve's bytes over HBM bandwidth and its flops over peak), over
the ``mesh`` chips the operator is split across, over the trace's busy
time per solve (``trace_reduce`` averages it over the devices).  The
one-chip ``solve_roofline`` would read K times too high here."""
import mesh_work
import work


def read(ctx):
    trace, solves, peaks = ctx.get("trace"), ctx.get("solves"), \
        ctx.get("peaks")
    if trace is None or not solves or peaks is None \
            or trace["busy_s"] <= 0:
        return None
    least, _bound = work.least_time_s(ctx["work"], peaks)
    least /= mesh_work.chips(ctx["config"])
    return least / (trace["busy_s"] / solves) * 100.0
