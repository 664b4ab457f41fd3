"""The column-blocked matvec kernels' share of one chip's HBM roofline:
the bytes one chip's operator applications of a solve move
(``mesh_work.py``: its row block of the operator and its vectors, each
application) over the chip's HBM bandwidth, over the device time per
solve of the ``cello_wide_*`` kernels among the trace's ten largest ops
(per chip: ``trace_reduce`` averages over the devices).  ``None``
without a trace or without such a kernel."""
import mesh_work

WIDE_PREFIX = "cello_wide_"


def read(ctx):
    trace, solves, peaks = ctx.get("trace"), ctx.get("solves"), \
        ctx.get("peaks")
    if trace is None or not solves or peaks is None:
        return None
    spent = sum(t for name, t in trace["device_ops"]
                if name.startswith(WIDE_PREFIX))
    if spent <= 0:
        return None
    least = (mesh_work.shard_solve_matvec_bytes(ctx["config"])
             / peaks["hbm_bytes_per_s"])
    return least / (spent / solves) * 100.0
