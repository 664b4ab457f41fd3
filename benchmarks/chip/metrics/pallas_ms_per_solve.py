"""Device time in the program's Pallas kernels (``cello_*``) per solve of
the traced window."""


def read(ctx):
    trace, solves = ctx.get("trace"), ctx.get("solves")
    if trace is None or not solves or trace["pallas_s"] <= 0:
        return None
    return trace["pallas_s"] / solves * 1e3
