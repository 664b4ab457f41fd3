"""Device time in every other device operation (XLA's gathers, layout
builds, copies, reductions) per solve of the traced window."""


def read(ctx):
    trace, solves = ctx.get("trace"), ctx.get("solves")
    if trace is None or not solves:
        return None
    return trace["other_s"] / solves * 1e3
