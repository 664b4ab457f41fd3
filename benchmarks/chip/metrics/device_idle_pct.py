"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    trace = ctx.get("trace")
    if trace is None:
        return None
    return (1.0 - trace["busy_s"] / trace["window_s"]) * 100.0
