"""Seconds of the warm-up calls in set-up: every shape the window uses,
compiled or loaded from the compilation cache (host clock)."""


def read(ctx):
    return ctx["setup"].get("warmup_s")
