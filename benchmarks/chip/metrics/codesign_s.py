"""Seconds of the program's staging pipeline (trace, analyze, codesign,
lower) in set-up, from its ``session.stage_s`` spans."""


def read(ctx):
    return ctx["setup"].get("codesign_s")
