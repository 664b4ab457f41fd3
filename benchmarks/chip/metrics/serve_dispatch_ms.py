"""Mean time of one batched dispatch over the window, from the program's
``serve.dispatch_s`` spans (operator feed, the vmapped solve, and the
copy of the answers back to the host)."""


def read(ctx):
    serve = ctx.get("serve")
    return None if serve is None else serve["dispatch_ms"]
