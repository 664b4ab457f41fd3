"""Mean wait of a request in the service's queue over the window, from
the program's ``serve.queue_wait_s`` spans (submit to batch close)."""


def read(ctx):
    serve = ctx.get("serve")
    return None if serve is None else serve["queue_wait_ms"]
