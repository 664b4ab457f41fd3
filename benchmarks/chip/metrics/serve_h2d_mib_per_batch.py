"""MiB of host arrays each batched dispatch of the service hands the
device (the shared operator and the padded lanes' inputs): the program's
``serve.h2d_bytes`` over its ``serve.batch_dispatches``."""
import program_registry


def read(ctx):
    sent = program_registry.counter_total("serve.h2d_bytes")
    batches = program_registry.counter_total("serve.batch_dispatches")
    if sent is None or not batches:
        return None
    return sent / batches / 2**20
