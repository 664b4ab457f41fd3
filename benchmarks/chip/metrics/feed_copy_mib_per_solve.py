"""MiB of the caller's device feeds the executor copies before donating
them to the executable, per solve: the program's ``exec.feed_copy_bytes``
over its ``exec.dispatches``.  Every solve of a cell passes the same
feeds, so the ratio over the process's solves is the window's."""
import program_registry


def read(ctx):
    copied = program_registry.counter_total("exec.feed_copy_bytes",
                                            backend="pallas")
    runs = program_registry.counter_total("exec.dispatches",
                                          backend="pallas")
    if copied is None or not runs:
        return None
    return copied / runs / 2**20
