"""MiB each chip receives through the collectives of one solve: the
program's ``exec.collective_bytes`` (counted per dispatch from its
traced exchanges: all_gather, psum, ppermute) over its
``exec.dispatches``.  Every solve of a cell dispatches the same program,
so the ratio over the process is the window's.  ``None`` on a program
without the counter."""
import program_registry


def read(ctx):
    moved = program_registry.counter_total("exec.collective_bytes",
                                           backend="pallas")
    runs = program_registry.counter_total("exec.dispatches",
                                          backend="pallas")
    if moved is None or not runs:
        return None
    return moved / runs / 2**20
