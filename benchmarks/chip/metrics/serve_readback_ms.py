"""Mean time of reading a batch's answers back to the host, which waits
for the transfers of its host arguments and for the solve, from the
program's ``serve.readback_s``."""
import program_registry


def read(ctx):
    mean = program_registry.hist_mean("serve.readback_s")
    return None if mean is None else mean * 1e3
