"""Host time to enqueue one solve (feed checks, the feed copies, the
executable's launch), not device time: the mean of the program's
``exec.dispatch_s`` over its runs that did not trace the program."""
import program_registry


def read(ctx):
    mean = program_registry.hist_mean("exec.dispatch_s", backend="pallas",
                                      traced="False")
    return None if mean is None else mean * 1e3
