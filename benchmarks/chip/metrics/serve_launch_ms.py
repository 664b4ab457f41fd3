"""Mean host time of the service's batched launch (the host arguments'
transfers are enqueued there, and finish later), from the program's
``serve.launch_s``, over the launches that did not trace a new batch
width."""
import program_registry


def read(ctx):
    mean = program_registry.hist_mean("serve.launch_s", traced="False")
    return None if mean is None else mean * 1e3
