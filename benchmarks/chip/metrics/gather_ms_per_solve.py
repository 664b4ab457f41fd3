"""Device time per solve of the traced window in the spmv's
``values * x[cols]`` gather: the ops the program runs under its
``spmv_gather`` scope (``device_scopes.py``)."""
import device_scopes


def read(ctx):
    return device_scopes.ms_per_solve(ctx, "spmv_gather")
