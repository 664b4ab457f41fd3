"""Device time per solve, on one chip, in the collectives that move data
between chips (all-gather, all-reduce, collective-permute,
reduce-scatter, all-to-all, and their async start / done halves), among
the trace's ten largest ops (``trace_reduce`` averages over the
devices).  A TPU trace names an op by its HLO instruction, which may
carry the name of the JAX primitive that made it: ``lax.psum``'s
all-reduce reads ``psum.<k>``, ``lax.ppermute``'s collective-permute
``ppermute.<k>``.  ``None`` without a trace or when no collective is
among them."""
COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "reduce-scatter", "all-to-all", "psum", "ppermute")


def read(ctx):
    trace, solves = ctx.get("trace"), ctx.get("solves")
    if trace is None or not solves:
        return None
    found = [t for name, t in trace["device_ops"]
             if name.startswith(COLLECTIVES)]
    if not found:
        return None
    return sum(found) / solves * 1e3
