"""1 - (union of the device's busy intervals) / (traced window), in %."""

from bench import trace_reduce as tr


def read(ctx):
    lo, hi = tr.window(ctx.trace)
    if hi <= lo:
        return None
    return 100.0 * (1.0 - tr.busy(ctx.trace, lo, hi) / (hi - lo))
