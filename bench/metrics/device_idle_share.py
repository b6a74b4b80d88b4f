"""Share of the traced window in which no operation ran on the device,
in %: 1 - (union of device op intervals) / window, averaged over chips."""
from bench import trace


def read(ctx):
    if ctx.trace is None or not ctx.trace.device:
        return None
    lo, hi = ctx.trace_window
    return 100.0 * (1.0 - trace.busy_s(ctx.trace, lo, hi) / (hi - lo))
