"""Median host time of one ``ControlPlane.tick`` in the window, in ms,
timed by the benchmark around the call."""
import statistics


def read(ctx):
    return statistics.median(ctx.tick_s) * 1e3 if ctx.tick_s else None
