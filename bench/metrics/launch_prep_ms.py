"""Median host time of one ``batcher/prep`` span in the window, in ms:
the executor's work before each step's dispatch (``_boundary``: block
tables and masks; ``_staging``; the batch's concatenate), read from the
program's own spans in the traced run (``bench/scopes.py``)."""
import statistics

from bench import scopes


def read(ctx):
    sc = scopes.of(ctx)
    if sc is None:
        return None
    prep = sc.spans_in("batcher/prep", *ctx.trace_window)
    if not prep:
        return None
    return statistics.median(e - s for _, s, e, _ in prep) * 1e3
