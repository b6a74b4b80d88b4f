"""Share of BMPR's chunk decisions in the window (``bmpr/select`` marks,
one per chunk begun) made in speed-recovery mode: no fidelity at or above
the quality floor fit the stream's slack, so the fastest one was taken.
Read from the program's own marks in the traced run (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    sc = scopes.of(ctx)
    if sc is None:
        return None
    marks = sc.spans_in("bmpr/select", *ctx.trace_window)
    if not marks:
        return None
    return sum(m[3].get("mode") == "speed-recovery"
               for m in marks) / len(marks)
