"""Programs compiled (or read from the persistent cache) inside the
window, counted by the compile clock; the warm-up should leave none."""


def read(ctx):
    return float(ctx.compiles)
