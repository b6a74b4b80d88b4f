"""The whole step's share of the chip's bf16 peak, in %: the operations
the chunks delivered in the window require at their fidelities (S denoise
passes and the clean pass each; ``bench/flops.py``), over the window
times the peak.  Passes a chunk ran before the window opened count too,
so the first chunks of the window read slightly high."""
from bench import flops


def read(ctx):
    if not ctx.delivered:
        return None
    work = sum(flops.chunk_flops(ctx.dims, key, n) for key, n in ctx.delivered)
    return 100.0 * work / (ctx.window_s * ctx.peak["bf16_flops"])
