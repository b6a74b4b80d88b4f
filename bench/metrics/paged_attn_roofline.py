"""Share of its roofline that ``paged_chunk_attention_pallas`` reaches, in
%: the least time the chip could take for the window's paged-context
attention, the larger of operations / bf16 peak and bytes / HBM
bandwidth (``bench/flops.py``, summed over every layer of every launch
the window dispatched), over the kernel's device time in the trace.
For the chunk sizes served it is compute that bounds it."""
from bench import flops, trace

KERNEL = "paged_chunk_attention"


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace_window
    seconds, calls = trace.kernel_s(ctx.trace, KERNEL, lo, hi)
    if not calls or not ctx.launch_rows:
        return None
    ops = nbytes = 0.0
    for visible in ctx.launch_rows:
        f, b = flops.paged_kernel_cost(ctx.dims, visible)
        ops += f * ctx.dims.n_layers
        nbytes += b * ctx.dims.n_layers
    bound = max(ops / ctx.peak["bf16_flops"],
                nbytes / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * bound / seconds
