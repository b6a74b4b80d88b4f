"""Share of its compute roofline the in-chunk attention reaches, in %:
4 T^2 (H Dh) L operations per row of every launch in the window (the
chunk's own T tokens, q.k and p.v in every layer) over the bf16 peak,
over the device time of the operations under the ``ardit/segment_attn``
scope (``_segment_attn``, the merge with the paged partials, the divide)
in the traced window (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.roofline(ctx, "segment_attn",
                           scopes.segment_attn_flops(ctx.dims))
