"""Share of its compute roofline the MLP reaches, in %: 4 T d d_ff L
operations per row of every launch in the window (the in and out
projections of every layer) over the bf16 peak, over the device time of
the operations under the ``ardit/mlp`` scope (its norm, modulation,
projections, GELU and residual) in the traced window
(``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.roofline(ctx, "mlp", scopes.mlp_flops(ctx.dims))
