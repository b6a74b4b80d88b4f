"""Operations and bytes the AR-DiT work needs, counted from its shapes.

A pass is one forward of one chunk (a denoise step or the clean pass):
``n_layers`` blocks of Q/K/V/O projections, a GELU MLP, the modulation
matmul, attention of the chunk's T queries over its own T tokens and over
the visible context (sink + window tokens left by rho), plus the in/out
projections and the time embedding.  A multiply-add counts 2 operations.
"""
from __future__ import annotations

from typing import Tuple

from bench.reference.ardit import (Dims, parse_fidelity,
                                   visible_window_tokens)


def visible_context(d: Dims, n: int, window: int, sparsity: float) -> int:
    """Context tokens a chunk at fill ``n`` attends to: the sink and the
    window tokens kept under rho."""
    return d.cond_tokens + len(visible_window_tokens(d, n, window, sparsity))


def pass_flops(d: Dims, visible: int) -> float:
    """One pass of one chunk with ``visible`` context tokens."""
    t, dm = d.chunk_tokens, d.d_model
    hq, hkv = d.n_heads * d.d_head, d.n_kv_heads * d.d_head
    per_layer = (2 * t * dm * (2 * hq + 2 * hkv)        # q, k, v, o
                 + 4 * t * dm * d.d_ff                   # MLP in + out
                 + 2 * dm * 6 * dm                       # modulation
                 + 4 * t * t * hq                        # in-chunk attention
                 + 4 * t * visible * hq)                 # context attention
    return float(d.n_layers * per_layer
                 + 4 * t * d.latent * dm                 # in + out proj
                 + 2 * 256 * dm + 2 * dm * dm + 4 * dm * dm)  # t-emb, final


def chunk_flops(d: Dims, fid_key: str, n: int) -> float:
    """All passes of a chunk at fill ``n`` served at fidelity ``fid_key``:
    S denoise passes (window thinned by rho) and the clean pass."""
    f = parse_fidelity(fid_key)
    return (f.steps * pass_flops(d, visible_context(d, n, f.window,
                                                    f.sparsity))
            + pass_flops(d, visible_context(d, n, f.window, 0.0)))


def paged_kernel_cost(d: Dims, visible: int,
                      kv_bytes: int = 2) -> Tuple[float, float]:
    """(operations, bytes) one layer's paged-context attention needs for
    one row: q.k and p.v over the visible context tokens; reading q and
    the visible K/V once, writing the fp32 softmax partials (m, l, acc)."""
    t, hq = d.chunk_tokens, d.n_heads * d.d_head
    flops = 4.0 * t * visible * hq
    nbytes = (t * hq * 2                                  # q, bf16
              + 2 * visible * d.n_kv_heads * d.d_head * kv_bytes
              + t * d.n_heads * (d.d_head + 2) * 4)       # m, l, acc
    return flops, float(nbytes)
