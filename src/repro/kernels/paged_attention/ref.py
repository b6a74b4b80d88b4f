"""Pure-jnp oracles for paged attention.

``paged_decode_attention_ref`` (single-token decode) gathers the logical
KV sequence out of the physical page pool through the block table, then
runs the dense decode-attention reference.  ``paged_chunk_attention_ref``
is the chunk-query generalization over the head-major serving pool,
used by the batched serving executor's ``paged`` context backend: it
returns ONLINE-SOFTMAX PARTIALS over the visible page set so the caller
can merge them with the chunk's own fresh KV segment
(``models.attention.paged_mha``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.attention import decode_attention

NEG_INF = -1e30


def gather_pages(pages: jax.Array, block_table: jax.Array) -> jax.Array:
    """pages [P_total, page, Hkv, D]; block_table [B, n] -> [B, n*page, Hkv, D]."""
    b, n = block_table.shape
    _, page, hkv, d = pages.shape
    out = pages[block_table.reshape(-1)]            # [B*n, page, Hkv, D]
    return out.reshape(b, n * page, hkv, d)


def paged_decode_attention_ref(q: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array, block_table: jax.Array,
                               lengths: jax.Array) -> jax.Array:
    """q [B,Hq,D] -> [B,Hq,D]; lengths [B] = valid tokens per sequence."""
    b, hq, d = q.shape
    hkv = k_pages.shape[2]
    k = gather_pages(k_pages, block_table)
    v = gather_pages(v_pages, block_table)
    out = decode_attention(q[:, None], k, v, n_kv_heads=hkv,
                           cache_len=lengths)
    return out[:, 0]


def paged_chunk_attention_ref(q: jax.Array, k_pages: jax.Array,
                              v_pages: jax.Array, block_table: jax.Array,
                              page_mask: jax.Array, layer=None, *,
                              sink: int = 0, chunk_tokens: int = 0):
    """Chunk-query paged attention partials over the visible page set.

    q [B,Sq,Hq,D]; pages HEAD-MAJOR [P_total, Hkv, page, D], or the
    layer-stacked pool [L, P_total, Hkv, page, D] with ``layer`` picking
    the layer (folded into the page gather, never a pool slice);
    block_table [B, n]; page_mask [B, n*page] bool — visible context
    tokens in TABLE order (entry 0's tokens first, then entry 1's, ...),
    with page tails past each page's valid extent already masked off by
    the caller.  ``page_mask=None`` (layout hint required) means "every
    valid token visible" — the homogeneous-fill, full-window,
    unsparsified common case — and skips per-score masking entirely.

    ``sink``/``chunk_tokens`` are an optional layout hint: when given,
    table entry 0 is known to hold at most ``sink`` valid tokens and
    every later entry at most ``chunk_tokens``, so the oracle skips the
    always-masked page tails entirely (the TPU kernel skips them per
    context tile), and the CPU serving path does not pay FLOPs for
    provably-dead padding.  The partials are identical either way:
    masked tokens contribute m=NEG_INF, p=0.

    Returns unfinalized fp32 partials in the ``attention._merge`` layout:
    m, l [B, Hkv, G, Sq] and acc [B, Hkv, G, Sq, D] (acc unnormalized),
    with m == NEG_INF where a query row saw no visible token.
    """
    if k_pages.ndim == 4:
        k_pages, v_pages = k_pages[None], v_pages[None]
        layer = 0
    b, sq, hq, d = q.shape
    hkv, page = k_pages.shape[2], k_pages.shape[3]
    g = hq // hkv
    n = block_table.shape[1]
    scale = 1.0 / math.sqrt(d)
    s0, tc = min(sink, page), min(chunk_tokens, page)
    if page_mask is None:
        assert sink and chunk_tokens, \
            "page_mask=None needs the sink/chunk_tokens layout hint"

    def tokens(pages, rows, extent):
        """[B, len(rows) * extent, Hkv, D]: the first ``extent`` tokens
        of each table entry in ``rows``, in table order."""
        x = pages[layer, block_table[:, rows], :, :extent]   # [B,r,H,e,D]
        return x.transpose(0, 1, 3, 2, 4).reshape(b, -1, hkv, d)

    if sink and chunk_tokens and (s0 < page or (n > 1 and tc < page)):
        # compact layout: valid prefixes only
        k = tokens(k_pages, slice(0, 1), s0)
        v = tokens(v_pages, slice(0, 1), s0)
        if n > 1:
            k = jnp.concatenate([k, tokens(k_pages, slice(1, n), tc)], 1)
            v = jnp.concatenate([v, tokens(v_pages, slice(1, n), tc)], 1)
        if page_mask is not None:
            cols = [jnp.arange(s0)] + [(1 + r) * page + jnp.arange(tc)
                                       for r in range(n - 1)]
            page_mask = page_mask[:, jnp.concatenate(cols)]
    else:
        k = tokens(k_pages, slice(0, n), page)      # [B, n*page, Hkv, D]
        v = tokens(v_pages, slice(0, n), page)
    qg = q.reshape(b, sq, hkv, g, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if page_mask is None:       # every compact token visible: no select
        m = jnp.max(s, axis=-1)                     # [B,Hkv,G,Sq]
        p = jnp.exp(s - m[..., None])
    else:
        vis = page_mask[:, None, None, None, :]
        s = jnp.where(vis, s, NEG_INF)
        m = jnp.max(s, axis=-1)
        p = jnp.where(vis, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhgqk,bkhd->bhgqd", p, v.astype(jnp.float32))
    return m, l, acc
