"""Dispatching wrapper for paged attention (``kernels.mode``: the Pallas
kernel on a TPU, the jnp oracle or interpret mode off it)."""
from __future__ import annotations

from repro.kernels.mode import kernel_mode
from repro.kernels.paged_attention import ref as _ref


def paged_decode_attention(q, k_pages, v_pages, block_table, lengths):
    """q [B,Hq,D]; pages [P_total,page,Hkv,D]; block_table [B,n];
    lengths [B] -> [B,Hq,D]."""
    mode = kernel_mode()
    if mode == "ref":
        return _ref.paged_decode_attention_ref(q, k_pages, v_pages,
                                               block_table, lengths)
    from repro.kernels.paged_attention import kernel as _k
    return _k.paged_decode_attention_pallas(
        q, k_pages, v_pages, block_table, lengths,
        interpret=(mode == "interpret"))


def paged_chunk_attention(q, k_pages, v_pages, block_table, page_mask,
                          layer=None, *, sink: int = 0,
                          chunk_tokens: int = 0):
    """Chunk-query paged attention partials (the serving executor's
    ``paged`` context backend).  q [B,Sq,Hq,D]; pages head-major
    [P_total,Hkv,page,D], or the layer-stacked pool
    [L,P_total,Hkv,page,D] with ``layer`` picking the layer in place;
    block_table [B,n]; page_mask [B,n*page] bool.
    ``sink``/``chunk_tokens`` optionally declare the valid prefix of the
    sink page / ring pages so the oracle can skip always-masked page
    tails (the Pallas kernel never reads them);
    ``page_mask=None`` (hint required) is the all-visible fast
    path that skips per-score masking.  ``page_mask`` is per-ROW, so a
    single launch serves rows with different fidelity windows and
    sparsities (fused heterogeneous-fidelity dispatch) as well as rows
    degraded by partial-window page eviction: the caller maps a dropped
    ring page's hole entry to some valid page row (the stream's own
    sink) with its whole mask slice false, so whatever K/V the hole
    stand-in holds contributes only -inf scores and never reaches the
    softmax.  Returns fp32 online-softmax
    partials (m, l [B,Hkv,G,Sq]; acc [B,Hkv,G,Sq,D] unnormalized) for
    ``attention.paged_mha`` to merge with the chunk's own fresh KV
    segment."""
    mode = kernel_mode()
    if mode == "ref":
        return _ref.paged_chunk_attention_ref(
            q, k_pages, v_pages, block_table, page_mask, layer,
            sink=sink, chunk_tokens=chunk_tokens)
    from repro.kernels.paged_attention import kernel as _k
    return _k.paged_chunk_attention_pallas(
        q, k_pages, v_pages, block_table, page_mask, layer,
        sink=sink, chunk_tokens=chunk_tokens,
        interpret=(mode == "interpret"))
