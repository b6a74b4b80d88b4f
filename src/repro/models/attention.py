"""Attention substrate with the paper's fidelity knobs.

All variants are pure JAX (jnp + lax) with *static* block schedules so that
compiled FLOPs actually scale with the knobs:

  * causal        — block-triangular schedule, no masked-out waste blocks
  * windowed      — sink + sliding window (paper SS2.1 "sink+local"; knob W):
                    per-q-block static KV slices
  * block-sparse  — knob rho: deterministic strided block keep-list
  * decode        — single-query direct attention over a (possibly sharded)
                    KV cache

The Pallas TPU kernels in ``repro/kernels`` implement the same math with
explicit VMEM tiling; ``repro/kernels/*/ops.py`` dispatches between the two.
Numerics: fp32 online-softmax accumulation regardless of input dtype.
"""
from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _group(q: jax.Array, n_kv: int) -> jax.Array:
    """[B,S,Hq,D] -> [B,S,Hkv,G,D] without materializing repeated KV."""
    b, s, hq, d = q.shape
    assert hq % n_kv == 0, (hq, n_kv)
    return q.reshape(b, s, n_kv, hq // n_kv, d)


def _segment_attn(q, k, v, mask, scale):
    """One (q-block, kv-segment) flash step.

    q: [B,bq,Hkv,G,D]; k/v: [B,skv,Hkv,D]; mask: [bq,skv] bool or None.
    Returns unnormalized partials (s_max, p_sum, p_v) in fp32.
    """
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if mask is not None:
        # [bq,skv] shared across batch, or [B,bq,skv] per-batch (the
        # batched serving executor's per-stream KV-validity masks)
        mask = mask[None, None, None] if mask.ndim == 2 \
            else mask[:, None, None]
        s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1)                                   # [B,H,G,bq]
    # Guard fully-masked rows (all -inf).
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)                                   # [B,H,G,bq]
    pv = jnp.einsum("bhgqk,bkhd->bhgqd", p, v.astype(jnp.float32))
    return m_safe, l, pv


def _merge(acc, new):
    """Merge two online-softmax partials."""
    m0, l0, o0 = acc
    m1, l1, o1 = new
    m = jnp.maximum(m0, m1)
    c0 = jnp.exp(m0 - m)
    c1 = jnp.exp(m1 - m)
    return m, l0 * c0 + l1 * c1, o0 * c0[..., None] + o1 * c1[..., None]


def _finalize(acc, dtype):
    _, l, o = acc
    l = jnp.where(l == 0.0, 1.0, l)                 # fully-masked rows -> 0
    out = o / l[..., None]                          # [B,H,G,bq,D]
    return out.astype(dtype)


def _init_acc(b, h, g, bq, d):
    z = jnp.zeros((b, h, g, bq), jnp.float32)
    return (jnp.full((b, h, g, bq), -jnp.inf, jnp.float32), z,
            jnp.zeros((b, h, g, bq, d), jnp.float32))


def _causal_mask(q_pos: jax.Array, k_pos: jax.Array) -> jax.Array:
    return q_pos[:, None] >= k_pos[None, :]


def sparse_keep_list(n_q_blocks: int, n_kv_blocks_per_q: Sequence[int],
                     sparsity: float, sink_blocks: int = 1) -> List[List[int]]:
    """Deterministic strided block keep-list for the rho fidelity knob.

    For q block i with causal KV blocks [0..i], always keep the sink block(s)
    and the diagonal block; keep a strided ~(1-rho) fraction of the rest.
    """
    keep: List[List[int]] = []
    frac = max(1e-6, 1.0 - sparsity)
    for i in range(n_q_blocks):
        n_kv = n_kv_blocks_per_q[i]
        forced = set(range(min(sink_blocks, n_kv))) | {n_kv - 1}
        middle = [j for j in range(n_kv) if j not in forced]
        n_keep = int(round(len(middle) * frac))
        if n_keep >= len(middle):
            chosen = middle
        elif n_keep <= 0:
            chosen = []
        else:
            idx = np.linspace(0, len(middle) - 1, n_keep).round().astype(int)
            chosen = [middle[j] for j in sorted(set(idx.tolist()))]
        keep.append(sorted(forced | set(chosen)))
    return keep


def mha(q: jax.Array, k: jax.Array, v: jax.Array, *,
        n_kv_heads: int,
        causal: bool = True,
        q_offset: int = 0,
        window: int = 0,
        sink: int = 0,
        sparsity: float = 0.0,
        kv_mask: Optional[jax.Array] = None,
        block_q: int = 512,
        block_kv: int = 512) -> jax.Array:
    """Multi-head attention with GQA + fidelity knobs.

    q: [B,Sq,Hq,D]; k,v: [B,Skv,Hkv,D].  Returns [B,Sq,Hq,D].
    ``q_offset``: absolute position of q[0] relative to k[0] (for chunk-wise
    generation and decode, where Skv > Sq).
    ``kv_mask``: optional [B,Skv] per-batch KV validity (non-causal/direct
    path only) — the batched serving executor masks ring-cache slots that
    are unfilled, outside a stream's fidelity window, or sparsity-dropped.
    Because the mask is per-ROW data, one launch can serve rows with
    DIFFERENT fidelity windows/sparsities (fused heterogeneous-fidelity
    dispatch) and rows whose ring pages were partially evicted — the
    caller zeroes the dropped chunks' token slices and this function
    never reads them.
    """
    b, sq, hq, d = q.shape
    skv = k.shape[1]
    dtype = q.dtype
    scale = 1.0 / math.sqrt(d)
    qg = _group(q, n_kv_heads)

    # ---- direct path: decode / tiny shapes / cross attention --------------
    # (rho block sparsity is defined on the blocked causal schedule, so any
    #  sparsity>0 request takes the blocked path at the given block sizes)
    if ((sq * skv <= block_q * block_kv and sparsity == 0.0)
            or sq == 1 or not causal):
        mask = None
        if causal:
            q_pos = q_offset + jnp.arange(sq)
            k_pos = jnp.arange(skv)
            mask = _causal_mask(q_pos, k_pos)
            if window:
                mask &= (k_pos[None, :] > q_pos[:, None] - window) | \
                        (k_pos[None, :] < sink)
        if kv_mask is not None:
            km = kv_mask[:, None, :]                     # [B,1,Skv]
            mask = km if mask is None else mask[None] & km
        m, l, pv = _segment_attn(qg, k, v, mask, scale)
        out = _finalize((m, l, pv), dtype)
        return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, d)
    assert kv_mask is None, "kv_mask is only supported on the direct path"

    # ---- blocked paths -----------------------------------------------------
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    assert sq % block_q == 0, (sq, block_q)
    n_q = sq // block_q
    g = hq // n_kv_heads

    def kv_seg(lo: int, hi: int):
        return k[:, lo:hi], v[:, lo:hi]

    outs = []
    for i in range(n_q):
        q_blk = qg[:, i * block_q:(i + 1) * block_q]
        q_lo = q_offset + i * block_q
        q_hi = q_lo + block_q
        q_pos = q_lo + jnp.arange(block_q)
        acc = _init_acc(b, n_kv_heads, g, block_q, d)

        if window:
            # sink prefix + sliding window (static slices; exact FLOPs)
            segs: List[Tuple[int, int]] = []
            if sink:
                segs.append((0, min(sink, skv)))
            w_lo = max(sink, q_lo - window + 1)
            # round down for block alignment, but never below the sink
            # prefix (it has its own segment; overlap would double-count)
            w_lo = max((w_lo // block_kv) * block_kv, sink)
            segs.append((w_lo, min(q_hi, skv)))
            for lo, hi in segs:
                if lo >= hi:
                    continue
                ks, vs = kv_seg(lo, hi)
                k_pos = lo + jnp.arange(hi - lo)
                msk = _causal_mask(q_pos, k_pos)
                msk &= (k_pos[None, :] > q_pos[:, None] - window) | \
                       (k_pos[None, :] < sink)
                acc = _merge(acc, _segment_attn(q_blk, ks, vs, msk, scale))
        else:
            # causal block-triangular schedule; optional rho block sparsity
            n_kv_for_q = (q_hi + block_kv - 1) // block_kv
            if sparsity > 0.0:
                keep = sparse_keep_list(1, [n_kv_for_q], sparsity)[0]
            else:
                keep = list(range(n_kv_for_q))
            for j in keep:
                lo, hi = j * block_kv, min((j + 1) * block_kv, skv)
                ks, vs = kv_seg(lo, hi)
                if hi > q_lo:  # diagonal/edge segment: needs elementwise mask
                    k_pos = lo + jnp.arange(hi - lo)
                    msk = _causal_mask(q_pos, k_pos)
                else:
                    msk = None
                acc = _merge(acc, _segment_attn(q_blk, ks, vs, msk, scale))

        outs.append(_finalize(acc, dtype))

    out = jnp.concatenate([o.transpose(0, 3, 1, 2, 4).reshape(
        b, block_q, hq, d) for o in outs], axis=1)
    return out


def shard_heads(x: jax.Array, n_kv_heads: int, lo: int,
                hi: int) -> jax.Array:
    """Slice a [B,S,H,D] tensor to the heads grouped under KV heads
    [lo, hi) — the Ulysses-style head partition of elastic SP (SS4.3).

    For a query tensor H = n_heads = G * n_kv_heads and the slice keeps
    the G query heads of every KV head in [lo, hi); for a KV tensor
    H = n_kv_heads and the slice is direct.  Head order is preserved, so
    concatenating the shards' attention outputs with
    ``merge_head_shards`` is bit-identical to the unsharded call —
    per-head attention never mixes heads.
    """
    b, s, h, d = x.shape
    g = h // n_kv_heads
    return x.reshape(b, s, n_kv_heads, g, d)[:, :, lo:hi] \
        .reshape(b, s, (hi - lo) * g, d)


def merge_head_shards(outs: Sequence[jax.Array],
                      n_kv_heads_per_shard: Sequence[int]) -> jax.Array:
    """Concatenate per-shard attention outputs back into full-head
    order (inverse of ``shard_heads`` over a covering partition)."""
    b, s = outs[0].shape[:2]
    d = outs[0].shape[-1]
    parts = [o.reshape(b, s, h, -1, d)
             for o, h in zip(outs, n_kv_heads_per_shard)]
    merged = jnp.concatenate(parts, axis=2)
    return merged.reshape(b, s, -1, d)


def paged_mha(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
              block_table: jax.Array, page_mask: jax.Array,
              chunk_k: jax.Array, chunk_v: jax.Array, layer=None, *,
              n_kv_heads: int, sink: int = 0,
              chunk_tokens: int = 0) -> jax.Array:
    """Page-table-native attention for chunk-wise generation.

    q [B,Sq,Hq,D] attends to (a) the visible cached context, read IN
    PLACE from the physical head-major page pool ``k_pages``/``v_pages``
    [n_pages, Hkv, page, D] (or the layer-stacked [L, n_pages, Hkv,
    page, D] pool, with ``layer`` picking the layer without slicing it)
    through per-stream ``block_table`` [B, n]
    with ``page_mask`` [B, n*page] marking the visible context tokens in
    table order (ring residency + fidelity window + sparsity + page-tail
    validity + partial-window page drops baked in by the caller — all
    per-row, so one fused launch serves heterogeneous fidelities, and a
    degraded stream's dropped ring page (hole remapped to its sink row,
    mask slice false) is simply never attended), and (b) the chunk's own fresh KV
    ``chunk_k``/``chunk_v`` [B,Sq,Hkv,D] (bidirectional, fully visible).

    The paged segment contributes online-softmax partials — the
    ``kernels/paged_attention`` chunk-query kernel on TPU, its pure-jnp
    oracle elsewhere — which are merged with a dense in-chunk segment
    before the softmax divide.  No contiguous [B, ctx_len, ...] context
    is ever materialized.  ``sink``/``chunk_tokens`` (optional) declare
    the valid prefixes of the sink/ring pages so the oracle can skip
    always-masked page tails.
    """
    # late import: the kernel package's ref oracle imports this module
    from repro.kernels.paged_attention.ops import paged_chunk_attention
    b, sq, hq, d = q.shape
    scale = 1.0 / math.sqrt(d)
    # device scopes of the AR-DiT step (``ardit._chunk_forward_pages``)
    with jax.named_scope("ardit/paged_attn"):
        ctx = paged_chunk_attention(q, k_pages, v_pages, block_table,
                                    page_mask, layer, sink=sink,
                                    chunk_tokens=chunk_tokens)
    with jax.named_scope("ardit/segment_attn"):
        own = _segment_attn(_group(q, n_kv_heads), chunk_k, chunk_v, None,
                            scale)
        out = _finalize(_merge(ctx, own), q.dtype)
        return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, d)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, *,
                     n_kv_heads: int, cache_len: jax.Array,
                     window: int = 0, sink: int = 0) -> jax.Array:
    """Single-token decode over a KV cache.

    q: [B,1,Hq,D]; caches: [B,Smax,Hkv,D]; ``cache_len``: [B] or scalar int32
    count of valid cache entries (the new token's KV must already be written).
    """
    b, sq, hq, d = q.shape
    smax = k_cache.shape[1]
    scale = 1.0 / math.sqrt(d)
    qg = _group(q, n_kv_heads)
    k_pos = jnp.arange(smax)
    valid = k_pos[None, :] < jnp.reshape(cache_len, (-1, 1))     # [B,S]
    if window:
        last = jnp.reshape(cache_len, (-1, 1)) - 1
        valid &= (k_pos[None, :] > last - window) | (k_pos[None, :] < sink)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    s = jnp.where(valid[:, None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", p, v_cache.astype(jnp.float32))
    return o.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, d).astype(q.dtype)
