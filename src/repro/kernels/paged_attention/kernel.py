"""Pallas TPU paged attention (State-Plane paged KV, SS4.4).

The State Plane stores KV at latent-frame granularity in a physical page
pool; attention must cover a logically-contiguous sequence scattered
across pages.  The block table is scalar-prefetched so the page index_map
performs the indirection *before* the DMA — the TPU analogue of gather-
from-page-table on GPU.

Two entry points:

* ``paged_decode_attention_pallas`` — single-token decode
  (q [B,Hq,D], per-stream valid ``lengths``; token-major pages
  [P, page, Hkv, D]), finalized output.  Grid: (batch, kv_head, page).
* ``paged_chunk_attention_pallas`` — chunk queries for the batched
  serving executor's ``paged`` context backend (q [B,Sq,Hq,D],
  per-stream token-granular visibility ``page_mask``) over the
  HEAD-MAJOR serving pool [L, P, Hkv, page, D], read in place.  Grid:
  (batch, kv_head, query tile, context tile), sized for scoped VMEM at
  published widths.  Returns ONLINE-SOFTMAX PARTIALS (m, l,
  unnormalized acc) so the caller can merge the paged-context segment
  with the chunk's own fresh KV (``models.attention.paged_mha``) — the
  pool is never gathered into a contiguous context.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(bt_ref, len_ref,                  # scalar prefetch
            q_ref, k_ref, v_ref,              # VMEM
            o_ref,
            m_scr, l_scr, acc_scr,
            *, scale: float, page_size: int):
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]

    @pl.when(i * page_size < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [G, D]
        k = k_ref[0, :, 0].astype(jnp.float32)         # [page, D]
        v = v_ref[0, :, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(i == pl.num_programs(2) - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(q: jax.Array, k_pages: jax.Array,
                                  v_pages: jax.Array,
                                  block_table: jax.Array,
                                  lengths: jax.Array, *,
                                  interpret: bool = False) -> jax.Array:
    """q [B,Hq,D]; pages [P_total, page, Hkv, D]; block_table [B, n];
    lengths [B].  Returns [B,Hq,D]."""
    b, hq, d = q.shape
    _, page, hkv, _ = k_pages.shape
    n_pages = block_table.shape[1]
    assert hq % hkv == 0
    group = hq // hkv
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, hkv, group, d)

    kernel = functools.partial(_kernel, scale=scale, page_size=page)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, group, d),
                         lambda b_, h, i, bt, ln: (b_, h, 0, 0)),
            pl.BlockSpec((1, page, 1, d),
                         lambda b_, h, i, bt, ln: (bt[b_, i], 0, h, 0)),
            pl.BlockSpec((1, page, 1, d),
                         lambda b_, h, i, bt, ln: (bt[b_, i], 0, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, d),
                               lambda b_, h, i, bt, ln: (b_, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group,), jnp.float32),
            pltpu.VMEM((group,), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_table, lengths, qg, k_pages, v_pages)
    return out.reshape(b, hq, d)


# Chunk-query tiles.  Query rows and context tokens per grid step are
# multiples of the TPU tile — 16 rows (a bf16 sublane tile), 128 tokens
# (one lane row) — capped so one step's fp32 score tile [tq, tk] and its
# softmax temporaries stay well inside scoped VMEM.
ROW_ALIGN = 16
MAX_BLOCK_Q = 1024
MAX_BLOCK_K = 1024


def query_tile(r: int) -> tuple:
    """(tq, r_pad): the fewest ``ROW_ALIGN``-multiple row tiles of at
    most ``MAX_BLOCK_Q`` rows that cover ``r`` query rows; the rows past
    ``r`` are zero padding, sliced off the partials."""
    n = -(-r // MAX_BLOCK_Q)
    tq = -(-r // n)
    tq = -(-tq // ROW_ALIGN) * ROW_ALIGN
    return tq, n * tq


def kv_tile(page: int) -> int:
    """Context tokens per grid step: the largest 128-multiple divisor of
    ``page`` up to ``MAX_BLOCK_K``, or the whole page when ``page`` is
    not 128-aligned (a full-extent block is always a legal block shape;
    the serving pool rounds its pages to 128 tokens:
    ``ardit.page_tokens``)."""
    if page % 128:
        return page
    return max(t for t in range(128, min(page, MAX_BLOCK_K) + 1, 128)
               if page % t == 0)


def _chunk_kernel(bt_ref, live_ref, layer_ref,        # scalar prefetch
                  q_ref, k_ref, v_ref, *rest,
                  scale: float, kv_tiles: int, sink: int,
                  chunk_tokens: int, masked: bool):
    """One (stream, kv head, query tile, context tile) step.  The
    partial outputs (m, l, acc) keep the same block index across the
    innermost context axis, so they stay in VMEM as the online-softmax
    accumulators.  Context tiles with no visible token (``live_ref``)
    are skipped: they would contribute m=NEG_INF, l+=0, acc+=0."""
    if masked:
        mask_ref, m_ref, l_ref, acc_ref = rest
    else:
        m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(3)
    tk = k_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live_ref[b, j] > 0)
    def _compute():
        q = q_ref[...]                                 # [tq, D]
        # bf16 (or fp8-upcast) operands feed the MXU with fp32
        # accumulation: each product is exact in fp32, as in the oracle
        k = k_ref[...].astype(q.dtype)                 # [tk, D]
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            vis = mask_ref[...] > 0                    # [1, tk]
        else:
            # all-visible fast path: each page's static valid prefix
            limit = jnp.where(j // kv_tiles == 0, sink, chunk_tokens)
            pos = (j % kv_tiles) * tk + jax.lax.broadcasted_iota(
                jnp.int32, (1, tk), 1)
            vis = pos < limit
        s = jnp.where(vis, s, NEG_INF)
        m_prev = m_ref[...]                            # [tq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # exp(NEG_INF - NEG_INF) == 1 on an all-masked row: zero those
        # probabilities explicitly so l is not polluted
        p = jnp.where(vis, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new


@functools.partial(jax.jit,
                   static_argnames=("interpret", "sink", "chunk_tokens"))
def paged_chunk_attention_pallas(q: jax.Array, k_pages: jax.Array,
                                 v_pages: jax.Array,
                                 block_table: jax.Array,
                                 page_mask, layer=None, *,
                                 sink: int = 0, chunk_tokens: int = 0,
                                 interpret: bool = False):
    """q [B,Sq,Hq,D]; pages HEAD-MAJOR [P_total, Hkv, page, D], or the
    whole layer-stacked pool [L, P_total, Hkv, page, D] with ``layer``
    (int32 scalar) picking the layer IN PLACE — the pool is never sliced
    or copied; block_table [B, n]; page_mask [B, n*page] bool (visible
    tokens in table order), or None for the all-visible fast path
    (``sink``/``chunk_tokens`` then give each page's static valid
    prefix).

    Grid: (stream, kv head, query tile, context tile) with the context
    tiles of all table pages on the innermost axis; K/V blocks are
    [tk, D] slabs of one (page, head), so every block is (8,128)-legal
    (tiles: ``query_tile``, ``kv_tile``).

    Returns fp32 online-softmax partials in the ``attention._merge``
    layout: m, l [B, Hkv, G, Sq]; acc [B, Hkv, G, Sq, D] unnormalized.
    """
    if k_pages.ndim == 4:
        k_pages, v_pages = k_pages[None], v_pages[None]
        layer = 0
    b, sq, hq, d = q.shape
    _, _, hkv, page, _ = k_pages.shape
    n = block_table.shape[1]
    assert hq % hkv == 0
    group = hq // hkv
    r = sq * group                      # query rows per (batch, kv head)
    tq, r_pad = query_tile(r)
    tk = kv_tile(page)
    kt = page // tk                     # context tiles per page
    scale = 1.0 / math.sqrt(d)
    qr = q.reshape(b, sq, hkv, group, d).transpose(0, 2, 1, 3, 4) \
          .reshape(b, hkv, r, d)
    if r_pad > r:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, r_pad - r), (0, 0)))
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))

    def kv_map(b_, h, i, j, bt, live, li):
        return (li[0], bt[b_, j // kt], h, j % kt, 0)

    in_specs = [
        pl.BlockSpec((None, None, tq, d),
                     lambda b_, h, i, j, *_: (b_, h, i, 0)),
        pl.BlockSpec((None, None, None, tk, d), kv_map),
        pl.BlockSpec((None, None, None, tk, d), kv_map),
    ]
    if page_mask is None:
        assert sink and chunk_tokens, \
            "page_mask=None needs the sink/chunk_tokens layout hint"
        tile_lo = (np.arange(n * kt) % kt) * tk
        limit = np.where(np.arange(n * kt) < kt, sink, chunk_tokens)
        live = jnp.broadcast_to(
            jnp.asarray((tile_lo < limit).astype(np.int32)), (b, n * kt))
        inputs = (qr, k_pages, v_pages)
    else:
        mask_i = page_mask.reshape(b, n * kt, 1, tk).astype(jnp.int32)
        live = jnp.max(mask_i, axis=(2, 3))
        in_specs.append(pl.BlockSpec(
            (None, None, 1, tk), lambda b_, h, i, j, *_: (b_, j, 0, 0)))
        inputs = (qr, k_pages, v_pages, mask_i)
    kernel = functools.partial(_chunk_kernel, scale=scale, kv_tiles=kt,
                               sink=sink, chunk_tokens=chunk_tokens,
                               masked=page_mask is not None)

    out_map = lambda b_, h, i, j, *_: (b_, h, i, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv, r_pad // tq, n * kt),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((None, None, tq, 1), out_map),
                   pl.BlockSpec((None, None, tq, 1), out_map),
                   pl.BlockSpec((None, None, tq, d), out_map)],
    )
    m, l, acc = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, hkv, r_pad, 1), jnp.float32),
                   jax.ShapeDtypeStruct((b, hkv, r_pad, 1), jnp.float32),
                   jax.ShapeDtypeStruct((b, hkv, r_pad, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(block_table, live, layer, *inputs)
    m = m[:, :, :r, 0].reshape(b, hkv, sq, group).transpose(0, 1, 3, 2)
    l = l[:, :, :r, 0].reshape(b, hkv, sq, group).transpose(0, 1, 3, 2)
    acc = acc[:, :, :r].reshape(b, hkv, sq, group, d) \
        .transpose(0, 1, 3, 2, 4)
    return m, l, acc
