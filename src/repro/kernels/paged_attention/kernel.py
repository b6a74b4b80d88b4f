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
  (batch, kv_head, query tile, table page): one step per page, whose
  valid prefix the kernel walks in static context sub-tiles sized for
  scoped VMEM at published widths.  Returns ONLINE-SOFTMAX PARTIALS (m, l,
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


# Chunk-query tiles.  The context axis of the grid takes one table page
# per step, and the kernel walks the page's valid prefix in static
# sub-tiles.  Query rows per step are a multiple of 16 (a bf16 sublane
# tile) and at most MAX_BLOCK_Q.  A sub-tile is a multiple of one
# 128-token lane row and at most MAX_BLOCK_K tokens (on a v5e at 480p,
# 1,024- and 2,048-token sub-tiles ran from 3% faster to 7% slower than
# 512), fewer where its fp32 score temporaries (SCORE_TEMPS of [tq, tk]:
# the scores and the probabilities) would not fit beside the step's
# blocks in the scoped VMEM a kernel gets by default, 16 MiB on v5e.
ROW_ALIGN = 16
LANES = 128
MAX_BLOCK_Q = 1024
MAX_BLOCK_K = 512
SCOPED_VMEM = 16 * 2**20
SCORE_TEMPS = 2


def query_tile(r: int) -> tuple:
    """(tq, r_pad): the fewest ``ROW_ALIGN``-multiple row tiles of at
    most ``MAX_BLOCK_Q`` rows that cover ``r`` query rows; the rows past
    ``r`` are zero padding, sliced off the partials."""
    n = -(-r // MAX_BLOCK_Q)
    tq = -(-r // n)
    tq = -(-tq // ROW_ALIGN) * ROW_ALIGN
    return tq, n * tq


def context_tile(tq: int, page: int, d: int, q_bytes: int, kv_bytes: int,
                 masked: bool) -> int:
    """Context tokens per in-kernel sub-tile for ``tq`` query rows of
    head_dim ``d`` against ``page``-token pages: ``MAX_BLOCK_K``, halved
    down to one lane row while its fp32 score temporaries do not fit in
    ``SCOPED_VMEM`` beside the step's double-buffered q, K/V page,
    partials and mask blocks and the m/l scratch.  A page that is not
    128-aligned is one sub-tile (a full-extent slice is always legal)."""
    if page % LANES:
        return page
    dl = -(-d // LANES) * LANES                 # lanes a row of d spans
    blocks = (tq * dl * q_bytes                 # q
              + 2 * page * dl * kv_bytes        # K, V pages
              + tq * dl * 4                     # acc
              + 2 * tq * LANES * 4              # m, l: [tq, 1], lane-padded
              + (8 * page * 4 if masked else 0))    # [1, page] int32 mask
    fixed = 2 * blocks + 2 * tq * LANES * 4     # double-buffered; m, l scratch
    tk = MAX_BLOCK_K
    while tk > LANES and fixed + SCORE_TEMPS * tq * tk * 4 > SCOPED_VMEM:
        tk //= 2
    return min(tk, page)


def sub_tiles(extent: int, page: int, tk: int) -> tuple:
    """Static (lo, n, valid) slices that cover the first ``extent``
    tokens of a ``page``-token page: ``tk``-token sub-tiles, the last one
    rounded up to a lane row but never past the page; the tokens at
    ``valid`` and beyond lie past ``extent`` and are masked."""
    out, lo = [], 0
    while lo < extent:
        n = min(tk, -(-(extent - lo) // LANES) * LANES, page - lo)
        out.append((lo, n, min(n, extent - lo)))
        lo += n
    return tuple(out)


def _lanes(x: jax.Array, n: int) -> jax.Array:
    """A lane-dense [tq, 128] row statistic spread over (or cut to) the
    ``n`` columns of a [tq, n] tile."""
    if n % LANES == 0:
        return jnp.tile(x, (1, n // LANES))
    return x[:, :n] if n < LANES else x[:, :1]


def _chunk_kernel(*refs, scale: float, tiles: tuple, masked: bool):
    """One (stream, kv head, query tile, table page) step.  The page's
    valid prefix is walked in the static sub-tiles ``tiles[0]`` (the sink,
    table entry 0) or ``tiles[1]`` (every later entry).  The partial acc
    keeps the same block index across the innermost page axis, so it
    stays in VMEM as the online-softmax accumulator beside the lane-dense
    m, l scratch; m and l are written out once, at the last page.  On the
    masked path a sub-tile with no visible token (``live_ref``, per
    stream and sub-tile) is skipped: it would contribute m=NEG_INF, l+=0,
    acc+=0."""
    if masked:
        (_, _, live_ref, _, q_ref, k_ref, v_ref, mask_ref,
         m_ref, l_ref, acc_ref, m_scr, l_scr) = refs
    else:
        (_, _, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
         m_scr, l_scr) = refs
    b = pl.program_id(0)
    j = pl.program_id(3)
    ns = max(map(len, tiles))

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def sub_tile(lo, n, valid):
        q = q_ref[...]                                 # [tq, D]
        # bf16 (or fp8-upcast) operands feed the MXU with fp32
        # accumulation: each product is exact in fp32, as in the oracle
        k = k_ref[pl.ds(lo, n), :].astype(q.dtype)     # [n, D]
        v = v_ref[pl.ds(lo, n), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        vis = mask_ref[:, pl.ds(lo, n)] > 0 if masked else None  # [1, n]
        if valid < n:
            # the page's static valid prefix ends inside this sub-tile
            tail = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) < valid
            vis = tail if vis is None else vis & tail
        if vis is not None:
            s = jnp.where(vis, s, NEG_INF)
        m_prev = m_scr[...]                            # [tq, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, n))
        if vis is not None:
            # exp(NEG_INF - NEG_INF) == 1 on an all-masked row: zero
            # those probabilities explicitly so l is not polluted
            p = jnp.where(vis, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * _lanes(alpha, acc_ref.shape[-1])
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    def run(t, lo, n, valid):
        body = functools.partial(sub_tile, lo, n, valid)
        if masked:
            pl.when(live_ref[b, j * ns + t] > 0)(body)
        else:
            body()

    def walk(page_tiles):
        # the leading whole sub-tiles in a loop: unrolled, they would
        # multiply the Mosaic lowering every step program repeats (on a
        # v5e a 480p set-up took 11 s longer for 19% less kernel time on
        # all-visible bf16 pages); the cut last one static
        tk, full = page_tiles[0][1], 0
        while full < len(page_tiles) and page_tiles[full][1:] == (tk, tk):
            full += 1
        if full > 1:
            def loop_body(t, carry):
                run(t, pl.multiple_of(t * tk, tk), tk, tk)
                return carry
            jax.lax.fori_loop(0, full, loop_body, 0)
        else:
            full = 0
        for t in range(full, len(page_tiles)):
            run(t, *page_tiles[t])

    if tiles[0] == tiles[1]:
        walk(tiles[1])
    else:
        pl.when(j == 0)(functools.partial(walk, tiles[0]))
        pl.when(j > 0)(functools.partial(walk, tiles[1]))

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        m_ref[...] = m_scr[:, :1]
        l_ref[...] = l_scr[:, :1]


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
    tokens in table order), or None for the all-visible fast path.
    ``sink``/``chunk_tokens`` give each page's static valid prefix (table
    entry 0 holds at most ``sink`` valid tokens, every later entry at
    most ``chunk_tokens``); the kernel never reads past it.

    Grid: (stream, kv head, query tile, table page).  K/V blocks are
    whole [page, D] slabs of one (page, head), a full-extent block legal
    for any page size and K/V dtype; inside a step the kernel walks the
    page's valid prefix in static sub-tiles (``query_tile``,
    ``context_tile``, ``sub_tiles``).  On the masked path a page with no
    visible token re-points its K/V and mask blocks at the last live
    page's, so the pipeline fetches nothing for it.

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
    masked = page_mask is not None
    if sink and chunk_tokens:
        extents = (min(sink, page), min(chunk_tokens, page))
    else:
        assert masked, "page_mask=None needs the sink/chunk_tokens layout hint"
        extents = (page, page)
    tk = context_tile(tq, page, d, q.dtype.itemsize,
                      k_pages.dtype.itemsize, masked)
    tiles = tuple(sub_tiles(e, page, tk) for e in extents)
    scale = 1.0 / math.sqrt(d)
    qr = q.reshape(b, sq, hkv, group, d).transpose(0, 2, 1, 3, 4) \
          .reshape(b, hkv, r, d)
    if r_pad > r:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, r_pad - r), (0, 0)))
    scalars = (block_table, jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)))
    inputs = (qr, k_pages, v_pages)

    if masked:
        # live sub-tiles: any visible token inside the page's valid
        # prefix, per (stream, page, sub-tile); sub-tile t of either
        # layout starts at t * tk
        ns = max(map(len, tiles))
        width = ns * tk
        valid = np.zeros((n, page), bool)
        valid[0, :extents[0]] = True
        valid[1:, :extents[1]] = True
        vis = (page_mask.reshape(b, n, page) & valid)[..., :width]
        vis = jnp.pad(vis, ((0, 0), (0, 0), (0, width - vis.shape[-1])))
        live = jnp.any(vis.reshape(b, n, ns, -1), axis=-1)      # [b, n, ns]
        # a dead page reads the blocks of the last live one before it
        entry = np.arange(n, dtype=np.int32)
        src = jnp.max(jnp.where(live.any(axis=-1)[:, None, :]
                                & (entry[None, :] <= entry[:, None]),
                                entry, 0), axis=-1)                 # [b, n]
        scalars += (live.reshape(b, n * ns).astype(jnp.int32), src)
        inputs += (page_mask.reshape(b, n, 1, page).astype(jnp.int32),)

    def kv_map(b_, h, i, j, bt, li, *live_src):
        e = live_src[1][b_, j] if masked else j
        return (li[0], bt[b_, e], h, 0, 0)

    in_specs = [
        pl.BlockSpec((None, None, tq, d),
                     lambda b_, h, i, j, *_: (b_, h, i, 0)),
        pl.BlockSpec((None, None, None, page, d), kv_map),
        pl.BlockSpec((None, None, None, page, d), kv_map),
    ]
    if masked:
        in_specs.append(pl.BlockSpec(
            (None, None, 1, page),
            lambda b_, h, i, j, bt, li, live, src: (b_, src[b_, j], 0, 0)))
    kernel = functools.partial(_chunk_kernel, scale=scale, tiles=tiles,
                               masked=masked)

    out_map = lambda b_, h, i, j, *_: (b_, h, i, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, hkv, r_pad // tq, n),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((None, None, tq, 1), out_map),
                   pl.BlockSpec((None, None, tq, 1), out_map),
                   pl.BlockSpec((None, None, tq, d), out_map)],
        scratch_shapes=[pltpu.VMEM((tq, LANES), jnp.float32),
                        pltpu.VMEM((tq, LANES), jnp.float32)],
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
    )(*scalars, *inputs)
    m = m[:, :, :r, 0].reshape(b, hkv, sq, group).transpose(0, 1, 3, 2)
    l = l[:, :, :r, 0].reshape(b, hkv, sq, group).transpose(0, 1, 3, 2)
    acc = acc[:, :, :r].reshape(b, hkv, sq, group, d) \
        .transpose(0, 1, 3, 2, 4)
    return m, l, acc
