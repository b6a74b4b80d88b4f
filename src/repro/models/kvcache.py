"""Sink + ring-buffer KV cache helpers (paper SS2.1 "sink+local").

Layout: slots [0, sink) hold the attention sink; slots [sink, cap) are a
ring over the sliding window.  When ``cap >= seq_len`` the ring degenerates
to a plain linear cache (dest == pos), so the same code serves both the
full-cache and the windowed-adaptation paths.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def capacity(seq_len: int, window: int, sink: int) -> int:
    """Cache capacity in tokens for a stream of ``seq_len``."""
    if window:
        return min(seq_len, sink + window)
    return seq_len


def ring_dest(pos: jax.Array, cap: int, sink: int) -> jax.Array:
    """Write slot for absolute position ``pos`` (per-batch array ok)."""
    ring = cap - sink
    wrapped = sink + (pos - sink) % jnp.maximum(ring, 1)
    return jnp.where(pos < cap, jnp.minimum(pos, cap - 1),
                     wrapped).astype(jnp.int32)


def write_token(cache: jax.Array, new: jax.Array,
                dest: jax.Array) -> jax.Array:
    """cache [B,cap,H,D]; new [B,1,H,D]; dest [B] -> updated cache."""
    return jax.vmap(lambda cb, nb, db: jax.lax.dynamic_update_slice(
        cb, nb.astype(cb.dtype), (db, 0, 0)))(cache, new, dest)


def n_valid(pos: jax.Array, cap: int) -> jax.Array:
    """Number of resident (valid) cache entries after writing ``pos``."""
    return jnp.minimum(pos + 1, cap)


def chunk_slot(chunk_idx: jax.Array, window_chunks: int, sink: int,
               chunk_tokens: int) -> jax.Array:
    """First-token slot of absolute chunk ``chunk_idx`` in the
    chunk-granular ring: slots [0, sink) hold the attention sink and the
    ring holds ``window_chunks`` chunks of ``chunk_tokens`` each.
    ``chunk_idx`` may be a per-stream batch array."""
    return (sink + (chunk_idx % window_chunks) * chunk_tokens).astype(
        jnp.int32)


def write_block(cache: jax.Array, new: jax.Array,
                dest: jax.Array) -> jax.Array:
    """cache [B,cap,...]; new [B,T,...]; dest [B] first-token slot.

    Block-granular sibling of ``write_token``: writes a contiguous
    T-token block per batch row at a per-row slot (the batched serving
    executor appends one chunk's KV per stream this way)."""
    return jax.vmap(lambda cb, nb, db: jax.lax.dynamic_update_slice(
        cb, nb.astype(cb.dtype),
        (db,) + (0,) * (cb.ndim - 1)))(cache, new, dest)


@jax.jit
def write_block_layers(cache: jax.Array, new: jax.Array,
                       dest: jax.Array) -> jax.Array:
    """``write_block`` lifted over a leading layer axis, jitted (eager
    vmap re-traces per call, which dominates append cost on CPU).

    cache [L,B,cap,...]; new [L,B,T,...]; dest [B]."""
    return jax.vmap(write_block, in_axes=(0, 0, None))(cache, new, dest)


# ---------------------------------------------------------------------------
# page-granular pool (serve/batcher.py KVPool): KV lives HEAD-MAJOR as
# [L, n_pages, Hkv, page_tokens, Dh] — each (page, head) is one
# contiguous [page_tokens, Dh] slab, which is the block the TPU paged
# kernel DMAs — and each stream owns a page *table* (entry 0 = cond sink
# page, entry 1+r = ring slot r, chunk c in entry 1 + c % window_chunks).
# The model produces KV token-major [L, b, T, Hkv, Dh]; ``to_pages``
# turns a chunk into page layout once, at append time.  The helpers
# below are pure permutations of pool rows, so a page-table cache is
# bitwise-identical to the stacked per-stream chunk-ring layout it
# replaces.
# ---------------------------------------------------------------------------


def pages_per_stream(window_chunks: int) -> int:
    """Pages a resident stream owns: one cond sink page + the ring."""
    return 1 + window_chunks


def page_of_chunk(chunk_idx: int, window_chunks: int) -> int:
    """Page-table entry holding absolute chunk ``chunk_idx`` (the ring
    slot of ``chunk_slot`` shifted past the sink entry)."""
    return 1 + chunk_idx % window_chunks


def to_pages(kv: jax.Array) -> jax.Array:
    """Token-major model KV [L, b, T, Hkv, Dh] -> page layout
    [L, b, Hkv, T, Dh] (what ``pool_write_pages`` takes)."""
    return jnp.swapaxes(kv, 2, 3)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def gather_pages(pool: jax.Array, tables: jax.Array, sink: int,
                 chunk_tokens: int, n_ring: int) -> jax.Array:
    """pool [L,n_pages,Hkv,P,Dh]; tables [b, 1+W] page ids ->
    token-major [L, b, sink + n_ring*chunk_tokens, Hkv, Dh].

    Reassembles, per stream, the contiguous sink+ring context the
    stacked chunk-ring layout kept per row: tokens [0, sink) from the
    sink page (table entry 0), ring slot r at
    [sink + r*chunk_tokens, sink + (r+1)*chunk_tokens) from table entry
    1+r, sliced to the first ``n_ring`` ring slots (the sub-batch's
    resident extent).  A pure gather: bitwise-exact."""
    l, b = pool.shape[0], tables.shape[0]
    hkv, d = pool.shape[2], pool.shape[4]
    sink_part = jnp.swapaxes(pool[:, tables[:, 0], :, :sink], 2, 3)
    if n_ring == 0:
        return sink_part
    ring = pool[:, tables[:, 1:1 + n_ring], :, :chunk_tokens]
    ring = ring.transpose(0, 1, 2, 4, 3, 5).reshape(
        l, b, n_ring * chunk_tokens, hkv, d)
    return jnp.concatenate([sink_part, ring], axis=2)


def mask_to_pages(mask: np.ndarray, n_ring: int, sink: int,
                  chunk_tokens: int, page_tokens: int) -> np.ndarray:
    """Contiguous sink+ring visibility mask [B, >= sink + n_ring*tc] ->
    page-coordinate mask [B, (1+n_ring)*page_tokens] in TABLE order
    (entry 0 = sink page, entry 1+r = ring slot r) for the paged
    attention path.  Pages are ``page_tokens`` wide but only partially
    valid — ``sink`` tokens on the sink page, ``chunk_tokens`` on ring
    pages — so page tails come out False regardless of the input mask.
    """
    b = mask.shape[0]
    out = np.zeros((b, (1 + n_ring) * page_tokens), bool)
    out[:, :sink] = mask[:, :sink]
    for r in range(n_ring):
        lo = (1 + r) * page_tokens
        out[:, lo:lo + chunk_tokens] = \
            mask[:, sink + r * chunk_tokens:sink + (r + 1) * chunk_tokens]
    return out


@functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(3,))
def pool_write_pages(pool: jax.Array, new: jax.Array,
                     pages: jax.Array, head_offset: int = 0) -> jax.Array:
    """pool [L,n_pages,Hkv,P,Dh]; new [L,b,h,T,Dh] in page layout
    (T <= P, h <= Hkv - head_offset); pages [b].

    Writes one T-token block per stream at token 0 of its destination
    page, KV heads [head_offset, head_offset + h) — the page-granular
    sibling of ``write_block``.  ``head_offset`` > 0 serves the
    elastic-SP donor pool, which holds only the upper half of a
    stream's KV heads (Ulysses head partition, paper App. C.4), so its
    appends touch only that half.  The pool buffer is donated so the
    update happens in place where the backend supports it.
    Device-backed pools rely on this donation staying device-local:
    ``new`` blocks arriving from another lane (migration landings, SP
    shipbacks) are ``device_put`` onto the pool's device by the caller
    BEFORE this jit, so the write never silently pins the donated pool
    to a foreign device."""
    for i in range(new.shape[1]):
        pool = jax.lax.dynamic_update_slice(
            pool, new[:, i:i + 1].astype(pool.dtype),
            (0, pages[i], head_offset, 0, 0))
    return pool


def place_prefill(k: jax.Array, cap: int, sink: int,
                  window: int) -> jax.Array:
    """[B,S,H,D] -> [B,cap,H,D]: full copy if it fits, else sink+ring gather.

    Ring slot r holds the LAST token t < S with (t - sink) % ring == r.
    Gather (not scatter) so duplicate ring slots resolve deterministically.
    """
    b, s = k.shape[:2]
    if cap >= s:
        return jnp.pad(k, ((0, 0), (0, cap - s)) + ((0, 0),) * (k.ndim - 2))
    assert window > 0, (
        f"cache capacity {cap} < sequence {s} without a sliding window "
        f"— caller must size max_len to the full prefill length")
    ring = cap - sink
    slots = jnp.arange(cap)
    r = slots - sink
    ring_tok = sink + r + ((s - 1 - sink - r) // ring) * ring
    tok_idx = jnp.where(slots < sink, slots, ring_tok)
    valid = (tok_idx >= 0) & (tok_idx < s)
    tok_idx = jnp.clip(tok_idx, 0, s - 1)
    out = k[:, tok_idx]
    shape = (1, cap) + (1,) * (k.ndim - 2)
    return out * valid.reshape(shape).astype(k.dtype)
