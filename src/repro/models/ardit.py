"""AR-DiT: chunk-wise autoregressive video diffusion transformer.

The paper's model family (Self-Forcing / Causal-Forcing style): video is
generated one *chunk* (``chunk_frames`` latent frames = ``chunk_tokens``
tokens) at a time.  Each chunk is denoised over ``S`` steps; within-chunk
attention is bidirectional, and every token also attends to the rolling
KV cache of previous chunks (sink + local window, SS2.1).  Conditioning
embeddings occupy the sink slot, so the sink doubles as the prompt context.

All four fidelity knobs are live here (SS5 / App. A):
    S    denoise steps       -> fewer model evaluations
    rho  attention sparsity  -> static strided drop of cached KV blocks
    W    KV window (chunks)  -> shorter visible cache slice
    Q    quantization        -> fp8 KV cache
``serve_chunk`` is the unit of work the serving system schedules.  Cache
bookkeeping (len/chunks) is host-side Python — the serving executor jits
only ``chunk_forward``; shapes are static per (fill, fidelity) state, of
which there are at most ``window_chunks + 1``.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.logical import shard
from repro.models import kvcache
from repro.models import layers as L
from repro.models.attention import (merge_head_shards, mha, paged_mha,
                                    shard_heads, sparse_keep_list)

Params = Dict[str, Any]

LATENT_CH = 16          # latent channels out of the (stubbed) video VAE
COND_TOKENS = 77        # text-conditioning tokens (stub encoder output)


class FidelityConfig(NamedTuple):
    """A concrete assignment of the paper's four fidelity knobs (SS5),
    plus the repo's fifth knob: the AdaCache-style step cache
    (``models/stepcache.py``), reusing a cached velocity when the
    inter-step residual delta is stable."""
    steps: int = 4              # S in {2,3,4}
    sparsity: float = 0.0       # rho in {0,.6,.7,.8,.9}
    window: int = 7             # W in {1,3,7} chunks
    quant: str = "bf16"         # Q in {bf16,fp8}
    cache: str = "off"          # step cache in {off,conservative,aggressive}

    @property
    def key(self) -> str:
        # cache=off keys are unchanged from the 4-knob era so existing
        # EMAs, calibration ratios, and parity baselines stay valid
        base = f"S{self.steps}_r{self.sparsity}_W{self.window}_{self.quant}"
        return base if self.cache == "off" else f"{base}_c{self.cache[0]}"


HIGHEST_QUALITY = FidelityConfig(4, 0.0, 7, "bf16")


def chunk_tokens(cfg: ModelConfig) -> int:
    return cfg.ardit_chunk_frames * cfg.ardit_frame_tokens


# page sizes are rounded up to whole 128-token lane rows, so the TPU
# paged kernel tiles a page's context into (8,128)-legal blocks
PAGE_ALIGN = 128


def page_tokens(cfg: ModelConfig) -> int:
    """Tokens per KV-pool page: room for the cond sink or one chunk,
    rounded up to ``PAGE_ALIGN`` (the tail is never valid)."""
    t = max(COND_TOKENS, chunk_tokens(cfg))
    return -(-t // PAGE_ALIGN) * PAGE_ALIGN


def cache_capacity(cfg: ModelConfig) -> int:
    """KV capacity in tokens: cond sink + window chunks."""
    return COND_TOKENS + cfg.ardit_window_chunks * chunk_tokens(cfg)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(cfg: ModelConfig, key, dtype, open_gates: bool) -> Params:
    ks = L.split_keys(key, 3)
    d = cfg.d_model
    return {
        "attn": L.init_attn(cfg, ks[0], dtype),
        "mlp": L.init_mlp(cfg, ks[1], dtype),
        # adaLN(-zero): 6 modulation vectors per layer
        "mod": (L.dense_init(ks[2], (d, 6 * d), dtype) if open_gates
                else jnp.zeros((d, 6 * d), dtype)),
        "mod_b": jnp.zeros((6 * d,), dtype),
    }


def init_params(cfg: ModelConfig, key, open_gates: bool = False) -> Params:
    """Random weights.  adaLN-zero by default (the DiT training init: every
    residual branch starts gated off).  ``open_gates=True`` draws the
    modulation projections like every other dense layer instead, so the
    residual branches — attention over the KV context among them —
    shape the output: what a serving check on random weights needs, or
    any comparison of attention paths would hold vacuously."""
    dtype = jnp.dtype(cfg.param_dtype)
    ks = L.split_keys(key, 6)
    layer_keys = jax.random.split(ks[0], cfg.n_layers)
    d = cfg.d_model
    return {
        "in_proj": L.dense_init(ks[1], (LATENT_CH, d), dtype),
        "cond_proj": L.dense_init(ks[2], (d, d), dtype),
        "t_mlp1": L.dense_init(ks[3], (256, d), dtype),
        "t_mlp2": L.dense_init(ks[4], (d, d), dtype),
        "layers": jax.vmap(lambda k: _init_layer(cfg, k, dtype,
                                                 open_gates))(layer_keys),
        "final_norm": jnp.ones((d,), dtype),
        "final_mod": (L.dense_init(jax.random.fold_in(key, 6),
                                   (d, 2 * d), dtype) if open_gates
                      else jnp.zeros((d, 2 * d), dtype)),
        "out_proj": L.dense_init(ks[5], (d, LATENT_CH), dtype, scale=0.02),
    }


def _time_embed(p: Params, t: jax.Array, d: int) -> jax.Array:
    """t [B] in [0,1] -> [B, D] conditioning vector."""
    half = 128
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    ang = t.astype(jnp.float32)[:, None] * freqs[None, :] * 1000.0
    emb = jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)  # [B,256]
    h = jax.nn.silu(emb.astype(p["t_mlp1"].dtype) @ p["t_mlp1"])
    return h @ p["t_mlp2"]


def _modulate(x, shift, scale):
    return x * (1.0 + scale[:, None, :]) + shift[:, None, :]


def cache_sparse_index(cfg: ModelConfig, ctx_len: int,
                       sparsity: float) -> Optional[np.ndarray]:
    """Static token indices of the cached context kept under knob rho.

    Sink (cond) tokens and the most recent chunk are always kept; a strided
    ~(1-rho) fraction of the middle blocks survives (SS5, Light-Forcing
    style block sparsity, 128-aligned for the TPU kernel).
    """
    if sparsity <= 0.0 or ctx_len <= COND_TOKENS:
        return None
    blk = 128
    body = ctx_len - COND_TOKENS
    n_blocks = max(1, body // blk)
    keep = sparse_keep_list(1, [n_blocks], sparsity, sink_blocks=1)[0]
    idx = [np.arange(COND_TOKENS)]
    for j in keep:
        lo = COND_TOKENS + j * blk
        hi = min(COND_TOKENS + (j + 1) * blk, ctx_len)
        idx.append(np.arange(lo, hi))
    tail = COND_TOKENS + n_blocks * blk
    if tail < ctx_len:
        idx.append(np.arange(tail, ctx_len))
    return np.unique(np.concatenate(idx))


# ---------------------------------------------------------------------------
# core forward: one chunk conditioned on visible context KV
# ---------------------------------------------------------------------------

def chunk_forward(cfg: ModelConfig, p: Params, x_chunk: jax.Array,
                  t: jax.Array, ctx_k: Optional[jax.Array],
                  ctx_v: Optional[jax.Array], *, q_offset,
                  sparsity: float = 0.0,
                  ctx_mask: Optional[jax.Array] = None,
                  ) -> Tuple[jax.Array, Dict[str, Any]]:
    """One DiT pass over a chunk.

    x_chunk [B, T_c, LATENT_CH]; t [B] denoise time; ctx_k/v
    [L, B, ctx_len, Hkv, Dh] visible context (or None).  Returns
    (prediction [B, T_c, LATENT_CH], {"k","v"} per-layer chunk KV).

    ``q_offset`` is either a host int (all streams at the same absolute
    position) or a per-stream [B] array (the batched executor's stacked
    streams sit at different chunk indices).  ``ctx_mask`` [B, ctx_len]
    marks the context tokens each stream may attend to (ring-cache
    residency + fidelity window + sparsity baked in by the caller);
    when given, the static ``sparsity`` gather is skipped.
    """
    b, tc, _ = x_chunk.shape
    d = cfg.d_model
    h = shard(x_chunk.astype(p["in_proj"].dtype) @ p["in_proj"],
              "batch", None, "embed")
    temb = _time_embed(p, t, d)                                   # [B,D]
    q_off = jnp.asarray(q_offset)
    if q_off.ndim:                                  # per-stream offsets
        positions = q_off[:, None] + jnp.arange(tc)[None, :]      # [B,Tc]
    else:
        positions = q_off + jnp.arange(tc)                        # [Tc]
    ones = jnp.ones((d,), h.dtype)

    keep_idx = None
    kv_mask = None
    if ctx_k is not None:
        if ctx_mask is not None:
            kv_mask = jnp.concatenate(
                [ctx_mask, jnp.ones((b, tc), bool)], axis=1)
        else:
            keep_idx = cache_sparse_index(cfg, ctx_k.shape[2], sparsity)

    def body(hh, xs):
        lp = xs["layer"]
        mod = jax.nn.silu(temb) @ lp["mod"] + lp["mod_b"]         # [B,6D]
        sh1, sc1, g1, sh2, sc2, g2 = jnp.split(mod, 6, axis=-1)
        a_in = _modulate(L.rmsnorm(hh, ones, cfg.norm_eps), sh1, sc1)
        q, k, v = L.attn_qkv(cfg, lp["attn"], a_in, positions)
        if ctx_k is not None:
            kc, vc = xs["ck"], xs["cv"]
            if keep_idx is not None:
                kc, vc = kc[:, keep_idx], vc[:, keep_idx]
            k_all = jnp.concatenate([kc.astype(k.dtype), k], axis=1)
            v_all = jnp.concatenate([vc.astype(v.dtype), v], axis=1)
        else:
            k_all, v_all = k, v
        o = mha(q, k_all, v_all, n_kv_heads=cfg.n_kv_heads, causal=False,
                kv_mask=kv_mask)
        o = o.reshape(b, tc, cfg.n_heads * cfg.head_dim)
        hh = hh + g1[:, None, :] * shard(o @ lp["attn"]["wo"],
                                         "batch", None, "embed")
        f_in = _modulate(L.rmsnorm(hh, ones, cfg.norm_eps), sh2, sc2)
        hh = hh + g2[:, None, :] * L.mlp_block(cfg, lp["mlp"], f_in)
        return hh, {"k": k, "v": v}

    xs = {"layer": p["layers"]}
    if ctx_k is not None:
        xs["ck"] = ctx_k
        xs["cv"] = ctx_v
    h, new_kv = jax.lax.scan(body, h, xs)

    mod = jax.nn.silu(temb) @ p["final_mod"]
    sh, sc = jnp.split(mod, 2, axis=-1)
    h = _modulate(L.rmsnorm(h, p["final_norm"], cfg.norm_eps), sh, sc)
    return h @ p["out_proj"], new_kv


# ---------------------------------------------------------------------------
# serving: host-side cache bookkeeping + chunk generation
# ---------------------------------------------------------------------------

def cond_kv(cfg: ModelConfig, p: Params, cond: jax.Array,
            kv_dtype: Optional[str] = None) -> Tuple[jax.Array, jax.Array]:
    """The sink KV of the conditioning tokens, every layer:
    cond [B, COND_TOKENS, d_model] -> k, v [L, B, COND_TOKENS, Hkv, Dh]."""
    dt = jnp.dtype(kv_dtype or cfg.kv_dtype)
    cond = cond.astype(p["cond_proj"].dtype) @ p["cond_proj"]
    positions = jnp.arange(COND_TOKENS)

    def kv_of(lp):
        _, k, v = L.attn_qkv(cfg, lp, cond, positions)
        return k, v

    ks, vs = jax.vmap(kv_of)(p["layers"]["attn"])
    return ks.astype(dt), vs.astype(dt)


def init_cache(cfg: ModelConfig, p: Params, cond: jax.Array,
               kv_dtype: Optional[str] = None) -> Dict[str, Any]:
    """Cache whose sink slot is the conditioning tokens.

    cond: [B, COND_TOKENS, d_model] (stub text-encoder output).
    ``len``/``chunks`` are host-side Python ints (static shapes per state).
    """
    ks, vs = cond_kv(cfg, p, cond, kv_dtype)
    return {"k": ks, "v": vs, "len": COND_TOKENS, "chunks": 0}


def visible_context(cfg: ModelConfig, cache: Dict[str, Any],
                    window: int) -> Tuple[jax.Array, jax.Array]:
    """Sink + last ``window`` chunks of the cache (knob W)."""
    tc = chunk_tokens(cfg)
    resident = (cache["len"] - COND_TOKENS) // tc
    w = min(window, resident)
    k, v = cache["k"], cache["v"]
    if w == resident:
        return k[:, :, :cache["len"]], v[:, :, :cache["len"]]
    lo = cache["len"] - w * tc
    return (jnp.concatenate([k[:, :, :COND_TOKENS], k[:, :, lo:cache["len"]]],
                            axis=2),
            jnp.concatenate([v[:, :, :COND_TOKENS], v[:, :, lo:cache["len"]]],
                            axis=2))


def append_chunk_kv(cfg: ModelConfig, cache: Dict[str, Any],
                    new_kv: Dict[str, jax.Array]) -> Dict[str, Any]:
    """Append a chunk's KV; evict the oldest non-sink chunk when full."""
    tc = chunk_tokens(cfg)
    cap = cache_capacity(cfg)
    k, v = cache["k"], cache["v"]
    ln, nch = cache["len"], cache["chunks"]
    nk = new_kv["k"].astype(k.dtype)    # [L,B,tc,H,Dh]
    nv = new_kv["v"].astype(v.dtype)
    if ln + tc <= cap:
        k = jnp.concatenate([k[:, :, :ln], nk], axis=2)
        v = jnp.concatenate([v[:, :, :ln], nv], axis=2)
        return {"k": k, "v": v, "len": ln + tc, "chunks": nch + 1}
    sink = COND_TOKENS
    k = jnp.concatenate([k[:, :, :sink], k[:, :, sink + tc:ln], nk], axis=2)
    v = jnp.concatenate([v[:, :, :sink], v[:, :, sink + tc:ln], nv], axis=2)
    return {"k": k, "v": v, "len": ln, "chunks": nch + 1}


def sigma_schedule(steps: int) -> np.ndarray:
    """Rectified-flow time grid 1 -> 0 (noise -> data)."""
    return np.linspace(1.0, 0.0, steps + 1)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("sparsity",))
def chunk_step(cfg: ModelConfig, p: Params, x: jax.Array, t: jax.Array,
               ctx_k: Optional[jax.Array], ctx_v: Optional[jax.Array],
               q_offset, ctx_mask: Optional[jax.Array],
               sparsity: float = 0.0):
    """Jitted one-denoise-step entry for the batched serving path (the
    sequential ``serve_chunk`` stays eager, as originally shipped).
    Shapes are static per (ctx extent, batch, sparsity), so a batched
    session compiles once per (sub-batch size, fill extent)."""
    return chunk_forward(cfg, p, x, t, ctx_k, ctx_v, q_offset=q_offset,
                         sparsity=sparsity, ctx_mask=ctx_mask)


@functools.partial(jax.jit, static_argnums=(0,))
def denoise_step(cfg: ModelConfig, p: Params, x: jax.Array, t: jax.Array,
                 dt: jax.Array, ctx_k: jax.Array, ctx_v: jax.Array,
                 q_offset: jax.Array, dn_mask: Optional[jax.Array],
                 cl_mask: Optional[jax.Array], is_denoise: jax.Array):
    """Fused batched executor step: forward + Euler update in ONE jitted
    call.  Rows in their denoise phase use the sparsified mask and a
    nonzero ``dt``; rows in their clean-context phase use the full-window
    mask and dt=0 (their ``new_kv`` is what matters).  Phase is data, so
    one executable serves every phase mix of a sub-batch.  Masks are
    None when the whole (extent-sliced) context is visible to every
    stream — the fill-homogeneous, unsparsified common case — which
    skips the per-score mask selects entirely."""
    if dn_mask is None and cl_mask is None:
        mask = None
    else:
        ones = jnp.ones(ctx_k.shape[1:3], bool)
        mask = jnp.where(is_denoise[:, None],
                         ones if dn_mask is None else dn_mask,
                         ones if cl_mask is None else cl_mask)
    v_pred, new_kv = chunk_forward(cfg, p, x, t, ctx_k, ctx_v,
                                   q_offset=q_offset, ctx_mask=mask)
    x_new = x - dt[:, None, None] * v_pred.astype(x.dtype)
    return x_new, new_kv


def _chunk_forward_pages(cfg: ModelConfig, p: Params, x_chunk: jax.Array,
                         t: jax.Array, pools,
                         page_mask: Optional[jax.Array], *, q_offset,
                         ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Shared DiT body of the page-table-native forwards.

    ``pools`` is a tuple of ``(k_pages, v_pages, block_table, head_lo,
    head_hi)`` KV-head shards covering ``[0, n_kv_heads)``, each pool
    the whole layer-stacked head-major [L, n_pages, Hkv, page, Dh]
    buffer.  One shard is the plain paged forward: the layer loop hands
    attention the layer INDEX and the kernel reads that layer of the
    pool in place (no per-layer pool slice is ever copied).  Two shards
    is elastic SP2, each shard's attention reading its own pool/table
    (Ulysses head partition — per-head attention never mixes heads, so
    the sharded result is bit-identical to the single-shard one
    whenever the shards mirror the same KV).
    """
    b, tc, _ = x_chunk.shape
    d = cfg.d_model
    hkv = cfg.n_kv_heads
    single = len(pools) == 1
    h = shard(x_chunk.astype(p["in_proj"].dtype) @ p["in_proj"],
              "batch", None, "embed")
    temb = _time_embed(p, t, d)                                   # [B,D]
    q_off = jnp.asarray(q_offset)
    if q_off.ndim:                                  # per-stream offsets
        positions = q_off[:, None] + jnp.arange(tc)[None, :]      # [B,Tc]
    else:
        positions = q_off + jnp.arange(tc)                        # [Tc]
    ones = jnp.ones((d,), h.dtype)

    def body(hh, xs):
        lp, li = xs["layer"], xs["index"]
        mod = jax.nn.silu(temb) @ lp["mod"] + lp["mod_b"]         # [B,6D]
        sh1, sc1, g1, sh2, sc2, g2 = jnp.split(mod, 6, axis=-1)
        a_in = _modulate(L.rmsnorm(hh, ones, cfg.norm_eps), sh1, sc1)
        q, k, v = L.attn_qkv(cfg, lp["attn"], a_in, positions)
        outs = []
        for kp, vp, tbl, lo, hi in pools:
            if single:
                o_s = paged_mha(q, kp, vp, tbl, page_mask, k, v, li,
                                n_kv_heads=hkv, sink=COND_TOKENS,
                                chunk_tokens=tc)
            else:
                o_s = paged_mha(shard_heads(q, hkv, lo, hi),
                                kp[li, :, lo:hi], vp[li, :, lo:hi],
                                tbl, page_mask,
                                shard_heads(k, hkv, lo, hi),
                                shard_heads(v, hkv, lo, hi),
                                n_kv_heads=hi - lo, sink=COND_TOKENS,
                                chunk_tokens=tc)
            outs.append(o_s)
        o = outs[0] if single else merge_head_shards(
            outs, [hi - lo for (_, _, _, lo, hi) in pools])
        o = o.reshape(b, tc, cfg.n_heads * cfg.head_dim)
        hh = hh + g1[:, None, :] * shard(o @ lp["attn"]["wo"],
                                         "batch", None, "embed")
        f_in = _modulate(L.rmsnorm(hh, ones, cfg.norm_eps), sh2, sc2)
        hh = hh + g2[:, None, :] * L.mlp_block(cfg, lp["mlp"], f_in)
        return hh, {"k": k, "v": v}

    xs = {"layer": p["layers"], "index": jnp.arange(cfg.n_layers)}
    h, new_kv = jax.lax.scan(body, h, xs)

    mod = jax.nn.silu(temb) @ p["final_mod"]
    sh, sc = jnp.split(mod, 2, axis=-1)
    h = _modulate(L.rmsnorm(h, p["final_norm"], cfg.norm_eps), sh, sc)
    return h @ p["out_proj"], new_kv


def chunk_forward_paged(cfg: ModelConfig, p: Params, x_chunk: jax.Array,
                        t: jax.Array, k_pages: jax.Array,
                        v_pages: jax.Array, block_table: jax.Array,
                        page_mask: Optional[jax.Array], *, q_offset,
                        ) -> Tuple[jax.Array, Dict[str, Any]]:
    """``chunk_forward`` with the cached context consumed IN PLACE from
    the paged KV pool instead of a gathered [L, B, ctx_len, ...] copy.

    k_pages/v_pages [L, n_pages, Hkv, page, Dh] — the whole head-major
    device pool; block_table [B, n] per-stream page tables (entry 0 =
    sink page, entry 1+r = ring slot r); page_mask [B, n*page] visible
    context tokens in table order, or None when every valid token is
    visible (homogeneous fill, full window, no sparsity — per-score
    masking is skipped entirely, like the gathered path's dropped
    masks).  Attention is
    ``attention.paged_mha``: paged-context online-softmax partials
    merged with the chunk's own fresh KV, so the only per-step KV
    traffic is the pages the tables actually reference.  Returns the
    same (prediction, {"k","v"}) as ``chunk_forward``; numerics agree
    with the gathered path up to fp32 online-softmax merge order.
    """
    return _chunk_forward_pages(
        cfg, p, x_chunk, t,
        ((k_pages, v_pages, block_table, 0, cfg.n_kv_heads),),
        page_mask, q_offset=q_offset)


@functools.partial(jax.jit, static_argnums=(0,))
def denoise_step_paged(cfg: ModelConfig, p: Params, x: jax.Array,
                       t: jax.Array, dt: jax.Array, k_pages: jax.Array,
                       v_pages: jax.Array, block_table: jax.Array,
                       dn_mask: Optional[jax.Array],
                       cl_mask: Optional[jax.Array],
                       q_offset: jax.Array, is_denoise: jax.Array):
    """Page-table-native sibling of ``denoise_step``: the sub-batch's
    context stays IN the pool and per-stream visibility rides in the
    page-coordinate masks.  Batch-axis elastic SP rides this same step:
    a stream borrowed onto another device becomes an ordinary extra
    batch row over the donor's pool (full-head mirror pages in the
    donor's block table), so co-serving it with the donor's own streams
    is the one fused call — no SP-specific kernel and no solo dispatch.
    ``dn_mask=None`` is the all-visible fast
    path (homogeneous fill, full window, no sparsity: each page's
    static valid prefix is visible, no per-score select — the paged
    analogue of the gathered path's dropped masks; note dn all-visible
    implies cl all-visible, since the clean window is a superset);
    ``cl_mask=None`` marks the common case where the clean pass sees
    exactly the denoise mask, skipping the per-row select."""
    mask = dn_mask if cl_mask is None else \
        jnp.where(is_denoise[:, None], dn_mask, cl_mask)
    v_pred, new_kv = chunk_forward_paged(cfg, p, x, t, k_pages, v_pages,
                                         block_table, mask,
                                         q_offset=q_offset)
    x_new = x - dt[:, None, None] * v_pred.astype(x.dtype)
    return x_new, new_kv


def chunk_forward_paged_sp(cfg: ModelConfig, p: Params, x_chunk: jax.Array,
                           t: jax.Array, k_home: jax.Array,
                           v_home: jax.Array, k_donor: jax.Array,
                           v_donor: jax.Array, table_home: jax.Array,
                           table_donor: jax.Array,
                           page_mask: Optional[jax.Array], *, q_offset,
                           ) -> Tuple[jax.Array, Dict[str, Any]]:
    """SP2 sibling of ``chunk_forward_paged``: the stream's KV heads are
    Ulysses-partitioned across two lanes (paper SS4.3 / App. C.4).

    The home lane's pool ``k_home``/``v_home`` is the system of record
    (full heads); the donor lane's pool ``k_donor``/``v_donor`` carries
    the stream's UPPER half heads in its own page set (``table_donor``).
    Each shard runs paged attention over its own half — the home shard
    reads heads [0, H/2) from the home pool, the donor shard reads
    heads [H/2, H) from the donor pool — and the outputs concatenate
    back into full-head order.  Per-head attention never mixes heads,
    so the result is bit-identical to the SP1 ``chunk_forward_paged``
    whenever the donor's half mirrors the home pool's upper half.  On a
    multi-device mesh the two shards map onto the two lanes' devices;
    on CPU they model the donor's borrowed compute slot.
    """
    hkv = cfg.n_kv_heads
    h2 = hkv // 2
    assert hkv % 2 == 0, f"SP2 head split needs even n_kv_heads ({hkv})"
    return _chunk_forward_pages(
        cfg, p, x_chunk, t,
        ((k_home, v_home, table_home, 0, h2),
         (k_donor, v_donor, table_donor, h2, hkv)),
        page_mask, q_offset=q_offset)


@functools.partial(jax.jit, static_argnums=(0,))
def denoise_step_paged_sp(cfg: ModelConfig, p: Params, x: jax.Array,
                          t: jax.Array, dt: jax.Array, k_home: jax.Array,
                          v_home: jax.Array, k_donor: jax.Array,
                          v_donor: jax.Array, table_home: jax.Array,
                          table_donor: jax.Array,
                          dn_mask: Optional[jax.Array],
                          cl_mask: Optional[jax.Array],
                          q_offset: jax.Array, is_denoise: jax.Array):
    """Elastic-SP2 sibling of ``denoise_step_paged``: one stream's
    denoise step with its KV heads split across the home and donor
    lanes' pools.  Mask semantics match ``denoise_step_paged``.  The
    serving executor pre-jits this per SP group (`LanePool.prejit_sp`)
    so triggering elastic SP never compiles on the critical path."""
    mask = dn_mask if cl_mask is None else \
        jnp.where(is_denoise[:, None], dn_mask, cl_mask)
    v_pred, new_kv = chunk_forward_paged_sp(
        cfg, p, x, t, k_home, v_home, k_donor, v_donor, table_home,
        table_donor, mask, q_offset=q_offset)
    x_new = x - dt[:, None, None] * v_pred.astype(x.dtype)
    return x_new, new_kv


def serve_chunk(cfg: ModelConfig, p: Params, cache: Dict[str, Any],
                noise: jax.Array, fidelity: FidelityConfig = HIGHEST_QUALITY,
                ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Generate one chunk under a fidelity configuration.

    noise: [B, T_c, LATENT_CH].  Returns (clean chunk latents, new cache).
    """
    tc = chunk_tokens(cfg)
    ctx_k, ctx_v = visible_context(cfg, cache, fidelity.window)
    q_offset = COND_TOKENS + cache["chunks"] * tc

    grid = sigma_schedule(fidelity.steps)
    x = noise
    for i in range(fidelity.steps):
        t = jnp.full((noise.shape[0],), float(grid[i]), jnp.float32)
        v_pred, _ = chunk_forward(cfg, p, x, t, ctx_k, ctx_v,
                                  q_offset=q_offset,
                                  sparsity=fidelity.sparsity)
        dt = float(grid[i] - grid[i + 1])
        x = x - dt * v_pred.astype(x.dtype)     # Euler step toward data

    # context KV for future chunks comes from a clean-context pass
    t0 = jnp.zeros((noise.shape[0],), jnp.float32)
    _, clean_kv = chunk_forward(cfg, p, x, t0, ctx_k, ctx_v,
                                q_offset=q_offset)
    if fidelity.quant == "fp8":
        clean_kv = {k_: v_.astype(jnp.float8_e4m3fn)
                    for k_, v_ in clean_kv.items()}
    cache = append_chunk_kv(cfg, cache, clean_kv)
    return x, cache


# ---------------------------------------------------------------------------
# batched serving: leading stream-batch axis over per-stream ring caches
# ---------------------------------------------------------------------------
# The batched executor stacks streams along the cache batch axis.  Unlike
# the sequential cache (host-side len/chunks, shapes grow with fill), the
# batched cache is a fixed-capacity chunk-granular ring per stream: the
# sink (cond) tokens sit in slots [0, COND_TOKENS) and chunk c lands in
# the ring slot ``kvcache.chunk_slot(c, window_chunks, ...)``.  Streams at
# different chunk indices coexist in one batch; per-stream positions come
# from ``chunks`` and per-stream visibility (residency + fidelity window
# + sparsity) is a boolean mask, so every denoise step is one jitted call
# at full-capacity static shapes regardless of fill.


def init_batched_cache(cfg: ModelConfig, p: Params, cond: jax.Array,
                       kv_dtype: Optional[str] = None) -> Dict[str, Any]:
    """Fixed-capacity ring cache for a batch of streams.

    cond: [B, COND_TOKENS, d_model] per-stream conditioning.  Returns
    {"k","v"} of [L, B, cap, Hkv, Dh] plus host-side per-stream chunk
    counts ``chunks`` [B].
    """
    ks, vs = cond_kv(cfg, p, cond, kv_dtype)
    pad = ((0, 0), (0, 0), (0, cache_capacity(cfg) - COND_TOKENS),
           (0, 0), (0, 0))
    return {"k": jnp.pad(ks, pad), "v": jnp.pad(vs, pad),
            "chunks": np.zeros(cond.shape[0], np.int64)}


def batched_context_mask(cfg: ModelConfig, chunks: np.ndarray, window: int,
                         sparsity: float = 0.0) -> np.ndarray:
    """Per-stream context-visibility mask [B, cap] over the ring cache.

    Marks, for each stream, the sink tokens plus the tokens of its last
    ``min(window, resident)`` chunks that survive the rho sparsity drop —
    the exact token set ``visible_context`` + ``cache_sparse_index`` give
    the sequential path, mapped through the ring permutation.
    """
    n = len(np.asarray(chunks, np.int64))
    return batched_context_mask_multi(
        cfg, chunks, np.full(n, window, np.int64),
        np.full(n, sparsity, np.float64))


def batched_context_mask_multi(cfg: ModelConfig, chunks: np.ndarray,
                               windows: np.ndarray,
                               sparsities: np.ndarray) -> np.ndarray:
    """``batched_context_mask`` with PER-ROW window/sparsity knobs.

    The fused heterogeneous-fidelity dispatch stacks streams of
    different fidelities into one sub-batch; since window and sparsity
    only ever enter the step as mask *data*, each row simply gets the
    mask its own fidelity would have produced — row i here is
    bit-identical to row i of a per-fidelity ``batched_context_mask``
    call (the uniform builder above delegates to this one).
    """
    tc = chunk_tokens(cfg)
    w_max = cfg.ardit_window_chunks
    mask = np.zeros((len(chunks), cache_capacity(cfg)), bool)
    windows = np.asarray(windows, np.int64)
    sparsities = np.asarray(sparsities, np.float64)
    for i, n in enumerate(np.asarray(chunks, np.int64)):
        w = min(int(windows[i]), int(n), w_max)
        ctx_len = COND_TOKENS + w * tc
        keep = cache_sparse_index(cfg, ctx_len, float(sparsities[i]))
        idx = np.arange(ctx_len) if keep is None else keep
        mask[i, idx[idx < COND_TOKENS]] = True
        body = idx[idx >= COND_TOKENS] - COND_TOKENS
        if w and body.size:
            c_abs = (int(n) - w) + body // tc       # absolute chunk index
            slot = COND_TOKENS + (c_abs % w_max) * tc + body % tc
            mask[i, slot] = True
    return mask


def append_chunk_kv_batched(cfg: ModelConfig, cache: Dict[str, Any],
                            new_kv: Dict[str, jax.Array]) -> Dict[str, Any]:
    """Ring-write one new chunk of KV per stream at its own slot."""
    tc = chunk_tokens(cfg)
    chunks = np.asarray(cache["chunks"], np.int64)
    dest = kvcache.chunk_slot(jnp.asarray(chunks), cfg.ardit_window_chunks,
                              COND_TOKENS, tc)
    return {"k": kvcache.write_block_layers(cache["k"], new_kv["k"], dest),
            "v": kvcache.write_block_layers(cache["v"], new_kv["v"], dest),
            "chunks": chunks + 1}


def serve_chunk_batched(cfg: ModelConfig, p: Params, cache: Dict[str, Any],
                        noise: jax.Array,
                        fidelity: FidelityConfig = HIGHEST_QUALITY,
                        ) -> Tuple[jax.Array, Dict[str, Any]]:
    """One chunk for every stream of a batched cache under one shared
    fidelity configuration (= one same-fidelity sub-batch).

    noise: [B, T_c, LATENT_CH]; streams may sit at different chunk
    indices.  Per stream, numerically equivalent to ``serve_chunk``.
    """
    tc = chunk_tokens(cfg)
    chunks = np.asarray(cache["chunks"], np.int64)
    q_offset = jnp.asarray(COND_TOKENS + chunks * tc, jnp.int32)
    dn_mask = jnp.asarray(batched_context_mask(
        cfg, chunks, fidelity.window, fidelity.sparsity))

    grid = sigma_schedule(fidelity.steps)
    x = noise
    for i in range(fidelity.steps):
        t = jnp.full((noise.shape[0],), float(grid[i]), jnp.float32)
        v_pred, _ = chunk_step(cfg, p, x, t, cache["k"], cache["v"],
                               q_offset, dn_mask)
        dt = float(grid[i] - grid[i + 1])
        x = x - dt * v_pred.astype(x.dtype)

    # clean-context pass sees the full (unsparsified) window
    clean_mask = jnp.asarray(batched_context_mask(
        cfg, chunks, fidelity.window))
    t0 = jnp.zeros((noise.shape[0],), jnp.float32)
    _, clean_kv = chunk_step(cfg, p, x, t0, cache["k"], cache["v"],
                             q_offset, clean_mask)
    if fidelity.quant == "fp8":
        clean_kv = {k_: v_.astype(jnp.float8_e4m3fn)
                    for k_, v_ in clean_kv.items()}
    return x, append_chunk_kv_batched(cfg, cache, clean_kv)


# ---------------------------------------------------------------------------
# training: causal-forcing style denoising over a chunk sequence
# ---------------------------------------------------------------------------

def train_loss(cfg: ModelConfig, p: Params,
               batch: Dict[str, jax.Array]) -> jax.Array:
    """Flow-matching loss over a sequence of chunks with causal context.

    batch: latents [B, n_chunks, T_c, LATENT_CH], cond [B, 77, d_model],
           t [B, n_chunks] denoise times, noise (same shape as latents).
    Chunks are processed in a Python loop (static, growing context), the
    exact teacher-forced analogue of ``serve_chunk``'s rolling window.
    """
    lat, cond = batch["latents"], batch["cond"]
    t_all, noise = batch["t"], batch["noise"]
    b, n_chunks, tc, _ = lat.shape
    cache = init_cache(cfg, p, cond)
    total = jnp.zeros((), jnp.float32)
    for c in range(n_chunks):
        x0, eps, t = lat[:, c], noise[:, c], t_all[:, c]
        x_t = (1.0 - t[:, None, None]) * x0 + t[:, None, None] * eps
        target = eps - x0                       # rectified-flow velocity
        ctx_k, ctx_v = visible_context(cfg, cache, cfg.ardit_window_chunks)
        q_offset = COND_TOKENS + c * chunk_tokens(cfg)
        pred, _ = chunk_forward(cfg, p, x_t, t, ctx_k, ctx_v,
                                q_offset=q_offset)
        total = total + jnp.mean((pred.astype(jnp.float32)
                                  - target.astype(jnp.float32)) ** 2)
        # clean pass provides the causal context for the next chunk
        _, clean_kv = chunk_forward(cfg, p, x0, jnp.zeros_like(t),
                                    ctx_k, ctx_v, q_offset=q_offset)
        cache = append_chunk_kv(cfg, cache, clean_kv)
    return total / n_chunks
