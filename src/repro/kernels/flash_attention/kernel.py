"""Pallas TPU flash attention with the paper's fidelity knobs.

One kernel serves four attention modes (SS2.1, SS5):
    causal                 block-triangular schedule
    sink + sliding window  knob W: off-window KV blocks skipped
    block-sparse           knob rho: static keep-list, skipped blocks do
                           not run (pl.when predication on the MXU)
    non-causal             chunk-bidirectional AR-DiT attention

TPU adaptation (DESIGN.md SS3): blocks are 128-aligned for the MXU; the
online-softmax running state (m, l, acc) lives in VMEM scratch and is
carried across the innermost (arbitrary-semantics) KV grid dimension;
whole-block skips are grid predicates rather than warp-level masks.

Layout: q [B, Hq, Sq, D]; k,v [B, Hkv, Skv, D] (ops.py transposes from the
model's [B, S, H, D]).  GQA: kv head index = q head // group.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(keep_ref,                       # scalar-prefetch [nq*nk] i32
            q_ref, k_ref, v_ref,            # VMEM blocks
            o_ref,                          # output block
            m_scr, l_scr, acc_scr,          # VMEM scratch
            *, scale: float, causal: bool, q_offset: int,
            window: int, sink: int, block_q: int, block_kv: int,
            n_kv: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = q_offset + qi * block_q
    k_lo = ki * block_kv

    # ---- whole-block schedule predicate (grid-level skip) -----------------
    run = keep_ref[qi * n_kv + ki] != 0
    if causal:
        run = jnp.logical_and(run, k_lo <= q_lo + block_q - 1)
        if window:
            # block overlaps [q_lo-window+1, q_hi] or the sink prefix
            in_win = k_lo + block_kv - 1 >= q_lo - window + 1
            in_sink = k_lo < sink
            run = jnp.logical_and(run, jnp.logical_or(in_win, in_sink))

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)          # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_kv), 0)
            k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_kv), 1)
            mask = q_pos >= k_pos
            if window:
                mask = jnp.logical_and(
                    mask, jnp.logical_or(k_pos > q_pos - window,
                                         k_pos < sink))
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[...] * alpha + jnp.sum(p, axis=-1)
        acc = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)              # fully-masked rows
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def keep_matrix(n_q: int, n_kv: int, *, causal: bool, q_offset: int,
                window: int, sink: int, sparsity: float,
                block_q: int, block_kv: int) -> np.ndarray:
    """Static [n_q, n_kv] 0/1 schedule for the rho knob (strided keep)."""
    keep = np.ones((n_q, n_kv), np.int32)
    if sparsity <= 0.0:
        return keep
    from repro.models.attention import sparse_keep_list
    sink_blocks = max(1, sink // block_kv) if sink else 1
    for i in range(n_q):
        if causal:
            q_hi = q_offset + (i + 1) * block_q
            n_vis = min(n_kv, (q_hi + block_kv - 1) // block_kv)
        else:
            n_vis = n_kv
        kept = sparse_keep_list(1, [n_vis], sparsity,
                                sink_blocks=sink_blocks)[0]
        row = np.zeros((n_kv,), np.int32)
        row[list(kept)] = 1
        row[n_vis:] = 1          # blocks beyond visibility: causal pred cuts
        keep[i] = row
    return keep


@functools.partial(
    jax.jit, static_argnames=("causal", "q_offset", "window", "sink",
                              "sparsity", "block_q", "block_kv", "interpret"))
def flash_mha_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     causal: bool = True, q_offset: int = 0,
                     window: int = 0, sink: int = 0, sparsity: float = 0.0,
                     block_q: int = 128, block_kv: int = 128,
                     interpret: bool = False) -> jax.Array:
    """q [B,Hq,Sq,D]; k,v [B,Hkv,Skv,D] -> [B,Hq,Sq,D]."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    assert hq % hkv == 0
    group = hq // hkv
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    assert sq % block_q == 0 and skv % block_kv == 0, (sq, skv)
    n_q, n_kv = sq // block_q, skv // block_kv
    scale = 1.0 / math.sqrt(d)

    keep = jnp.asarray(keep_matrix(
        n_q, n_kv, causal=causal, q_offset=q_offset, window=window,
        sink=sink, sparsity=sparsity, block_q=block_q,
        block_kv=block_kv).reshape(-1))

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, q_offset=q_offset,
        window=window, sink=sink, block_q=block_q, block_kv=block_kv,
        n_kv=n_kv)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hq, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h, qi, ki, keep: (b_, h, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b_, h, qi, ki, keep: (b_, h // group, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b_, h, qi, ki, keep: (b_, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h, qi, ki, keep: (b_, h, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(keep, q, k, v)
