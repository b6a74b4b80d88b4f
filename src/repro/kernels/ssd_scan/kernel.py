"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

TPU adaptation of the SSD algorithm (arXiv:2405.21060): the quadratic
intra-chunk part is three MXU matmuls over a [Q, Q] segment-sum mask; the
inter-chunk recurrence is carried in VMEM scratch ([N, P] per (batch,
head)) across the innermost (arbitrary-semantics) chunk grid dimension —
the kernel-level analogue of ``lax.scan`` over chunk states.

Wrapper layout: x [B, H, NC, Q, P]; dt [B, H, NC, Q]; Bm/Cm [B, NC, Q, N]
(n_groups folded to 1; shared across heads); A [H].
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, s0_ref,
            y_ref, sf_ref,
            state_scr,
            *, q: int, use_init: bool):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        if use_init:
            state_scr[...] = s0_ref[0, 0].astype(jnp.float32)
        else:
            state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0, 0].astype(jnp.float32)           # [Q, P]
    dt = dt_ref[0, 0, 0].astype(jnp.float32)         # [Q]
    a = a_ref[0]                                     # scalar
    bm = b_ref[0, 0].astype(jnp.float32)             # [Q, N]
    cm = c_ref[0, 0].astype(jnp.float32)             # [Q, N]

    dA = dt * a                                      # [Q] (<= 0)
    cs = jnp.cumsum(dA)                              # [Q]

    # intra-chunk: Y = ((C B^T) * L * dt_j) X
    seg = cs[:, None] - cs[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    L = jnp.where(ii >= jj, jnp.exp(seg), 0.0)
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # [Q,Q]
    w = cb * L * dt[None, :]
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)    # [Q,P]

    # inter-chunk: Y += (C * exp(cs)) @ state   (state [N, P])
    state = state_scr[...]
    c_scaled = cm * jnp.exp(cs)[:, None]
    y += jax.lax.dot_general(c_scaled, state, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    # state update: state' = exp(cs_last) * state + (B * dt * decay)^T X
    decay_to_end = jnp.exp(cs[-1] - cs)              # [Q]
    b_scaled = bm * (dt * decay_to_end)[:, None]     # [Q,N]
    chunk_state = jax.lax.dot_general(
        b_scaled, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # [N,P]
    state_scr[...] = jnp.exp(cs[-1]) * state + chunk_state

    @pl.when(ci == pl.num_programs(2) - 1)
    def _final():
        sf_ref[0, 0] = state_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_pallas(x: jax.Array, dt: jax.Array, A: jax.Array,
               Bm: jax.Array, Cm: jax.Array, *, chunk: int = 128,
               init_state: Optional[jax.Array] = None,
               interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Same contract as ``ref.ssd_ref`` (model layout [B,S,H,P] etc.)."""
    b, s, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    assert g == 1, "kernel folds n_groups to 1 (models use G=1)"
    out_dtype = x.dtype

    q = min(chunk, s)
    pad = (-s) % q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = s + pad
    nc = sp // q

    xk = x.reshape(b, nc, q, h, p).transpose(0, 3, 1, 2, 4)   # [B,H,NC,Q,P]
    dtk = dt.reshape(b, nc, q, h).transpose(0, 3, 1, 2)       # [B,H,NC,Q]
    bk = Bm.reshape(b, nc, q, n)                              # [B,NC,Q,N]
    ck = Cm.reshape(b, nc, q, n)
    use_init = init_state is not None
    if use_init:
        s0 = init_state.transpose(0, 1, 3, 2).astype(jnp.float32)  # [B,H,N,P]
    else:
        s0 = jnp.zeros((b, h, n, p), jnp.float32)

    kernel = functools.partial(_kernel, q=q, use_init=use_init)

    y, sf = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, q, p), lambda b_, h_, c: (b_, h_, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, q), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1,), lambda b_, h_, c: (h_,)),
            pl.BlockSpec((1, 1, q, n), lambda b_, h_, c: (b_, c, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda b_, h_, c: (b_, c, 0, 0)),
            pl.BlockSpec((1, 1, n, p), lambda b_, h_, c: (b_, h_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, q, p), lambda b_, h_, c: (b_, h_, c, 0, 0)),
            pl.BlockSpec((1, 1, n, p), lambda b_, h_, c: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, nc, q, p), out_dtype),
            jax.ShapeDtypeStruct((b, h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xk, dtk, A.astype(jnp.float32), bk, ck, s0)

    y = y.transpose(0, 2, 3, 1, 4).reshape(b, sp, h, p)[:, :s]
    return y.astype(out_dtype), sf.transpose(0, 1, 3, 2)      # [B,H,P,N]
