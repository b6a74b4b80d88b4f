"""Dispatching wrapper for the scaled fp8 matmul."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.fp8_matmul import ref as _ref
from repro.kernels.mode import kernel_mode


def quantize_fp8(x: jax.Array, axis: int) -> Tuple[jax.Array, jax.Array]:
    return _ref.quantize_fp8_ref(x, axis)


def fp8_matmul(x: jax.Array, w: jax.Array, *,
               out_dtype=jnp.float32) -> jax.Array:
    """Online-quantized matmul: x [M,K] any float, w [K,N] any float."""
    x_q, sx = quantize_fp8(x, axis=1)
    w_q, sw = quantize_fp8(w, axis=0)
    mode = kernel_mode()
    if mode == "ref":
        return _ref.fp8_matmul_ref(x_q, w_q, sx, sw).astype(out_dtype)
    from repro.kernels.fp8_matmul import kernel as _k
    return _k.fp8_matmul_pallas(x_q, w_q, sx, sw, out_dtype=out_dtype,
                                interpret=(mode == "interpret"))
