"""Dispatching wrapper for flash attention.

Model layout in/out: [B, S, H, D].  Implementation per
``kernels.mode.kernel_mode``: the Pallas kernel on a TPU, the jnp ref or
interpret mode off it.
"""
from __future__ import annotations

import jax

from repro.kernels.flash_attention import ref as _ref
from repro.kernels.mode import kernel_mode


def flash_mha(q: jax.Array, k: jax.Array, v: jax.Array, *,
              n_kv_heads: int, causal: bool = True, q_offset: int = 0,
              window: int = 0, sink: int = 0, sparsity: float = 0.0,
              block_q: int = 128, block_kv: int = 128) -> jax.Array:
    """q [B,Sq,Hq,D]; k,v [B,Skv,Hkv,D] -> [B,Sq,Hq,D]."""
    mode = kernel_mode()
    if mode == "ref":
        return _ref.flash_mha_ref(q, k, v, n_kv_heads=n_kv_heads,
                                  causal=causal, q_offset=q_offset,
                                  window=window, sink=sink,
                                  sparsity=sparsity)
    from repro.kernels.flash_attention import kernel as _k
    out = _k.flash_mha_pallas(
        q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
        causal=causal, q_offset=q_offset, window=window, sink=sink,
        sparsity=sparsity, block_q=block_q, block_kv=block_kv,
        interpret=(mode == "interpret"))
    return out.swapaxes(1, 2)
