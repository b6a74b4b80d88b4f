"""Dispatching wrapper for the SSD chunked scan.

Implementation per ``kernels.mode.kernel_mode``: the Pallas kernel
(``kernel.py``) on a TPU; off it the pure-jnp oracle (``ref.py``), or
the kernel in interpret mode under ``REPRO_FORCE_PALLAS_INTERPRET=1``.
"""
from __future__ import annotations

from typing import Tuple

import jax

from repro.kernels.mode import kernel_mode
from repro.kernels.ssd_scan import ref as _ref


def ssd(x, dt, A, Bm, Cm, *, chunk: int = 128,
        init_state=None) -> Tuple[jax.Array, jax.Array]:
    mode = kernel_mode()
    if mode == "ref":
        return _ref.ssd_ref(x, dt, A, Bm, Cm, chunk=chunk,
                            init_state=init_state)
    from repro.kernels.ssd_scan import kernel as _k
    return _k.ssd_pallas(x, dt, A, Bm, Cm, chunk=chunk,
                         init_state=init_state,
                         interpret=(mode == "interpret"))


def ssd_decode(x, dt, A, Bm, Cm, state):
    return _ref.ssd_decode_ref(x, dt, A, Bm, Cm, state)
