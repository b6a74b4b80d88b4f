"""Which implementation the kernel dispatchers (``*/ops.py``) run."""
from __future__ import annotations

import os

import jax

INTERPRET_ENV = "REPRO_FORCE_PALLAS_INTERPRET"


def kernel_mode() -> str:
    """``"pallas"`` on a TPU backend, always.  Off the TPU: the pure-jnp
    oracle (``"ref"``), or the Pallas kernel in interpret mode
    (``"interpret"``) when ``REPRO_FORCE_PALLAS_INTERPRET=1`` — a switch
    for kernel tests on the CPU, which is an error on a TPU: there the
    kernels always compile for the chip."""
    forced = os.environ.get(INTERPRET_ENV)
    if jax.default_backend() == "tpu":
        if forced is not None:
            raise RuntimeError(
                f"{INTERPRET_ENV}={forced!r} is set on a TPU backend; it "
                f"only selects interpret mode for CPU kernel tests")
        return "pallas"
    return "interpret" if forced == "1" else "ref"
