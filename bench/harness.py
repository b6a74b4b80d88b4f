"""Helpers copied from ``chip_smoke.py`` at commit 14b04cc, frozen here so
a change to the smoke check cannot change the benchmark: the TPU guard,
the compile clock and the cycling fidelity policy (the warm-up's)."""
from __future__ import annotations

import sys


def require_tpu(chips: int):
    """The JAX module, once a TPU backend with ``chips`` chips is up;
    exit non-zero otherwise."""
    import jax
    try:
        backend = jax.default_backend()
    except RuntimeError as e:
        sys.exit(f"no TPU found: {e}")
    if backend != "tpu":
        sys.exit(f"no TPU found: JAX runs on {backend!r}")
    if len(jax.devices()) < chips:
        sys.exit(f"the cell needs {chips} chips, JAX sees "
                 f"{len(jax.devices())}")
    return jax


class CompileClock:
    """Sums JAX's backend-compile durations (persistent-cache reads
    included) from the moment it is installed."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


class CyclingFidelity:
    """Fidelity policy that alternates between the given configs, one per
    chunk decision — a fixed mix of fidelities, whatever the budget."""

    def __init__(self, fids, profile):
        from repro.core.bmpr import BMPRDecision
        self._dec = [BMPRDecision(f, profile.latency(f), profile.quality(f),
                                  "static") for f in fids]
        self.by_key = {f.key: f for f in fids}
        self._i = 0

    def select(self, budget):
        dec = self._dec[self._i % len(self._dec)]
        self._i += 1
        return dec
