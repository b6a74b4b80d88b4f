"""Frozen copy of the paper's VBench quality prior per fidelity.

Copied from ``src/repro/profiler/profiles.py`` (``chunk_quality`` and its
constants) at commit 14b04cc, so that a change to the program's profile
cannot move the benchmark's ``quality_loss``.  The prior is the quality
model BMPR routes on; with random weights, perceptual quality cannot be
measured, so this is the yardstick of what a fidelity choice costs.
"""
from __future__ import annotations

W_MAX = 7
Q_MAX = {"causal-forcing": 81.3, "self-forcing": 80.9}
A_S = 0.55
A_RHO = 2.6
A_W = 1.1
A_Q = 0.35
A_INT = 0.8
A_CACHE = {"off": 0.0, "conservative": 0.18, "aggressive": 0.5}
TOP = (4, 0.0, 7, "bf16")          # the highest-quality fidelity


def chunk_quality(steps: int, sparsity: float, window: int, quant: str,
                  cache: str = "off", *, model: str) -> float:
    vis = min(window, W_MAX) / W_MAX
    q = Q_MAX.get(model, 81.0)
    q -= A_S * (4 - steps) ** 1.6
    q -= A_RHO * (sparsity ** 2.5) * (vis ** 0.5)
    q -= A_W * (1.0 - vis) ** 1.4
    q -= A_Q * (1.0 if quant == "fp8" else 0.0)
    q -= A_INT * sparsity * (4 - steps) / 2.0
    q -= A_CACHE[cache]
    return q


def of_key(key: str, model: str) -> float:
    """Prior of a fidelity key such as ``S3_r0.6_W3_fp8`` (``_cc`` /
    ``_ca`` suffix: step cache)."""
    parts = key.split("_")
    cache = {"cc": "conservative", "ca": "aggressive"}.get(
        parts[4] if len(parts) > 4 else "", "off")
    return chunk_quality(int(parts[0][1:]), float(parts[1][1:]),
                         int(parts[2][1:]), parts[3], cache, model=model)


def top(model: str) -> float:
    return chunk_quality(*TOP, model=model)
