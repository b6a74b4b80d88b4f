"""Traffic generators.  A traffic file under ``traffic/`` names one of
``GENERATORS`` and its parameters; every generator returns
``(arrival seconds, pixel frames)`` pairs, arrival 0 being the start of
the ramp that precedes the measured window.

``steady`` and ``flash_crowd`` are frozen copies of
``src/repro/sched_sim/workloads.py`` at commit 14b04cc (paper App. B:
Poisson arrivals, lengths drawn from {81, 129, 161, 241} frames).
``stratified_poisson`` gives every seed the same work: the inter-arrival
gaps are the n stratified quantiles of an exponential of the given rate,
and the lengths an even multiset of the given frame counts, each in an
order drawn from the seed.  Runs on different seeds then differ in order,
not in load, so their spread measures the server and not the draw.
"""
from __future__ import annotations

import math
import random
from typing import Dict, List, Sequence, Tuple

Arrivals = List[Tuple[float, int]]
APP_B_FRAMES = (81, 129, 161, 241)


def _poisson_arrivals(n: int, rate: float, rng: random.Random) -> List[float]:
    t, out = 0.0, []
    for _ in range(n):
        t += rng.expovariate(rate)
        out.append(t)
    return out


def steady(n: int, rate: float, seed: int,
           frames: Sequence[int] = APP_B_FRAMES) -> Arrivals:
    rng = random.Random(seed)
    arr = _poisson_arrivals(n, rate, rng)
    return [(arr[i], rng.choice(list(frames))) for i in range(n)]


def flash_crowd(n: int, rate: float, seed: int, spike_frac: float = 0.3,
                spike_width: float = 2.0,
                frames: Sequence[int] = APP_B_FRAMES) -> Arrivals:
    rng = random.Random(seed)
    n_spike = int(spike_frac * n)
    base = _poisson_arrivals(n - n_spike, rate, rng)
    t_spike = base[len(base) // 2] if base else 0.0
    spike = sorted(t_spike + rng.uniform(0.0, spike_width)
                   for _ in range(n_spike))
    arrivals = sorted(base + spike)
    rng2 = random.Random(seed + 1)
    return [(a, rng2.choice(list(frames))) for a in arrivals]


def stratified_poisson(n: int, rate: float, seed: int,
                       frames: Sequence[int] = APP_B_FRAMES) -> Arrivals:
    rng = random.Random(seed)
    gaps = [-math.log(1.0 - (k + 0.5) / n) / rate for k in range(n)]
    lengths = [frames[k % len(frames)] for k in range(n)]
    rng.shuffle(gaps)
    rng.shuffle(lengths)
    t, out = 0.0, []
    for g, f in zip(gaps, lengths):
        out.append((t, f))
        t += g
    return out


GENERATORS = {"steady": steady, "flash_crowd": flash_crowd,
           "stratified_poisson": stratified_poisson}


def generate(traffic: Dict, seed: int, span_s: float) -> Arrivals:
    """Arrivals for ``span_s`` seconds (ramp + window) of a traffic file:
    its generator at ``rate_per_s`` streams per second."""
    rate = traffic["rate_per_s"]
    n = max(1, math.ceil(rate * span_s))
    arr = GENERATORS[traffic["generator"]](n, rate, seed,
                                        **traffic.get("params", {}))
    return [(a, f) for a, f in arr if a < span_s]
