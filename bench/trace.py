"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` the profiler wrote: device operations
from each ``/device:TPU:<n>`` plane's "XLA Ops" line, and the benchmark's
own host spans (``bench/...`` ``TraceAnnotation`` events).  The window is
bounded by the ``bench/window_open`` and ``bench/window_close`` markers,
so device and host times are read on the profiler's one clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start s, end s


@dataclasses.dataclass
class Trace:
    device: Dict[str, List[Event]]        # plane -> ops, by start
    host: List[Event]                     # bench/... spans

    def marker(self, name: str) -> Optional[float]:
        ts = [s for n, s, _ in self.host if n == name]
        return ts[0] if ts else None

    def window(self) -> Tuple[float, float]:
        lo = self.marker("bench/window_open")
        hi = self.marker("bench/window_close")
        if lo is None or hi is None:
            raise ValueError("trace holds no window markers")
        return lo, hi


def load(directory: str) -> Trace:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = ProfileData.from_file(paths[-1])
    device: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            # "%fusion.12 = bf16[...] fusion(...)" -> "fusion.12"
            ops = [(e.name.split(" = ")[0].lstrip("%"), e.start_ns * 1e-9,
                    e.end_ns * 1e-9)
                   for line in plane.lines if line.name == "XLA Ops"
                   for e in line.events]
            device[plane.name] = sorted(ops, key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            host.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                        for line in plane.lines for e in line.events
                        if e.name.startswith("bench/"))
    return Trace(device, sorted(host, key=lambda e: e[1]))


def _clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union(events: Sequence[Event], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Merged busy intervals of ``events`` inside [lo, hi]."""
    out: List[Tuple[float, float]] = []
    for _, s, e in sorted(_clip(events, lo, hi), key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def busy_s(trace: Trace, lo: float, hi: float) -> float:
    """Seconds in which some operation ran, averaged over the chips."""
    if not trace.device:
        return 0.0
    return sum(sum(e - s for s, e in union(ops, lo, hi))
               for ops in trace.device.values()) / len(trace.device)


def kernel_s(trace: Trace, match: str, lo: float,
             hi: float) -> Tuple[float, int]:
    """(seconds, calls) of the device ops whose name contains ``match``,
    summed over the chips."""
    ev = [x for ops in trace.device.values() for x in _clip(ops, lo, hi)
          if match in x[0]]
    return sum(e - s for _, s, e in ev), len(ev)


def leaves(ops: Sequence[Event]) -> List[Event]:
    """The ops that contain no other op: a loop (``while``) holds the
    ops of its body on the same line."""
    ops = sorted(ops, key=lambda x: (x[1], -x[2]))
    return [a for a, b in zip(ops, ops[1:] + [("", float("inf"), 0.0)])
            if not b[1] < a[2]]


def top_ops(trace: Trace, lo: float, hi: float,
            k: int = 10) -> List[List]:
    """The device operations (innermost ones) that took most time, by
    name."""
    tot: Dict[str, float] = {}
    for ops in trace.device.values():
        for n, s, e in leaves(_clip(ops, lo, hi)):
            tot[n] = tot.get(n, 0.0) + (e - s)
    return [[n, t] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(trace: Trace, lo: float, hi: float,
              k: int = 10) -> List[List]:
    """Device idle time by what the host was doing: the part of each gap
    between busy intervals (first chip) that a ``bench/...`` span covers
    goes to that span's name, the rest (waiting for arrivals, Python
    between the spans) to ``host/other``; totals by label."""
    if not trace.device:
        return []
    busy = union(next(iter(trace.device.values())), lo, hi)
    edges = [lo] + [x for b in busy for x in b] + [hi]
    spans = [h for h in trace.host if not h[0].startswith("bench/window")]
    starts = [h[1] for h in spans]
    longest = max((he - hs for _, hs, he in spans), default=0.0)
    tot: Dict[str, float] = {}
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        covered = 0.0
        j = bisect.bisect_left(starts, e) - 1
        while j >= 0 and starts[j] > s - longest:
            n, hs, he = spans[j]
            ov = min(e, he) - max(s, hs)
            if ov > 0:
                tot[n] = tot.get(n, 0.0) + ov
                covered += ov
            j -= 1
        tot["host/other"] = tot.get("host/other", 0.0) + max(
            0.0, (e - s) - covered)
    return [[n, t] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]
