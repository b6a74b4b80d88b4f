"""The benchmark's own player model and end-to-end metrics.

Everything here works from two things the benchmark records itself: each
stream's scheduled arrival, and the time each of its chunks was ready
(stamped after ``block_until_ready`` on the chunk's array).  Nothing reads
the server's deadlines or slack.

Player: playout starts when chunk 0 is ready; chunk i is due at that start
+ i x cadence + the stall accumulated so far; a late chunk stalls playout
by its lateness.
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import Dict, List, Optional, Sequence, Tuple


@dataclasses.dataclass
class StreamLog:
    """What the benchmark saw of one stream."""
    sid: int
    arrival: float                     # scheduled arrival (session clock)
    target_chunks: int
    ready: List[float] = dataclasses.field(default_factory=list)
    quality: List[float] = dataclasses.field(default_factory=list)
    refused: bool = False
    finite: bool = True


def due_times(ready: Sequence[float], target: int, cadence: float,
              until: float) -> List[Tuple[float, Optional[float]]]:
    """(due, ready-or-None) for each chunk i >= 1 whose due time is known
    by ``until``.  Chunk 0 starts playout and is never late by this
    model (its wait is TTFC's).  A chunk not ready by ``until`` is due at
    the player's schedule; the chunks after it are not due yet (playout
    stalls on it)."""
    if not ready:
        return []
    start, stall, out = ready[0], 0.0, []
    for i in range(1, target):
        due = start + i * cadence + stall
        got = ready[i] if i < len(ready) and ready[i] <= until else None
        if got is None:
            if due <= until:
                out.append((due, None))
            break
        out.append((due, got))
        stall += max(0.0, got - due)
    return out


def cpr(logs: Sequence[StreamLog], cadence: float, t_open: float,
        t_close: float) -> Tuple[float, int]:
    """(on-time share, chunks due) over chunks due in [open, close]."""
    due_n = on_time = 0
    for s in logs:
        for due, got in due_times(s.ready, s.target_chunks, cadence,
                                  t_close):
            if t_open <= due <= t_close:
                due_n += 1
                on_time += got is not None and got <= due
    return (on_time / due_n if due_n else float("nan")), due_n


def ttfc_mean(logs: Sequence[StreamLog], t_open: float,
              t_close: float) -> Tuple[float, int]:
    """Mean scheduled-arrival -> first-chunk time over streams arriving
    in the window; a stream with no chunk by the close counts at its
    wait so far."""
    waits = []
    for s in logs:
        if t_open <= s.arrival < t_close:
            first = s.ready[0] if s.ready and s.ready[0] <= t_close else None
            waits.append((first if first is not None else t_close)
                         - s.arrival)
    return (statistics.fmean(waits) if waits else float("nan")), len(waits)


def chunk_gaps(logs: Sequence[StreamLog], t_open: float,
               t_close: float) -> List[float]:
    """For every chunk ready in the window, the time since the stream's
    previous chunk (for chunk 0, since its scheduled arrival)."""
    gaps = []
    for s in logs:
        prev = s.arrival
        for r in s.ready:
            if t_open <= r <= t_close:
                gaps.append(r - prev)
            prev = r
    return gaps


def p90(values: Sequence[float]) -> float:
    """90th percentile, linear between order statistics."""
    return statistics.quantiles(values, n=10, method="inclusive")[8]


def quality_loss(logs: Sequence[StreamLog], top: float, t_open: float,
                 t_close: float) -> Tuple[float, int]:
    """Mean (top-fidelity prior - chunk's prior) over chunks ready in
    the window."""
    loss = [top - q for s in logs for r, q in zip(s.ready, s.quality)
            if t_open <= r <= t_close]
    return (statistics.fmean(loss) if loss else float("nan")), len(loss)


def outcome(logs: Sequence[StreamLog], t_open: float, t_close: float,
            grace: float) -> Tuple[int, int]:
    """(attempted, failed).  Attempted: streams whose scheduled arrival
    lies in the window at least ``grace`` seconds before the close.
    Failed: those refused, given no chunk by the close, or served a
    non-finite chunk."""
    att = [s for s in logs if t_open <= s.arrival < t_close - grace]
    failed = [s for s in att if s.refused or not s.finite
              or not s.ready or s.ready[0] > t_close]
    return len(att), len(failed)


def end_to_end(logs: Sequence[StreamLog], cadence: float, top: float,
               t_open: float, t_close: float) -> Dict[str, float]:
    """The user-facing numbers, plus their sample counts.  Chunks per
    second counts the chunks ready in the window over its length."""
    share, n_due = cpr(logs, cadence, t_open, t_close)
    ttfc, n_arr = ttfc_mean(logs, t_open, t_close)
    gaps = chunk_gaps(logs, t_open, t_close)
    loss, n_ready = quality_loss(logs, top, t_open, t_close)
    return {"chunks_per_s": n_ready / (t_close - t_open), "cpr": share, "ttfc_mean_s": ttfc,
            "chunk_gap_p90_s": p90(gaps) if len(gaps) >= 2 else float("nan"),
            "quality_loss": loss, "chunks_due": n_due,
            "arrivals": n_arr, "chunks_ready": n_ready}
