"""The system under test, driven for one bounded window.

``BenchSession`` is the program's ``StreamingSession`` with the benchmark's
instruments around the calls into each layer: it stamps every chunk on the
benchmark's clock once the chunk's array is ready, times each control
tick, counts launches, notes spills and restores, and records how late
arrival events were handled.  Under ``--trace 1`` each of those calls is
also a ``jax.profiler.TraceAnnotation`` named ``bench/<layer>``.  It ends
serving at the window's close (``run`` serves every stream to completion
otherwise).  With ``live_cap`` it holds an arrival, on its scheduled
arrival time, until fewer than that many streams are live: an admission
gate in front of the server, as a deployment that never oversubscribes
its pool would run; the wait counts in the stream's TTFC.

``warm_up`` compiles, before the window, every program the window can
launch: one launch of each (batch size, ring extent, mask kind) the fused
paged step can meet, the appends of one and two rows in bf16 and fp8 KV,
and one spill and restore of a stream where the window can spill.
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable, Dict, List, Optional, Set, Tuple

import jax

from repro.core.fidelity import FidelityConfig
from repro.serve.session import StreamingSession

from bench.harness import CyclingFidelity

WARM_SID = 1_000_000            # warm-up streams: far above traffic sids


def _annotate(on: bool, name: str):
    return (jax.profiler.TraceAnnotation(name) if on
            else contextlib.nullcontext())


class BenchSession(StreamingSession):
    """``StreamingSession`` bounded to [0, close_at) on its own clock, with
    the benchmark's instruments.  The window is [open_at, close_at)."""

    def __init__(self, config, *, open_at: float, close_at: float,
                 annotate: bool, on_open: Callable[[], None],
                 live_cap: Optional[int] = None):
        self.open_at, self.close_at = open_at, close_at
        self.live_cap = live_cap
        self.gated: List[Tuple[int, float]] = []
        self.annotate = annotate
        self._on_open = on_open
        self.in_window = False
        self.ready: Dict[int, List[float]] = {}
        self.arrival_lag: List[float] = []
        self.tick_s: List[float] = []
        self.launches: List[List[Tuple[int, str, str]]] = []
        self.chunk_batches: Dict[Tuple[int, int], Set[Tuple[int, bool]]] = {}
        self.spilled: Set[int] = set()
        super().__init__(config)
        tick = self.control.tick

        def timed_tick(view, now):
            t0 = time.perf_counter()
            with _annotate(self.annotate, "bench/control_tick"):
                out = tick(view, now)
            if self.in_window:
                self.tick_s.append(time.perf_counter() - t0)
            return out

        self.control.tick = timed_tick
        for ex in self.lanes.all_executors:
            self._instrument(ex)

    def _instrument(self, ex) -> None:
        run_step, admit = ex.run_step, ex.admit
        evict, restore = ex.pool.evict, ex.pool.restore

        def bench_run_step(sids, sp_serve=False):
            rows = [(ex.pool.chunks[s], ex.inflight[s].fidelity.key,
                     ex.inflight[s].phase) for s in sids]
            mixed = len({k for _, k, _ in rows}) > 1
            for s, (n, _, _) in zip(sids, rows):
                self.chunk_batches.setdefault((s, n), set()).add(
                    (len(sids), mixed))
            with _annotate(self.annotate, "bench/run_step"):
                out = run_step(sids, sp_serve)
            if self.in_window:
                self.launches.append(rows)
            return out

        def bench_admit(sid, *a, **kw):
            with _annotate(self.annotate, "bench/admit"):
                return admit(sid, *a, **kw)

        def bench_evict(sid):
            self.spilled.add(sid)
            with _annotate(self.annotate, "bench/spill"):
                return evict(sid)

        def bench_restore(sid, **kw):
            with _annotate(self.annotate, "bench/restore"):
                return restore(sid, **kw)

        ex.run_step, ex.admit = bench_run_step, bench_admit
        ex.pool.evict, ex.pool.restore = bench_evict, bench_restore

    # ---- the clock and the window ------------------------------------------
    def clock(self) -> float:
        return self._now()

    def _all_done(self) -> bool:
        now = self._now()
        if not self.in_window and now >= self.open_at:
            self.in_window = True
            self._on_open()
        while self.gated and self._live() < self.live_cap:
            sid, t_arr = self.gated.pop(0)
            with _annotate(self.annotate, "bench/arrival"):
                super()._on_arrival(sid, t_arr)
        return now >= self.close_at or super()._all_done()

    def _live(self) -> int:
        fd = self.front_door
        return (sum(not s.done for s in self.view.streams.values())
                + (len(fd.waiting) if fd is not None else 0))

    def _on_arrival(self, sid: int, t_arr: float) -> None:
        self.arrival_lag.append(self._now() - t_arr)
        if (self.live_cap is not None
                and (self.gated or self._live() >= self.live_cap)):
            self.gated.append((sid, t_arr))
            return
        with _annotate(self.annotate, "bench/arrival"):
            super()._on_arrival(sid, t_arr)

    def _complete_chunk(self, sid, fid, started, now) -> None:
        self.lanes.chunks_of(sid)[-1].block_until_ready()
        self.ready.setdefault(sid, []).append(self._now())
        super()._complete_chunk(sid, fid, started, now)


# ---------------------------------------------------------------------------
# warm-up
# ---------------------------------------------------------------------------

def _run(ex, sids, fids, fills, one_launch: bool) -> None:
    """Run ``sids`` as one fused group at the given fills (set directly on
    the ledger: the warm-up needs the shapes, not the KV), either for one
    launch (then abort) or until every row's chunk completes."""
    for sid, fid, n in zip(sids, fids, fills):
        ex.pool.ledger.chunks[sid] = n
        ex.begin_chunk(sid, fid, 0.0)
    while any(s in ex.inflight for s in sids):
        ex.run_step([s for s in sids if s in ex.inflight])
        if one_launch:
            for s in sids:
                ex.abort_chunk(s)


def warm_up(session: StreamingSession, max_batch: int,
            spill: bool = True) -> int:
    """Compile everything the window can launch on the session's
    executor (a spill and restore only where ``spill``: behind a gate
    at the pool's size nothing spills); returns the launches it made.
    Leaves the pool empty and the latency EMAs as the session's own
    warm-up chunk set them."""
    ex = session.executor
    prior = (dict(ex.latency_ema), dict(ex.step_ema))
    n0 = ex.dispatch_count
    w_max = ex.cfg.ardit_window_chunks
    b_max = min(max_batch, ex.max_streams)
    full = FidelityConfig(2, 0.0, w_max, "bf16")      # all visible
    narrow = FidelityConfig(2, 0.0, 1, "fp8")          # window < fill
    sparse = FidelityConfig(2, 0.6, w_max, "bf16")     # rho: dn != cl
    cyc = CyclingFidelity([full, narrow, sparse], session._profile)
    sids = [WARM_SID + j for j in range(b_max)]
    for sid in sids:
        ex.admit(sid, seed=sid)
    for b in range(1, b_max + 1):
        grp = sids[:b]
        for r in range(w_max + 1):
            for _ in range(3 if r else 1):
                fid = cyc.select(0.0).fidelity
                # with the narrow window the second row trails by one
                # chunk: a batch whose fills differ
                fills = [r] + [max(r - (fid is narrow), 0)] * (b - 1)
                _run(ex, grp, [fid] * b, fills, one_launch=True)
        cyc = CyclingFidelity([full, narrow, sparse], session._profile)
    # appends: one and two rows, bf16 and fp8, each row completing alone
    s3 = FidelityConfig(3, 0.0, w_max, "bf16")
    s3q = FidelityConfig(3, 0.0, w_max, "fp8")
    _run(ex, sids[:1], [full], [0], one_launch=False)
    _run(ex, sids[:1], [narrow], [0], one_launch=False)
    if b_max >= 2:
        _run(ex, sids[:2], [full, full], [0, 0], one_launch=False)
        _run(ex, sids[:2], [narrow, narrow], [0, 0], one_launch=False)
        _run(ex, sids[:2], [full, s3], [0, 0], one_launch=False)
        _run(ex, sids[:2], [s3q, narrow], [0, 0], one_launch=False)
    if spill:
        # a spill of a resident and its restore (host round trip)
        ex.pool.evict(sids[0])
        ex.pool.restore(sids[0])
    for sid in sids:
        ex.retire(sid, drop_history=True)
    ex.latency_ema.clear()
    ex.latency_ema.update(prior[0])
    ex.step_ema.clear()
    ex.step_ema.update(prior[1])
    jax.block_until_ready((ex.pool.k, ex.pool.v))
    return ex.dispatch_count - n0
