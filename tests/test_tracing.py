"""The serving path's own spans and marks (``repro/tracing.py``), recorded
in a real ``jax.profiler`` trace of a reduced-config ``StreamingSession``
on the CPU: every span and its stats are there, one ``bmpr/select`` mark
per served chunk, launches hold their prep and sync, the control tick's
one timer still fills ``tick_times``, and a running trace leaves every
served chunk and fidelity log bit-identical."""
import dataclasses
import glob
import os
from typing import Dict, List, NamedTuple

import jax
import numpy as np
import pytest

from repro import tracing
from repro.configs.base import get_config
from repro.core.bmpr import StaticFidelity
from repro.core.fidelity import FidelityConfig
from repro.serve.batcher import BatchedChunkExecutor
from repro.serve.session import (SessionConfig, StreamingSession,
                                 uniform_specs)

FID = FidelityConfig(2, 0.0, 2, "bf16")
N_STREAMS, N_CHUNKS = 3, 3
LAYERS = ("session/", "batcher/", "bmpr/")


class Served(NamedTuple):
    chunks: Dict[int, List[np.ndarray]]
    logs: Dict[int, List[str]]
    tick_times: List[float]
    ticks: int                  # calls of ControlPlane.tick
    launches: int               # jitted steps dispatched while serving
    spans: list                 # the program's spans, where traced


def serve(profile_dir=None) -> Served:
    """Serve the same streams under one fixed fidelity; with
    ``profile_dir``, inside a profiler trace."""
    cfg = dataclasses.replace(get_config("ardit-self-forcing").reduced(),
                              n_layers=2, ardit_window_chunks=2)
    sess = StreamingSession(
        SessionConfig(verbose=False),
        executor=BatchedChunkExecutor(cfg=cfg, max_streams=4),
        fidelity_policy=StaticFidelity(FID))
    tick, calls = sess.control.tick, []

    def counted(view, now):
        calls.append(now)
        return tick(view, now)

    sess.control.tick = counted
    handles = [sess.submit(s) for s in uniform_specs(N_STREAMS, N_CHUNKS)]
    warm = sess.executor.dispatch_count         # the calibration chunk's
    if profile_dir is None:
        sess.run()
    else:
        jax.profiler.start_trace(profile_dir)
        try:
            sess.run()
        finally:
            jax.profiler.stop_trace()
    return Served({h.sid: [np.asarray(c) for c in h.chunks]
                   for h in handles},
                  {h.sid: list(h.fidelity_log) for h in handles},
                  sess.control.tick_times, len(calls),
                  sess.executor.dispatch_count - warm,
                  program_spans(profile_dir) if profile_dir else [])


def program_spans(directory):
    """(name, start ns, end ns, stats) of the program's host spans."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            out.extend((e.name, e.start_ns, e.end_ns, dict(e.stats))
                       for line in plane.lines for e in line.events
                       if e.name.startswith(LAYERS))
    return sorted(out, key=lambda s: s[1])


@pytest.fixture(scope="module")
def untraced():
    return serve()


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    return serve(str(tmp_path_factory.mktemp("profile")))


def named(spans, name):
    return [s for s in spans if s[0] == name]


def test_spans_and_their_stats_are_in_the_trace(traced):
    spans = traced.spans
    sids = set(range(N_STREAMS))
    assert named(spans, "session/tick")
    assert {s[3]["sid"] for s in named(spans, "session/arrival")} == sids
    assert {s[3]["sid"] for s in named(spans, "session/admit")} == sids
    launches = named(spans, "batcher/launch")
    assert len(launches) == traced.launches > 0
    for s in launches:
        assert 1 <= s[3]["rows"] <= N_STREAMS
        assert s[3]["quant"] == FID.quant
        assert 0 <= s[3]["clean"] <= s[3]["rows"]
    syncs = named(spans, "batcher/sync")
    assert syncs and all({"sid", "chunk"} <= set(s[3]) for s in syncs)


def test_launch_holds_its_prep_and_its_one_sync(traced):
    spans = traced.spans
    launches = named(spans, "batcher/launch")
    for name in ("batcher/prep", "batcher/sync"):
        for _, s, e, _ in named(spans, name):
            assert any(ls <= s and e <= le for _, ls, le, _ in launches)
    # every launch prepares once; a launch syncs once iff a row is in its
    # clean pass (the chunk completes there)
    preps = named(spans, "batcher/prep")
    syncs = named(spans, "batcher/sync")
    for _, ls, le, meta in launches:
        assert sum(ls <= s and e <= le for _, s, e, _ in preps) == 1
        assert (sum(ls <= s and e <= le for _, s, e, _ in syncs)
                == (meta["clean"] > 0))


def test_one_bmpr_select_mark_per_served_chunk(traced):
    chunks, spans = traced.chunks, traced.spans
    marks = named(spans, "bmpr/select")
    got = sorted((m[3]["sid"], m[3]["chunk"]) for m in marks)
    assert got == [(sid, i) for sid in sorted(chunks)
                   for i in range(len(chunks[sid]))]
    # a mark is an event, not a span of work: its enter and exit only
    assert all(m[3]["fid"] == FID.key and m[3]["mode"] == "static"
               and m[2] - m[1] < 1e6 for m in marks)
    # a stream's sync spans name the chunks its marks began
    syncs = {(s[3]["sid"], s[3]["chunk"])
             for s in named(spans, "batcher/sync")}
    assert syncs <= set(got)


def test_trace_leaves_served_chunks_bit_identical(untraced, traced):
    assert untraced.logs == traced.logs == {
        sid: [FID.key] * N_CHUNKS for sid in range(N_STREAMS)}
    for sid, chunks in untraced.chunks.items():
        assert len(chunks) == len(traced.chunks[sid]) == N_CHUNKS
        for a, b in zip(chunks, traced.chunks[sid]):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("which", ["untraced", "traced"])
def test_tick_times_one_entry_per_tick(which, untraced, traced):
    run = untraced if which == "untraced" else traced
    assert run.ticks > 0
    assert len(run.tick_times) == run.ticks
    assert all(t >= 0.0 for t in run.tick_times)
    if which == "traced":
        assert len(named(run.spans, "session/tick")) == run.ticks


def test_span_and_mark_without_a_trace():
    with tracing.span("batcher/prep", rows=2):
        pass
    assert tracing.mark("bmpr/select", sid=0, chunk=1, mode="quality",
                        fid="S2_r0.0_W7_fp8") is None
