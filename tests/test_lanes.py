"""Multi-lane real sessions: cross-lane migration (bit-exact KV move),
elastic SP2 (Ulysses head split, expand/release parity with the SP1
step), prompt-switch fresh conditioning, and the decision -> apply ->
metrics loop of the lane-aware StreamingSession.

All tests drive the jitted batched executor on a 2-layer config (same
budget as test_batcher/test_session)."""
import dataclasses

import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.bmpr import StaticFidelity
from repro.core.elastic_sp import SPDecision
from repro.core.fidelity import FidelityConfig
from repro.core.rehoming import Migration
from repro.sched_sim.metrics import summarize, transfer_stats
from repro.serve.batcher import BatchedChunkExecutor
from repro.serve.lanes import LanePool
from repro.serve.session import (SessionConfig, StreamingSession,
                                 uniform_specs)

FID = FidelityConfig(2, 0.0, 2, "bf16")


def tiny_cfg(window_chunks=2):
    return dataclasses.replace(
        get_config("ardit-self-forcing").reduced(),
        n_layers=2, ardit_window_chunks=window_chunks)


def gen_chunks(ex, sid, n=1, fid=FID, sp=False):
    """Drive one stream through n whole chunks on one executor
    (``sp=True`` = a reserved SP2 dispatch, the head-split path)."""
    out = []
    for _ in range(n):
        ex.begin_chunk(sid, fid, 0.0)
        while sid in ex.inflight:
            ex.run_step([sid], sp_serve=sp)
        out.append(np.asarray(ex.chunks[sid][-1]))
    return out


# ---------------------------------------------------------------------------
# cross-lane migration: a real KV move, bit-exact
# ---------------------------------------------------------------------------

def test_cross_lane_migration_kv_bit_exact():
    """Migrating a stream moves its pages into the destination lane's
    pool verbatim, subsequent chunks are bit-identical to a never-
    migrated run, and the move shows up on the shared transfer
    engine."""
    cfg = tiny_cfg()
    ref_ex = LanePool(1, cfg=cfg, max_streams=3).ex(0)
    ref_ex.admit(5, seed=0)
    ref = gen_chunks(ref_ex, 5, 4)

    lanes = LanePool(2, cfg=cfg, params=ref_ex.params, max_streams=3)
    lanes.admit(5, 0, seed=0)
    got = gen_chunks(lanes.ex(0), 5, 2)
    ctx_before = np.asarray(lanes.ex(0).pool.gather([5], 2)[0])
    n_log = len(lanes.engine.log)

    assert lanes.migrate(5, 0, 1)
    assert lanes.lane_of[5] == 1
    assert not lanes.ex(0).pool.resident(5)
    assert lanes.ex(1).pool.resident(5)
    lanes.ex(0).pool.ledger.check()
    lanes.ex(1).pool.ledger.check()
    # ONE src->dst transfer charged on the shared engine
    assert len(lanes.engine.log) == n_log + 1
    # the pages landed bit-exactly (same gathered context)
    ctx_after = np.asarray(lanes.ex(1).pool.gather([5], 2)[0])
    np.testing.assert_array_equal(ctx_before, ctx_after)

    got += gen_chunks(lanes.ex(1), 5, 2)
    for c, (a, b) in enumerate(zip(ref, got)):
        np.testing.assert_array_equal(
            a, b, err_msg=f"chunk {c} diverged across the migration")
    assert lanes.n_migrations == 1


def test_migration_refused_mid_chunk_or_wrong_lane():
    """The apply layer re-checks executor ground truth: a mid-chunk or
    wrongly-addressed migration decision is dropped, not applied."""
    cfg = tiny_cfg()
    lanes = LanePool(2, cfg=cfg, max_streams=3)
    lanes.admit(0, 0, seed=0)
    gen_chunks(lanes.ex(0), 0, 1)
    lanes.ex(0).begin_chunk(0, FID, 0.0)
    lanes.ex(0).run_step([0])                  # mid-chunk now
    assert not lanes.migrate(0, 0, 1)          # boundary only
    assert not lanes.migrate(0, 1, 0)          # stream is not on lane 1
    lanes.ex(0).abort_chunk(0)
    assert lanes.migrate(0, 0, 1)              # boundary: applies


# ---------------------------------------------------------------------------
# elastic SP2: head-split step parity, donor mirror, release
# ---------------------------------------------------------------------------

def test_sp2_expand_release_numerical_parity_with_sp1():
    """The Ulysses head-split SP2 step is bit-identical to the SP1 step
    (per-head attention never mixes heads and the donor's half mirrors
    the home pool verbatim), through expand, appends under SP, and
    release."""
    cfg = tiny_cfg()
    ref_ex = LanePool(1, cfg=cfg, max_streams=3).ex(0)
    ref_ex.admit(0, seed=0)
    ref = gen_chunks(ref_ex, 0, 4)

    lanes = LanePool(2, cfg=cfg, params=ref_ex.params, max_streams=3)
    ex0 = lanes.ex(0)
    lanes.admit(0, 0, seed=0)
    got = gen_chunks(ex0, 0, 1)
    assert lanes.sp_expand(0, 1)
    assert lanes.sp_link(0) is not None and lanes.sp_link(0).donor == 1
    # an UNRESERVED dispatch of a linked stream must stay on the SP1
    # step (donor compute is only consumed when the scheduler lent the
    # slot): the boundary it builds carries no SP marker
    ex0.begin_chunk(0, FID, 0.0)
    ex0.run_step([0])
    assert all(k[-1] is None for k in ex0._boundary_cache)
    ex0.abort_chunk(0)
    got += gen_chunks(ex0, 0, 2, sp=True)      # SP2 chunks (incl. appends)

    # donor mirror: the donor pool's page set holds exactly the home
    # pool's upper half heads (kept in lockstep by the SP append)
    h2 = cfg.n_kv_heads // 2
    rows_h = ex0.pool.ledger.tables[0]
    rows_d = lanes.ex(1).pool.ledger.tables[0]
    for pool_h, pool_d in ((ex0.pool.k, lanes.ex(1).pool.k),
                           (ex0.pool.v, lanes.ex(1).pool.v)):
        np.testing.assert_array_equal(
            np.asarray(pool_h[:, rows_h])[:, :, h2:],
            np.asarray(pool_d[:, rows_d])[:, :, h2:])

    lanes.sp_release(0)
    assert lanes.sp_link(0) is None
    lanes.ex(1).pool.ledger.check()            # donor pages freed cleanly
    got += gen_chunks(ex0, 0, 1)               # back on the SP1 step
    for c, (a, b) in enumerate(zip(ref, got)):
        np.testing.assert_array_equal(
            a, b, err_msg=f"chunk {c}: SP2 diverged from the SP1 step")
    assert lanes.n_sp_expands == 1 and lanes.n_sp_releases == 1


def test_sp_mirror_protected_from_donor_pool_eviction():
    """Regression: the donor lane's eviction paths saw a live SP
    half-head mirror as an ordinary (non-inflight) resident and could
    evict it mid-borrow, breaking the linked SP2 step."""
    from repro.core.types import Stream
    cfg = tiny_cfg()
    lanes = LanePool(2, cfg=cfg, max_streams=2)
    streams = {}
    for sid, lane, ddl in ((0, 0, 9.0), (10, 1, 5.0), (11, 1, 4.0)):
        lanes.admit(sid, lane, seed=sid)
        s = Stream(sid=sid, arrival=0.0, target_chunks=8,
                   chunk_seconds=1.0, home=lane, ttfc_slack=1.0)
        s.credit = ddl          # sid 0 has the HIGHEST credit: the
        streams[sid] = s        # pre-fix pick would evict its mirror
    gen_chunks(lanes.ex(0), 0, 1)
    # donor pool (lane 1) is full: expansion evicts a donor resident,
    # then mirrors stream 0's upper heads there
    assert lanes.sp_expand(0, 1, streams)
    assert 0 in lanes.ex(1).sp_mirrors
    assert lanes.ex(1).pool.resident(0)
    # fresh pressure on the donor pool must NOT pick the mirror
    lanes.ex(1).admit(12, seed=12, streams=streams)
    streams[12] = streams[11]
    assert lanes.ex(1).pool.resident(0), \
        "live SP mirror was evicted from the donor pool"
    # the SP2 step still runs (and the mirror is released cleanly)
    gen_chunks(lanes.ex(0), 0, 1, sp=True)
    lanes.sp_release(0)
    assert 0 not in lanes.ex(1).sp_mirrors
    lanes.ex(1).pool.ledger.check()


def test_deferred_sp_release_blocks_same_tick_donor_reuse():
    """Regression: a release deferred to the next safe boundary (its
    stream mid-chunk) left the donor physically borrowed, but the
    planner's same-tick rejoin could re-grant it — and the deferred
    apply_release would then clear the NEW borrower's donated_to."""
    from repro.core.control_plane import TickDecisions
    from repro.core import elastic_sp
    cfg = tiny_cfg()
    sess = StreamingSession(
        SessionConfig(lanes=2, model_cfg=cfg, pool_streams=3,
                      verbose=False),
        fidelity_policy=StaticFidelity(FID))
    sess._t0 = 0.0
    sess.submit(uniform_specs(2, 4)[0])
    sess.submit(uniform_specs(2, 4)[1])
    sess._drain_events(0.0)                   # admit both
    h0 = sess.view.streams[0].home
    donor = 1 - h0
    gen_chunks(sess.lanes.ex(h0), 0, 1)
    assert sess.lanes.sp_expand(0, donor, sess.view.streams)
    elastic_sp.apply_expand(sess.view, SPDecision(0, donor, "expand"))
    # stream 0 goes mid-chunk: its release must defer
    sess.lanes.ex(h0).begin_chunk(0, FID, 0.0)
    sess._apply_decisions(TickDecisions(
        migrations=[],
        sp_decisions=[SPDecision(0, donor, "release"),
                      SPDecision(1, donor, "expand")],
        control_time_s=0.0))
    # the deferred release is pending, the donor was NOT re-granted
    assert sess._pending_sp_release == {0: donor}
    assert sess.view.workers[donor].donated_to == 0
    assert sess.view.streams[1].sp_donor is None
    assert sess.lanes.sp_link(1) is None


def test_sp_expand_rejected_on_gather_backend():
    """The head split rides the paged step; on the gather backend the
    expand decision is dropped (and may be re-planned), never applied
    half-way."""
    lanes = LanePool(2, cfg=tiny_cfg(), max_streams=3,
                     context_backend="gather")
    lanes.admit(0, 0, seed=0)
    gen_chunks(lanes.ex(0), 0, 1)
    assert not lanes.sp_expand(0, 1)
    assert lanes.sp_link(0) is None


# ---------------------------------------------------------------------------
# prompt switch: fresh conditioning through KVPool.admit
# ---------------------------------------------------------------------------

def test_prompt_switch_serves_fresh_conditioning():
    """Regression (the old session kept the stale cond embedding): the
    post-switch chunk must differ from the no-switch chunk and match a
    fresh stream's first chunk under the new conditioning seed
    bit-exactly."""
    cfg = tiny_cfg()
    ex = BatchedChunkExecutor(cfg=cfg, max_streams=3)
    ex.admit(7, seed=7)
    gen_chunks(ex, 7, 1)
    assert ex.reset_condition(7, seed=777)
    ex.pool.ledger.check()
    post = gen_chunks(ex, 7, 1)[0]

    no_switch = BatchedChunkExecutor(cfg=cfg, params=ex.params,
                                     max_streams=3)
    no_switch.admit(7, seed=7)
    gen_chunks(no_switch, 7, 1)
    stale = gen_chunks(no_switch, 7, 1)[0]
    assert not np.array_equal(post, stale), \
        "post-switch chunk still serves the OLD conditioning"

    fresh = BatchedChunkExecutor(cfg=cfg, params=ex.params, max_streams=3)
    fresh.admit(7, seed=777)
    first = gen_chunks(fresh, 7, 1)[0]
    np.testing.assert_array_equal(
        post, first, err_msg="post-switch chunk is not bit-identical to "
                             "a fresh stream under the new conditioning")


def test_session_prompt_switch_resets_condition_and_completes():
    """Session wiring of the fix: a switch event re-encodes the cond
    (switch counter advances, seed derivable) and the stream still
    completes its chunk target."""
    from repro.sched_sim.workloads import StreamSpec
    sess = StreamingSession(
        SessionConfig(lanes=1, model_cfg=tiny_cfg(), pool_streams=3,
                      verbose=False),
        fidelity_policy=StaticFidelity(FID))
    # the switch lands 20 ms in — well inside a 4-chunk stream on any
    # host (a single tiny-model chunk takes longer than that)
    h = sess.submit(StreamSpec(0, 0.0, 48, switches=(0.02,)))
    sess.run()
    assert h.done and h.chunks_ready == 4
    assert sess._switches.get(0) == 1
    assert sess.switch_seed(0) == 0 + 100003


# ---------------------------------------------------------------------------
# the lane-aware session: decisions -> apply -> metrics, bit-identical
# ---------------------------------------------------------------------------

def test_multi_lane_session_applies_decisions_bit_identically():
    """A 2-lane session that REALLY migrates one stream and REALLY
    expands+releases SP on another produces, under a fixed fidelity,
    chunks bit-identical to the single-lane session — the acceptance
    bar for the real decision apply layer — and reports the applied
    counts on the metrics surface."""
    cfg = tiny_cfg()
    n, chunks = 2, 3
    ref = StreamingSession(
        SessionConfig(lanes=1, model_cfg=cfg, pool_streams=n + 1,
                      verbose=False),
        fidelity_policy=StaticFidelity(FID))
    for spec in uniform_specs(n, chunks):
        ref.submit(spec)
    ref.run()
    ref_chunks = {i: [np.asarray(c) for c in ref.handles[i].chunks]
                  for i in range(n)}

    sess = StreamingSession(
        SessionConfig(lanes=2, model_cfg=cfg, pool_streams=n + 1,
                      verbose=False),
        fidelity_policy=StaticFidelity(FID))
    for spec in uniform_specs(n, chunks):
        sess.submit(spec)

    # force one migration and one SP expand/release through the SAME
    # tick -> apply path the control plane uses (the planner's own
    # trigger conditions are load-dependent; the apply layer is what
    # this test pins)
    state = {"mig": False, "sp": False, "rel": False}
    orig_tick = sess.control.tick

    def tick(view, now):
        d = orig_tick(view, now)
        s0, s1 = view.streams.get(0), view.streams.get(1)
        if (not state["mig"] and s0 is not None and s0.chunks_done >= 1
                and not s0.done and not sess.lanes.is_inflight(0)):
            src = sess.lanes.lane_of[0]
            d.migrations.append(Migration(0, src, 1 - src,
                                          cross_node=False))
            state["mig"] = True
        if (not state["sp"] and s1 is not None and s1.chunks_done >= 1
                and not s1.done
                and sess.lanes.ex(sess.lanes.lane_of[1]).pool.resident(1)):
            d.sp_decisions.append(
                SPDecision(1, 1 - sess.lanes.lane_of[1], "expand"))
            state["sp"] = True
        elif (state["sp"] and not state["rel"] and s1 is not None
                and not s1.done and s1.sp_donor is not None
                and s1.chunks_done >= 2):
            d.sp_decisions.append(SPDecision(1, s1.sp_donor, "release"))
            state["rel"] = True
        return d

    sess.control.tick = tick
    res = sess.run()

    assert res.n_migrations_applied >= 1
    assert res.n_sp_expands_applied >= 1
    assert res.n_sp_releases_applied >= 1      # explicit or at retire
    # view bookkeeping followed the applies: stream 0 lives on its new
    # home lane, every donor was returned
    assert sess.lanes.lane_of[0] == 1 - res.streams[0].home or \
        res.streams[0].home == sess.lanes.lane_of[0]
    assert all(w.donated_to is None for w in sess.view.workers)
    for ex in sess.lanes.executors:
        ex.pool.ledger.check()
    for i in range(n):
        got = [np.asarray(c) for c in sess.handles[i].chunks]
        assert len(got) == chunks
        for c in range(chunks):
            np.testing.assert_array_equal(
                ref_chunks[i][c], got[c],
                err_msg=f"stream {i} chunk {c} diverged from the "
                        f"single-lane session")
    # one metrics surface: transfers (migration + SP half) on the
    # shared engine, Summary fields well-defined
    assert transfer_stats(res)["n"] == len(res.engine.log) >= 2
    s = summarize(res)
    assert s.n_chunks == n * chunks and 0.0 <= s.qoe <= 1.0


def test_multi_lane_session_oversubscribed_completes():
    """2 lanes x 2-resident pools serving 6 streams: per-lane
    credit-aware eviction keeps rotating everyone through and the
    session completes (the PR 2 oversubscription guarantee holds per
    lane)."""
    n, chunks = 6, 2
    sess = StreamingSession(
        SessionConfig(lanes=2, model_cfg=tiny_cfg(), pool_streams=2,
                      max_batch=2, verbose=False),
        fidelity_policy=StaticFidelity(FID))
    for spec in uniform_specs(n, chunks):
        sess.submit(spec)
    res = sess.run()
    assert all(res.streams[i].chunks_done == chunks for i in range(n))
    assert len(sess.view.workers) == 2
    for ex in sess.lanes.executors:
        ex.pool.ledger.check()
