"""Model plane: registry-backed heterogeneous co-serving.

Fast tier: bundle resolution + geometry, (model, kv_dtype) sub-batch
grouping, weighted placement (``Worker.load`` / ``choose_home``),
per-model Summary rows, the keyed front-door service EMAs (single-key
bit-identity AND the low-fidelity over-reject regression), and the
mixed-model workload generator.

Slow tier: a live 2-model co-serve session whose per-model chunks
match each model's SOLO session within the repo's batched-parity
tolerance (allclose 1e-5), with zero unserved streams and per-model
Summary rows; plus single-bundle degeneration (bit-identical chunks to
the legacy single-model session path)."""
import dataclasses
import types

import numpy as np
import pytest

from repro.core.control_plane import ControlPlane
from repro.core.fidelity import FidelityConfig
from repro.core.types import ClusterView, Stream, Worker
from repro.sched_sim.frontdoor import FrontDoor, FrontDoorConfig
from repro.sched_sim.metrics import summarize
from repro.sched_sim.workloads import mixed_models, steady
from repro.serve.batcher import KVPool, compose_batch

FID = FidelityConfig(2, 0.0, 2, "bf16")
MODELS = ["ardit-self-forcing", "ardit-causal-forcing"]


# ---------------------------------------------------------------------------
# bundle resolution
# ---------------------------------------------------------------------------

class TestResolveBundles:
    def test_geometry_and_primary_normalization(self):
        from repro.serve.modelplane import resolve_bundles
        bundles = resolve_bundles(MODELS)
        assert [b.name for b in bundles] == MODELS
        primary = bundles[0]
        assert primary.step_cost == 1.0 and primary.page_cost == 1.0
        assert primary.placement_weight == 1.0
        for b in bundles:
            # sink page + ring pages, page fits cond AND one chunk
            assert b.pages_per_stream == 1 + b.cfg.ardit_window_chunks
            assert b.page_tokens > 0 and b.page_bytes > 0
            assert b.stream_bytes == b.pages_per_stream * b.page_bytes
            # ... and is exactly what a pool allocates per stream (K + V)
            pool = KVPool(b.cfg, b.params, max_streams=2)
            assert pool.k.nbytes + pool.v.nbytes == 2 * b.stream_bytes
            assert b.params is not None and b.profile is not None
        # both reduced ardit configs share geometry -> equal page cost
        assert bundles[1].page_cost == pytest.approx(1.0)

    def test_rejects_empty_duplicates_and_non_ardit(self):
        from repro.serve.modelplane import resolve_bundle, resolve_bundles
        with pytest.raises(ValueError):
            resolve_bundles([])
        with pytest.raises(ValueError, match="duplicate"):
            resolve_bundles(["ardit-self-forcing", "ardit-self-forcing"])
        with pytest.raises(ValueError, match="ardit-family"):
            resolve_bundle("mamba2-780m")

    def test_profile_name_mapping(self):
        from repro.serve.modelplane import profile_name_of
        assert profile_name_of("ardit-self-forcing") == "self-forcing"
        assert profile_name_of("ardit-causal-forcing") == "causal-forcing"
        assert profile_name_of("mamba2-780m") == "mamba2-780m"


# ---------------------------------------------------------------------------
# (model, kv_dtype) sub-batch grouping
# ---------------------------------------------------------------------------

class TestComposeBatchModelGrouping:
    FIDS = {0: FidelityConfig(4, 0.0, 7, "bf16"),
            1: FidelityConfig(4, 0.0, 7, "bf16"),
            2: FidelityConfig(2, 0.5, 5, "bf16"),
            3: FidelityConfig(2, 0.5, 5, "fp8")}

    def test_no_model_of_is_legacy(self):
        legacy = compose_batch([0, 1, 2, 3], self.FIDS.get, 4)
        explicit = compose_batch([0, 1, 2, 3], self.FIDS.get, 4,
                                 model_of=None)
        assert legacy == explicit

    def test_models_split_groups(self):
        model_of = {0: "a", 1: "b", 2: "a", 3: "a"}.get
        groups = compose_batch([0, 1, 2, 3], self.FIDS.get, 4,
                               model_of=model_of)
        # same fidelity but different model never shares a group
        assert [0] in groups and [1] in groups
        for grp in groups:
            assert len({model_of(s) for s in grp}) == 1

    def test_fused_groups_by_model_and_dtype(self):
        model_of = {0: "a", 1: "a", 2: "a", 3: "a"}.get
        groups = compose_batch([0, 1, 2, 3], self.FIDS.get, 4,
                               fuse=True, model_of=model_of)
        # one model, two dtypes -> exactly two fused groups
        assert sorted(map(sorted, groups)) == [[0, 1, 2], [3]]


# ---------------------------------------------------------------------------
# weighted placement
# ---------------------------------------------------------------------------

class TestWeightedPlacement:
    def _worker(self, wid, queue=(), running=None, donated=None):
        w = Worker(wid, node=0)
        w.queue = list(queue)
        w.running = running
        w.donated_to = donated
        return w

    def test_load_none_is_legacy_integer(self):
        w = self._worker(0, queue=[1, 2], running=3, donated=4)
        assert w.load() == 4
        assert isinstance(w.load(), int)

    def test_load_weighted_sums_stream_weights(self):
        w = self._worker(0, queue=[1, 2], running=3)
        weight = {1: 1.0, 2: 2.5, 3: 0.5}.get
        assert w.load(lambda sid: weight(sid)) == pytest.approx(4.0)

    def test_choose_home_unweighted_parity(self):
        workers = [self._worker(0, queue=[1, 2]), self._worker(1, queue=[3])]
        view = ClusterView({}, workers, 2)
        assert view.stream_weight is None
        assert ControlPlane().choose_home(view) == 1

    def test_choose_home_weighs_heavy_models(self):
        # worker 0 holds ONE heavy stream, worker 1 TWO light ones: the
        # integer argmin would pick worker 0, the weighted one must not
        workers = [self._worker(0, queue=[10]),
                   self._worker(1, queue=[11, 12])]
        view = ClusterView({}, workers, 2)
        assert ControlPlane().choose_home(view) == 0
        view.stream_weight = lambda sid: 5.0 if sid == 10 else 1.0
        assert ControlPlane().choose_home(view) == 1


# ---------------------------------------------------------------------------
# per-model Summary rows
# ---------------------------------------------------------------------------

def _stream(sid, model, arrival=0.0, ready=(1.0,), deadlines=(2.0,)):
    s = Stream(sid=sid, arrival=arrival, target_chunks=len(ready),
               chunk_seconds=1.0, home=0, ttfc_slack=1.0)
    s.model = model
    s.ready_times = list(ready)
    s.deadlines = list(deadlines)
    s.first_chunk_time = ready[0] if ready else None
    s.qualities = [80.0] * len(ready)
    return s


class TestSummaryByModel:
    def test_rows_keyed_by_model(self):
        res = types.SimpleNamespace(streams={
            0: _stream(0, "a", ready=(1.0, 2.0), deadlines=(2.0, 3.0)),
            1: _stream(1, "b", ready=(3.0,), deadlines=(2.0,)),  # late
            2: _stream(2, "a", ready=(1.5,), deadlines=(2.0,)),
        })
        summ = summarize(res)
        assert set(summ.by_model) == {"a", "b"}
        assert summ.by_model["a"]["cpr"] == 1.0
        assert summ.by_model["b"]["cpr"] == 0.0
        assert summ.by_model["a"]["n_streams"] == 2
        assert summ.by_model["a"]["streams_per_s"] > 0
        assert len(summ.model_rows()) == 2

    def test_untagged_streams_yield_no_rows(self):
        res = types.SimpleNamespace(streams={
            0: _stream(0, None), 1: _stream(1, None)})
        summ = summarize(res)
        assert summ.by_model == {}
        assert summ.model_rows() == []


# ---------------------------------------------------------------------------
# keyed front-door service EMAs (satellite: over-reject regression)
# ---------------------------------------------------------------------------

def _view(load=0, n_workers=2):
    workers = []
    for w in range(n_workers):
        worker = Worker(w, node=0)
        worker.queue = list(range(load))
        workers.append(worker)
    return ClusterView({}, workers, n_workers)


class TestKeyedServiceEMA:
    def test_single_key_traffic_bit_identical_to_global(self):
        fd = FrontDoor(FrontDoorConfig(), first_chunk_estimate=1.0)
        kd = FrontDoor(FrontDoorConfig(), first_chunk_estimate=1.0)
        for v in (0.5, 0.7, 0.3, 0.9, 0.4):
            fd.observe_chunk(v)                          # legacy keyless
            kd.observe_chunk(v, fidelity="S4", model="m")
        assert kd.chunk_service_ema == fd.chunk_service_ema
        # the keyed recurrence reproduces the global one EXACTLY
        assert kd.expected_service() == kd.chunk_service_ema
        assert kd.predict_ttfc(_view(load=3)) == \
            fd.predict_ttfc(_view(load=3))

    def test_no_observations_falls_back_to_global(self):
        fd = FrontDoor(FrontDoorConfig(), first_chunk_estimate=1.0)
        assert fd.expected_service() == fd.chunk_service_ema
        assert fd.predict_ttfc(_view(load=5)) == \
            5 * fd.chunk_service_ema + 1.0

    def test_low_fidelity_heavy_fleet_no_longer_over_rejects(self):
        """Regression (the satellite's motivating scenario): a fleet
        serving mostly cheap low-fidelity chunks, with a couple of
        RECENT slow high-fidelity completions.  The old single global
        EMA is dragged to the recent expensive observations and
        over-predicts TTFC -> over-rejects; the observation-weighted
        keyed mix stays near the traffic's real cost -> admits."""
        fd = FrontDoor(FrontDoorConfig(autoscale=False, queue_limit=0),
                       first_chunk_estimate=1.0)
        for _ in range(20):
            fd.observe_chunk(0.1, fidelity="S1_lo")
        for _ in range(2):
            fd.observe_chunk(1.0, fidelity="S4_hi")
        view = _view(load=8)
        slo = fd.slo_ttfc()
        old_prediction = 8 * fd.chunk_service_ema + fd.first_est
        new_prediction = fd.predict_ttfc(view)
        # the single global EMA would have over-predicted past the SLO
        assert old_prediction > slo
        # the keyed mix tracks the 20:2 cheap-heavy traffic ratio
        assert new_prediction < old_prediction
        assert new_prediction <= slo
        dec = fd.on_arrival(view, 23.0, 1.0, sid=0)
        assert dec.action == "admit"

    def test_per_model_keys_are_distinct(self):
        fd = FrontDoor(FrontDoorConfig(), first_chunk_estimate=1.0)
        fd.observe_chunk(0.1, fidelity="S4", model="light")
        fd.observe_chunk(1.0, fidelity="S4", model="heavy")
        assert fd._service_emas[("light", "S4")] != \
            fd._service_emas[("heavy", "S4")]


# ---------------------------------------------------------------------------
# mixed-model workload generator
# ---------------------------------------------------------------------------

class TestMixedModelsWorkload:
    def test_arrivals_match_steady_and_models_are_tagged(self):
        base = steady(n=20, rate=1.0, seed=3)
        mixed = mixed_models(n=20, rate=1.0, seed=3)
        assert [s.arrival for s in mixed] == [s.arrival for s in base]
        assert [s.frames for s in mixed] == [s.frames for s in base]
        assert all(s.model in ("causal-forcing", "self-forcing")
                   for s in mixed)
        assert len({s.model for s in mixed}) == 2
        # deterministic per seed
        again = mixed_models(n=20, rate=1.0, seed=3)
        assert [s.model for s in again] == [s.model for s in mixed]

    def test_weights_bias_the_draw(self):
        mixed = mixed_models(n=200, rate=1.0, seed=0,
                             models=("a", "b"), weights=(9.0, 1.0))
        n_a = sum(1 for s in mixed if s.model == "a")
        assert n_a > 150
        with pytest.raises(ValueError):
            mixed_models(n=4, models=())

    def test_simulator_attributes_model_and_cost(self):
        """Tagged streams carry their model into the Stream record and
        a heavier model's chunks take proportionally longer."""
        from repro.profiler.profiles import MODEL_COST
        from repro.sched_sim.policies import make_policy
        from repro.sched_sim.simulator import SimConfig, Simulator
        specs = [dataclasses.replace(s, model=m) for s, m in zip(
            steady(n=4, rate=5.0, seed=0),
            ["causal-forcing", "minitron-8b"] * 2)]
        cfg = SimConfig(n_workers=2, max_time=2e4)
        res = Simulator(cfg, specs, make_policy("slackserve")).run()
        summ = summarize(res)
        assert set(summ.by_model) == {"causal-forcing", "minitron-8b"}
        for s in res.streams.values():
            assert s.model in ("causal-forcing", "minitron-8b")
        assert MODEL_COST["minitron-8b"] > 1.0


# ---------------------------------------------------------------------------
# live co-serving sessions (slow tier: JAX-compiling)
# ---------------------------------------------------------------------------

def _tagged_specs(n, chunks, models):
    from repro.serve.session import uniform_specs
    return [dataclasses.replace(sp, model=models[i % len(models)])
            for i, sp in enumerate(uniform_specs(n, chunks))]


def _run_session(models, specs, pool=8):
    from repro.core.bmpr import StaticFidelity
    from repro.serve.session import SessionConfig, StreamingSession
    session = StreamingSession(
        SessionConfig(executor="batched", models=list(models),
                      pool_streams=pool, verbose=False),
        fidelity_policy=StaticFidelity(FID))
    handles = [session.submit(sp) for sp in specs]
    res = session.run()
    return session, handles, res


@pytest.mark.slow
def test_co_serve_session_matches_solo_runs():
    """A 2-model co-serve session completes with zero unserved streams,
    keeps every sub-batch same-model, reports per-model Summary rows,
    and generates chunks matching each model's SOLO session within the
    repo's batched-parity tolerance."""
    specs = _tagged_specs(4, 2, MODELS)
    _, co_handles, co_res = _run_session(MODELS, specs)
    co_summ = summarize(co_res)
    assert co_summ.n_unserved == 0
    assert set(co_summ.by_model) == set(MODELS)
    for m in MODELS:
        assert co_summ.by_model[m]["n_streams"] == 2
        assert co_summ.by_model[m]["n_chunks"] == 4

    co_chunks = {h.sid: [np.asarray(c) for c in h.chunks]
                 for h in co_handles}
    for m in MODELS:
        solo_specs = [sp for sp in specs if sp.model == m]
        _, solo_handles, solo_res = _run_session([m], solo_specs)
        assert summarize(solo_res).n_unserved == 0
        for h in solo_handles:
            assert len(co_chunks[h.sid]) == len(h.chunks) == 2
            for got, ref in zip(co_chunks[h.sid], h.chunks):
                np.testing.assert_allclose(got, np.asarray(ref),
                                           rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_single_bundle_session_degenerates_to_legacy():
    """models=[one ardit config] produces chunks BIT-identical to the
    legacy model_cfg single-model path (same seeds, fixed fidelity)."""
    from repro.configs.base import get_config
    from repro.core.bmpr import StaticFidelity
    from repro.serve.session import (SessionConfig, StreamingSession,
                                     uniform_specs)
    specs = uniform_specs(2, 2)
    _, handles_a, _ = _run_session(["ardit-causal-forcing"], specs)
    legacy = StreamingSession(
        SessionConfig(executor="batched",
                      model_cfg=get_config("ardit-causal-forcing")
                      .reduced(),
                      pool_streams=8, verbose=False),
        fidelity_policy=StaticFidelity(FID))
    handles_b = [legacy.submit(sp) for sp in specs]
    legacy.run()
    for ha, hb in zip(handles_a, handles_b):
        assert len(ha.chunks) == len(hb.chunks) == 2
        for ca, cb in zip(ha.chunks, hb.chunks):
            assert np.array_equal(np.asarray(ca), np.asarray(cb))


@pytest.mark.slow
def test_same_model_only_migration_routing():
    """LanePool resolves migration src/dst through the stream's OWN
    bundle: after a cross-lane migration of a non-primary stream its
    pages live in the non-primary pool of the destination lane."""
    from repro.serve.lanes import LanePool
    from repro.serve.modelplane import resolve_bundles
    bundles = resolve_bundles(MODELS)
    lanes = LanePool(2, seed=0, max_streams=4, bundles=bundles)
    other = MODELS[1]
    lanes.admit(0, 0, seed=0, model=other)
    ex_src = lanes.ex_for(0, other)
    ex_dst = lanes.ex_for(1, other)
    assert ex_src is lanes.bundle_executors[other][0]
    assert ex_src is not lanes.ex(0)
    ex_src.begin_chunk(0, FID, 0.0)
    while 0 in ex_src.inflight:
        ex_src.run_step([0])
    assert lanes.migrate(0, 0, 1)
    assert ex_dst.pool.resident(0)
    assert not ex_src.pool.resident(0)
    # the primary bundle's pools never saw the stream
    assert not lanes.ex(0).pool.resident(0)
    assert not lanes.ex(1).pool.resident(0)
    assert lanes.model_of[0] == other
    assert lanes.lane_of[0] == 1
