"""Multi-lane serving: N DEVICE-BACKED lanes under one control plane.

A *lane* is one execution slot over a device — one
``BatchedChunkExecutor`` with its own paged ``KVPool`` — standing in
for one Worker of the paper's cluster (SS3.1).  When the runtime
exposes more than one device (real accelerators, or forced host
devices in CI via ``XLA_FLAGS=--xla_force_host_platform_device_
count=N``), each lane COMMITS its pool, params view, and per-stream
buffers to its own ``jax.devices()`` entry; cross-lane KV movement is
then a real ``jax.device_put`` between device buffers, timed on the
spot (``MeasuredTransfer``) next to the engine's modeled timeline, and
the measurements EMA-calibrate the model's ``bw_intra``.  A
single-device runtime keeps the legacy placement (uncommitted buffers)
bit-for-bit.

``LanePool`` is the **apply layer** for the cross-worker decisions
``core.control_plane.ControlPlane.tick`` already emits (and which the
discrete-event simulator already applies on its virtual clock):

* ``rehoming.Migration`` -> :meth:`migrate`: a real cross-lane KV move.
  Same-device lanes detach the pages host-side (``KVPool.export_spill``,
  bit-exact) and land through the normal restore path; device-backed
  lanes ship the page block device-to-device (measured) and land it
  immediately resident (``KVPool.import_pages``).  Either way ONE
  src->dst transfer is charged on the shared
  ``state_plane.AsyncTransferEngine`` (cross-node bandwidth when the
  lanes' nodes differ) and the bytes are attributed directionally:
  source ``transfer_bytes_out``, destination ``transfer_bytes_in``.
* ``elastic_sp.SPDecision`` -> :meth:`sp_expand` / :meth:`sp_release`,
  in one of two modes (``SPLink.mode``):

  - **solo** (same-device lanes): expand copies the stream's UPPER
    half KV heads into a page set of the donor lane's pool (the
    App. C.4 head-partition transfer: half the stream's bytes) and the
    executor serves it with the Ulysses head-split
    ``ardit.denoise_step_paged_sp`` — home computes heads [0, H/2),
    donor heads [H/2, H) — dispatched solo, so the donor's step slot
    is genuinely occupied.  The home pool stays the full-head system
    of record; release just frees the donor pages.
  - **batch** (cross-device lanes, where one jit cannot read two
    devices' pools): expand mirrors FULL-head pages into the donor
    pool and the borrowed stream joins the *batch axis* of the donor's
    own sub-batch — co-served with the donor's streams in the donor's
    standard fused ``denoise_step_paged`` call, consuming no solo
    dispatch slot.  Each completed chunk's KV is shipped back
    (appended) to the home pool, which therefore stays the system of
    record: release frees the donor pages and moves nothing back.

  Both modes are bit-identical to the SP1 step.

All lanes of one model share ONE replica (per-device views of the same
params), one transfer engine (one metrics surface), and — because the
jitted step functions are module-level — one compile cache per device.
:meth:`prejit_sp` warms the solo-SP executables up front so triggering
elastic SP never compiles on the critical path (batch-axis SP reuses
the donor's ordinary step shapes, which warm naturally).

**Heterogeneous co-serving** (``bundles=``): the pool holds one
executor + paged ``KVPool`` per *(bundle, lane)* — a lane's device
hosts one pool per co-served model, each with that model's params,
geometry, and compile cache.  Every stream is pinned to its bundle
(``model_of``) and all routing (``executor_of``/``serving_ex``,
migrate, SP expand/release) resolves through ``ex_for(lane, model)``,
so re-homing and elastic SP are *same-model-only by construction*: a
move or mirror always lands in the target lane's pool of the SAME
bundle.  ``bundles=None`` (or a single bundle) builds exactly the
legacy objects in the legacy order — single-model sessions are
bit-identical to the pre-refactor path.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.state_plane import AsyncTransferEngine
from repro.core.types import Stream
from repro.models import ardit as A
from repro.models import kvcache
from repro.serve.batcher import (BatchedChunkExecutor, KVPool, SPGuest,
                                 SPLink)


class LanePool:
    """One ``BatchedChunkExecutor`` per lane + the decision apply layer.

    ``lane_of`` maps every admitted stream to its current home lane;
    migrations move it.  Counters (``n_migrations``, ``n_sp_expands``,
    ``n_sp_releases``) record decisions actually *applied* — the
    control plane separately counts decisions *planned*.
    """

    def __init__(self, n_lanes: int, cfg: Any = None, params: Any = None,
                 seed: int = 0, max_streams: int = 16,
                 context_backend: str = "paged",
                 engine: Optional[AsyncTransferEngine] = None,
                 sp_mode: str = "auto", page_evict: bool = False,
                 bundles: Optional[Sequence[Any]] = None):
        assert n_lanes >= 1
        assert sp_mode in ("auto", "solo", "batch"), sp_mode
        # lanes round-robin over the runtime's real devices (forced host
        # devices in CI via XLA_FLAGS=--xla_force_host_platform_device_
        # count=N); a single-device runtime keeps the legacy placement
        # (device=None, uncommitted buffers) bit-for-bit
        devs = jax.devices()
        self.lane_devices: List[Optional[Any]] = (
            [devs[i % len(devs)] for i in range(n_lanes)]
            if len(devs) > 1 else [None] * n_lanes)
        self.sp_mode = sp_mode
        # heterogeneous co-serving: the PRIMARY bundle's executors are
        # ``self.executors`` (constructed exactly like the legacy
        # single-model path, in the same order), extra bundles add one
        # executor + pool per lane on the same devices/engine
        self.bundles = list(bundles) if bundles else None
        if self.bundles:
            cfg, params = self.bundles[0].cfg, self.bundles[0].params
        first = BatchedChunkExecutor(cfg=cfg, params=params, seed=seed,
                                     max_streams=max_streams,
                                     context_backend=context_backend,
                                     engine=engine,
                                     device=self.lane_devices[0],
                                     page_evict=page_evict)
        self.engine = first.pool.engine
        self.executors: List[Any] = [first]
        for lane in range(1, n_lanes):
            self.executors.append(BatchedChunkExecutor(
                cfg=first.cfg, params=first.params,
                max_streams=max_streams, context_backend=context_backend,
                engine=self.engine, device=self.lane_devices[lane],
                page_evict=page_evict))
        self.bundle_executors: Dict[str, List[Any]] = {}
        self.model_of: Dict[int, str] = {}
        if self.bundles:
            self.bundle_executors[self.bundles[0].name] = self.executors
            for b in self.bundles[1:]:
                self.bundle_executors[b.name] = [
                    BatchedChunkExecutor(
                        cfg=b.cfg, params=b.params,
                        max_streams=max_streams,
                        context_backend=context_backend,
                        engine=self.engine,
                        device=self.lane_devices[lane],
                        page_evict=page_evict)
                    for lane in range(n_lanes)]
        self.lane_of: Dict[int, int] = {}
        self.n_migrations = 0
        self.n_sp_expands = 0
        self.n_sp_releases = 0

    @classmethod
    def wrap(cls, executor: Any) -> "LanePool":
        """Single-lane pool around an existing executor (the session's
        back-compat ``executor=`` injection; also adapts the sequential
        whole-chunk executor, which has no page pool)."""
        self = cls.__new__(cls)
        self.executors = [executor]
        self.lane_devices = [getattr(executor, "device", None)]
        self.sp_mode = "auto"
        pool = getattr(executor, "pool", None)
        self.engine = (pool.engine if pool is not None
                       else getattr(executor, "engine",
                                    AsyncTransferEngine()))
        self.bundles = None
        self.bundle_executors = {}
        self.model_of = {}
        self.lane_of = {}
        self.n_migrations = 0
        self.n_sp_expands = 0
        self.n_sp_releases = 0
        return self

    # ---- views -------------------------------------------------------------
    @property
    def n_lanes(self) -> int:
        return len(self.executors)

    def ex(self, lane: int) -> Any:
        return self.executors[lane]

    def ex_for(self, lane: int, model: Optional[str] = None) -> Any:
        """The executor of ``lane`` serving ``model``'s bundle — the
        primary list when ``model`` is None or unknown (single-model
        paths resolve here to exactly the legacy object)."""
        if model is not None and model in self.bundle_executors:
            return self.bundle_executors[model][lane]
        return self.executors[lane]

    @property
    def all_executors(self) -> List[Any]:
        """Every executor across bundles, primary bundle's lanes first."""
        if not self.bundle_executors:
            return self.executors
        out = list(self.executors)
        for name, exs in self.bundle_executors.items():
            if exs is not self.executors:
                out.extend(exs)
        return out

    def executor_of(self, sid: int) -> Any:
        return self.ex_for(self.lane_of.get(sid, 0), self.model_of.get(sid))

    def chunks_of(self, sid: int) -> List[Any]:
        return self.executor_of(sid).chunks.get(sid, [])

    def serving_ex(self, sid: int) -> Any:
        """The executor currently SERVING ``sid``: its donor lane during
        a batch-axis SP borrow (the stream runs there as a guest batch
        row), its home lane otherwise."""
        link = self.sp_link(sid)
        if link is not None and getattr(link, "mode", "solo") == "batch":
            return self.ex_for(link.donor, self.model_of.get(sid))
        return self.executor_of(sid)

    def is_inflight(self, sid: int) -> bool:
        return sid in self.serving_ex(sid).inflight

    def any_inflight(self) -> bool:
        return any(ex.inflight for ex in self.all_executors)

    def sp_link(self, sid: int) -> Optional[SPLink]:
        return getattr(self.executor_of(sid), "sp_links", {}).get(sid)

    def remaining_estimate(self, sid: int) -> float:
        return self.serving_ex(sid).remaining_estimate(sid)

    def latency_ema_get(self, key: str, default: float,
                        model: Optional[str] = None) -> float:
        """Measured chunk-latency EMA for a fidelity, averaged over the
        lanes that have observed it (all lanes share one host/device
        class, so their EMAs estimate the same quantity).  ``model``
        scopes the read to that bundle's executors — fidelity keys
        collide across co-served models, so a cross-bundle average
        would mix surfaces."""
        exs = (self.bundle_executors.get(model, self.executors)
               if model is not None else self.executors)
        vals = [ex.latency_ema[key] for ex in exs
                if key in ex.latency_ema]
        return sum(vals) / len(vals) if vals else default

    # ---- stream lifecycle (routed to the home lane) ------------------------
    def admit(self, sid: int, lane: int, seed: int = 0,
              streams: Optional[Dict[int, Stream]] = None,
              protect: Sequence[int] = (),
              model: Optional[str] = None) -> bool:
        self.lane_of[sid] = lane
        if model is not None:
            self.model_of[sid] = model
        else:
            self.model_of.pop(sid, None)      # sid reuse across models
        return self.ex_for(lane, model).admit(sid, seed=seed,
                                              streams=streams,
                                              protect=protect)

    def ensure_resident(self, sid: int,
                        streams: Optional[Dict[int, Stream]] = None,
                        protect: Sequence[int] = ()) -> bool:
        return self.executor_of(sid).ensure_resident(sid, streams,
                                                     protect=protect)

    def abort_chunk(self, sid: int) -> None:
        self.serving_ex(sid).abort_chunk(sid)

    def reset_condition(self, sid: int, seed: int) -> bool:
        """Prompt switch: fresh cond encode + sink rewrite on the home
        lane.  Any live SP link must be released by the caller FIRST
        (the donor's half mirrors the old prompt's KV)."""
        ex = self.executor_of(sid)
        assert sid not in getattr(ex, "sp_links", {}), \
            f"stream {sid}: release the SP link before a prompt switch"
        return ex.reset_condition(sid, seed)

    def retire(self, sid: int) -> None:
        if self.sp_link(sid) is not None:
            self.sp_release(sid)
        self.executor_of(sid).retire(sid)
        # model_of is deliberately RETAINED: generated chunks survive
        # retire inside the bundle's executor, so chunks_of / handle
        # reads must keep routing to it (admit() clears stale entries
        # if a sid is ever reused)

    # ---- real device moves -------------------------------------------------
    def _measured_put(self, tree: Any, device: Any, *,
                      cross_node: bool = False,
                      kind: str = "move") -> Any:
        """Move a pytree of arrays onto ``device`` with
        ``jax.device_put``, timing the copy wall-to-wall (source blocked
        first so pending compute doesn't pollute the measurement) and
        recording the measured move on the shared engine — which
        calibrates its bandwidth model from the observed bytes/sec."""
        jax.block_until_ready(tree)
        n = sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(tree))
        t0 = time.perf_counter()
        moved = jax.device_put(tree, device)
        jax.block_until_ready(moved)
        self.engine.record_measured(n, time.perf_counter() - t0,
                                    cross_node=cross_node, kind=kind)
        return moved

    # ---- decision apply: re-homing -----------------------------------------
    def migrate(self, sid: int, src: int, dst: int, *,
                cross_node: bool = False) -> bool:
        """Apply one ``rehoming.Migration`` as a real KV move.  Returns
        False (decision dropped) when the stream is mid-chunk or
        SP-linked — states the planner excludes, re-checked here
        because the executor, not the planner, owns ground truth.

        Device-backed lanes take the DIRECT path: the source's resident
        pages are handed over as device arrays and ``jax.device_put``
        onto the destination lane's device (measured, recorded on the
        engine), landing straight in the destination page table — no
        host round trip.  Lanes sharing one device (or a parked source
        stream) keep the host-spill path; either way the stream's KV is
        bit-identical after the move."""
        if self.lane_of.get(sid) != src or src == dst:
            return False
        # same-model-only by construction: both endpoints resolve to the
        # stream's OWN bundle's executor on each lane
        model = self.model_of.get(sid)
        src_ex, dst_ex = self.ex_for(src, model), self.ex_for(dst, model)
        if sid in src_ex.inflight or sid in src_ex.sp_links:
            return False
        dst_dev = getattr(dst_ex, "device", None)
        direct = (dst_dev is not None
                  and dst_dev != getattr(src_ex, "device", None)
                  and src_ex.pool.resident(sid)
                  and dst_ex.pool.can_admit())
        state = src_ex.export_stream(sid, to_host=not direct)
        n_bytes = int(state["pages"]["k"].nbytes
                      + state["pages"]["v"].nbytes)
        src_ex.pool.transfer_bytes_out += n_bytes
        if direct:
            state["pages"] = self._measured_put(
                state["pages"], dst_dev, cross_node=cross_node,
                kind="migration")
        dst_ex.import_stream(sid, state, cross_node=cross_node,
                             direct=direct)
        self.lane_of[sid] = dst
        # land it in the destination pool right away when there is room
        # — the import already charged the src->dst move, so this
        # restore is free; under pressure the stream stays parked and
        # rejoins via ensure_resident (a genuine second movement,
        # charged then).  The direct path is already page-resident.
        if not direct and dst_ex.pool.can_admit():
            dst_ex.pool.restore(sid, charge=False)
            dst_ex._boundary_cache.clear()
        self.n_migrations += 1
        return True

    # ---- decision apply: elastic SP ----------------------------------------
    def _sp_mode_for(self, home_ex: Any, donor_ex: Any) -> str:
        """Serving mode of a new SP link.  Lanes on DIFFERENT devices
        always use batch-axis SP: the fused head-split step reads both
        pools in ONE jitted call, which JAX rejects across committed
        devices.  Same-device lanes follow ``sp_mode`` ("auto" keeps
        the legacy solo head-split; "batch" forces the batch axis —
        how the parity tests compare the two on one device)."""
        if getattr(home_ex, "device", None) != \
                getattr(donor_ex, "device", None):
            return "batch"
        return "batch" if self.sp_mode == "batch" else "solo"

    def sp_expand(self, sid: int, donor: int,
                  streams: Optional[Dict[int, Stream]] = None) -> bool:
        """Apply one SP expand: allocate a donor-pool page set, copy the
        stream's KV into it, and link the stream.  Solo mode copies the
        UPPER half heads (App. C.4 head-partition transfer, half the
        stream's bytes) and ``run_step`` takes the head-split path;
        batch mode copies FULL heads onto the donor's device (a
        measured ``jax.device_put`` when the lanes are device-backed)
        and registers the stream as a donor-lane guest — it joins the
        donor's own micro-batches instead of consuming a solo dispatch
        slot.  False when the apply is impossible right now (non-paged
        backend, stream not resident, donor pool unevictable) — the
        decision is dropped and the planner may re-issue it next tick."""
        home = self.lane_of.get(sid)
        if home is None or donor == home:
            return False
        # same-model-only: the mirror lands in the donor LANE's pool of
        # the stream's own bundle (that model's params drive the split)
        model = self.model_of.get(sid)
        ex = self.ex_for(home, model)
        if getattr(ex, "context_backend", None) != "paged":
            return False          # head split rides the paged step only
        if sid in ex.sp_links:
            return True
        if not ex.pool.resident(sid) and \
                not ex.ensure_resident(sid, streams, protect=[sid]):
            return False
        donor_ex = self.ex_for(donor, model)
        dpool: KVPool = donor_ex.pool
        while not dpool.can_admit():
            # the executor's own credit-aware eviction (protects the
            # donor's in-flight streams AND any live SP mirrors)
            if not donor_ex._evict_one(streams, protect={sid}):
                return False
        mode = self._sp_mode_for(ex, donor_ex)
        dpool.ledger.take(sid, chunks=ex.pool.ledger.chunks[sid])
        dpool._dev_tables.pop(sid, None)
        if mode == "batch":
            n_bytes = self._copy_sp_full(ex.pool, dpool, sid)
            # the donor serves the guest with the HOME stream's noise
            # cursor and playout history: the chunk/fidelity lists are
            # SHARED objects (one system of record), the noise counter
            # is synced here and synced back on release
            donor_ex.sp_guests[sid] = SPGuest(home=home, pool=ex.pool)
            donor_ex.chunk_seq[sid] = ex.chunk_seq.get(sid, 0)
            donor_ex.chunks[sid] = ex.chunks[sid]
            donor_ex.fidelity_log[sid] = ex.fidelity_log[sid]
            # guest rows build their masks on the DONOR executor: any
            # page-evicted chunks must stay masked there too
            dropped = ex.pool.ledger.dropped.get(sid)
            if dropped:
                dpool.ledger.dropped[sid] = set(dropped)
        else:
            n_bytes = self._copy_sp_half(ex.pool, dpool, sid)
        t = self.engine.transfer(time.perf_counter(), n_bytes,
                                 cross_node=False)
        # the modeled dispatcher wait rides on the stream's next
        # completed chunk — which batch mode completes on the DONOR
        serving = donor_ex if mode == "batch" else ex
        serving._pending_wait[sid] = \
            serving._pending_wait.get(sid, 0.0) + t.residual_wait
        serving.transfer_wait_s += t.residual_wait
        # per-lane attribution: the mirror bytes LEAVE the home pool and
        # LAND in the donor pool (charging the home pool's aggregate for
        # pages the donor received made per-lane rows lie)
        ex.pool.transfer_bytes_out += n_bytes
        dpool.transfer_bytes_in += n_bytes
        ex.sp_links[sid] = SPLink(donor=donor, pool=dpool, mode=mode)
        donor_ex.sp_mirrors.add(sid)   # shield the mirror from eviction
        ex._boundary_cache.clear()
        donor_ex._boundary_cache.clear()
        self.n_sp_expands += 1
        return True

    def _copy_sp_half(self, home: KVPool, dpool: KVPool,
                      sid: int) -> int:
        """Mirror the stream's upper half KV heads (all of its pages)
        into the donor pool's page set.  Verbatim copy — the SP2 step's
        donor shard then reads bit-identical values, which is what
        makes SP2 == SP1 numerically."""
        h2 = home.cfg.n_kv_heads // 2
        # holes (page-evicted ring entries) map to the sink page: the
        # mirrored rows are garbage there, but the dropped-chunk masks
        # keep them unread on both pools
        rows = jnp.asarray(home.table_rows(sid), jnp.int32)
        drows = jnp.asarray(dpool.ledger.tables[sid], jnp.int32)
        kh = home.k[:, rows][:, :, h2:]         # [L, pps, H/2, P, Dh]
        vh = home.v[:, rows][:, :, h2:]
        dpool.k = kvcache.pool_write_pages(dpool.k, kh, drows, h2)
        dpool.v = kvcache.pool_write_pages(dpool.v, vh, drows, h2)
        return kh.nbytes + vh.nbytes

    def _copy_sp_full(self, home: KVPool, dpool: KVPool,
                      sid: int) -> int:
        """Copy the stream's FULL-head pages into the donor pool's page
        set (batch-axis SP): a measured ``jax.device_put`` when the
        pools live on different devices.  Verbatim copy — the donor
        then serves the stream with the ordinary SP1 step over
        bit-identical values."""
        rows = jnp.asarray(home.table_rows(sid), jnp.int32)
        pages = {"k": home.k[:, rows], "v": home.v[:, rows]}
        if dpool.device is not None and dpool.device != home.device:
            pages = self._measured_put(pages, dpool.device,
                                       kind="sp-expand")
        dpool._write(dpool.ledger.tables[sid], pages["k"], pages["v"])
        return int(pages["k"].nbytes + pages["v"].nbytes)

    def sp_release(self, sid: int) -> None:
        """Apply one SP release at a safe boundary: drop the link and
        free the donor pages.  The home pool kept full heads (batch
        mode shipped each completed chunk's KV home), so nothing moves
        back; a batch-mode release also clears the guest registration
        and carries the noise cursor home.  Idempotent."""
        ex = self.executor_of(sid)
        link = getattr(ex, "sp_links", {}).pop(sid, None)
        if link is None:
            return
        donor_ex = self.ex_for(link.donor, self.model_of.get(sid))
        if link.mode == "batch":
            assert sid not in donor_ex.inflight, \
                "batch-axis SP release only at a chunk boundary"
            donor_ex.sp_guests.pop(sid, None)
            ex.chunk_seq[sid] = donor_ex.chunk_seq.pop(
                sid, ex.chunk_seq.get(sid, 0))
            donor_ex.chunks.pop(sid, None)        # shared list: home keeps it
            donor_ex.fidelity_log.pop(sid, None)
            w = donor_ex._pending_wait.pop(sid, 0.0)
            if w:
                ex._pending_wait[sid] = ex._pending_wait.get(sid, 0.0) + w
            donor_ex._boundary_cache.clear()
        link.pool.ledger.drop(sid, spill=False)
        link.pool._dev_tables.pop(sid, None)
        donor_ex.sp_mirrors.discard(sid)
        ex._boundary_cache.clear()
        self.n_sp_releases += 1

    # ---- compile-cache warm-up ---------------------------------------------
    def prejit_sp(self, extents: Sequence[int] = (0, 1, 2)) -> None:
        """Warm the SP2 head-split executables for the given ring
        extents — unmasked, dn-masked, and dn+cl-masked variants (a
        C<0 stream is exactly the one BMPR pushes toward sparsified
        fidelities, whose clean mask differs from the denoise mask) —
        so an expansion mid-burst never compiles on the critical path.
        All SP groups share these executables — the jitted steps are
        module-level, so one warm-up covers every (home, donor) lane
        pair.  Extents beyond the list (deep rings under long streams)
        compile on first use.  With co-served bundles every bundle's
        executable set is warmed — each bundle's head-split step is
        compiled against ITS config/params/pool shapes."""
        if self.n_lanes < 2 or self.sp_mode == "batch":
            return
        lanes_by_bundle = (self.bundle_executors.values()
                           if self.bundle_executors else [self.executors])
        for exs in lanes_by_bundle:
            self._prejit_sp_bundle(exs, extents)

    def _prejit_sp_bundle(self, executors: List[Any],
                          extents: Sequence[int]) -> None:
        ex0 = executors[0]
        if getattr(ex0, "context_backend", None) != "paged":
            return
        # the fused two-pool head-split step only ever runs between
        # lanes that SHARE a device (cross-device pairs use batch-axis
        # SP, which rides the already-warm SP1 step) — warm it for the
        # first same-device pair, or skip when every pair is split
        ex1 = next((e for e in executors[1:]
                    if getattr(e, "device", None)
                    == getattr(ex0, "device", None)), None)
        if ex1 is None:
            return
        cfg = ex0.cfg
        tc = A.chunk_tokens(cfg)
        pt = ex0.pool.page_tokens
        x = jnp.zeros((1, tc, A.LATENT_CH))
        t = jnp.zeros((1,), jnp.float32)
        qo = jnp.asarray([A.COND_TOKENS], jnp.int32)
        is_dn = jnp.asarray([True])
        for n_ring in extents:
            if n_ring > cfg.ardit_window_chunks:
                continue
            tables = jnp.zeros((1, 1 + n_ring), jnp.int32)
            full = np.zeros((1, (1 + n_ring) * pt), bool)
            full[:, :A.COND_TOKENS] = True
            for r in range(n_ring):
                lo = (1 + r) * pt
                full[:, lo:lo + tc] = True
            m = jnp.asarray(full)
            for dn, cl in ((None, None), (m, None), (m, m)):
                A.denoise_step_paged_sp(
                    cfg, ex0.params, x, t, t, ex0.pool.k, ex0.pool.v,
                    ex1.pool.k, ex1.pool.v, tables, tables, dn, cl,
                    qo, is_dn)
