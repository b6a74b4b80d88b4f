"""Batched multi-stream serving executor (continuous cross-request
batching at denoise-step granularity).

The sequential ``ChunkExecutor`` generates chunks one stream at a time,
so the control plane's credit ordering cannot exploit any batch
parallelism.  This module adds the execution-side counterpart of the
paper's step-boundary preemption (SS3.1): every scheduler iteration
composes a *micro-batch* from the credit-ordered runnable set (lowest
credit first, up to ``max_batch``), splits it into same-fidelity
sub-batches, and advances each sub-batch by ONE denoise step with a
single jitted batched denoise-step call over a PAGE-GRANULAR device KV
pool (SS4.1's state plane): each stream owns a cond sink page plus a
ring of chunk pages through a per-stream page table.  By default the
step is PAGE-TABLE-NATIVE (``context_backend="paged"``): attention
consumes (pool, block tables, page-coordinate masks) directly via
``ardit.denoise_step_paged`` -> ``attention.paged_mha`` ->
``kernels/paged_attention``, never materializing a contiguous context;
``context_backend="gather"`` keeps the gather-per-boundary path as the
executable reference.
Streams join and leave the batch at step boundaries; on admission
pressure the executor evicts the highest-credit resident (host spill,
bit-exact restore) instead of failing, so more streams than the pool
holds can be served (oversubscription).  Measured whole-chunk wall time
feeds the latency EMAs so BMPR budgets and service-credit estimates
stay honest (re-profiling).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from repro.configs.base import ModelConfig
from repro.core import queues
from repro.core.fidelity import FidelityConfig
from repro.core.state_plane import AsyncTransferEngine, PagedKVPool
from repro.core.types import Stream
from repro.models import ardit as A
from repro.models import kvcache
from repro.models.stepcache import StepCacheManager
from repro.serve.executor import EMA_DECAY, ChunkExecutor, ServedStream


def compose_batch(sids: Sequence[int],
                  fidelity_of: Callable[[int], FidelityConfig],
                  max_batch: int, fuse: bool = False,
                  model_of: Optional[Callable[[int], str]] = None,
                  ) -> List[List[int]]:
    """Credit-ordered micro-batch composition.

    ``sids`` is the runnable set already ordered by service credit
    ascending (``queues.next_dispatch_set``).  Takes the lowest-credit
    ``max_batch`` streams and splits them into same-fidelity sub-batches
    (``FidelityConfig.key``), preserving credit order within and across
    groups — the first group contains the most urgent stream.

    ``fuse=True`` groups by **quantization dtype only** (the fused
    heterogeneous-fidelity dispatch): steps, window, and sparsity are
    per-row data inside ``run_step`` — the padded-steps schedule — so
    one jitted launch serves every fidelity of a dtype, cutting
    dispatch count from O(#fidelity keys) to O(#dtypes).  The dtype
    split stays: KV quantization changes the pool buffer dtype the
    jitted step is compiled against, which cannot be row data.

    ``model_of`` (heterogeneous co-serving) prefixes every group key
    with the stream's model bundle: a sub-batch runs one jitted step of
    ONE model against ONE pool, so ``(model, kv_dtype)`` is the fused
    grouping floor.  None (single-model sessions) keeps the exact
    legacy keys.
    """
    groups: Dict[Any, List[int]] = {}
    for sid in list(sids)[:max_batch]:
        fid = fidelity_of(sid)
        key = fid.quant if fuse else fid.key
        if model_of is not None:
            key = (model_of(sid), key)
        groups.setdefault(key, []).append(sid)
    return list(groups.values())


class PageLedger:
    """Host-side page bookkeeping of the device pool (no KV values).

    LIFO free list (O(1) pop/push), per-stream page tables (entry 0 =
    cond sink page, entry 1+r = ring slot r), per-stream chunk counts,
    and the set of spilled streams.  Residency is mirrored into a
    ``core.state_plane.PagedKVPool`` so the real executor and the
    simulator share one accounting model (and one invariant checker).
    """

    def __init__(self, n_pages: int, pages_per_stream: int):
        self.n_pages = n_pages
        self.pages_per_stream = pages_per_stream
        self._free: List[int] = list(range(n_pages))
        self.tables: Dict[int, np.ndarray] = {}
        self.chunks: Dict[int, int] = {}
        self.spilled: set = set()
        self.accounting = PagedKVPool(n_pages)
        # partial-window residency: absolute chunk indices whose ring
        # page was individually evicted (table entry -1, KV DISCARDED —
        # a degradation, not a spill).  The set survives whole-stream
        # spill/restore (the restored page holds zeros, not the lost
        # KV) and is pruned as chunks age out of the ring.
        self.dropped: Dict[int, set] = {}

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def can_admit(self) -> bool:
        return len(self._free) >= self.pages_per_stream

    def resident(self, sid: int) -> bool:
        return sid in self.tables

    def resident_sids(self) -> List[int]:
        return list(self.tables)

    def take(self, sid: int, chunks: int = 0) -> np.ndarray:
        """Allocate a page table for ``sid`` (admission or restore)."""
        assert sid not in self.tables, f"stream {sid} already resident"
        assert self.can_admit(), "ledger full: caller must evict first"
        table = np.asarray([self._free.pop()
                            for _ in range(self.pages_per_stream)])
        self.tables[sid] = table
        self.chunks[sid] = chunks
        self.spilled.discard(sid)
        self.accounting.alloc(sid, self.pages_per_stream)
        return table

    def drop(self, sid: int, spill: bool) -> Optional[np.ndarray]:
        """Free ``sid``'s pages; ``spill=True`` keeps it re-admittable.
        Idempotent: dropping a non-resident stream is a no-op (returns
        None) — no double-free."""
        table = self.tables.pop(sid, None)
        if table is None:
            if not spill:
                self.spilled.discard(sid)
                self.chunks.pop(sid, None)
                self.dropped.pop(sid, None)
            return None
        # hole entries (-1: individually evicted ring pages) own nothing
        self._free.extend(int(p) for p in table if int(p) >= 0)
        self.accounting.release(sid)
        if spill:
            self.spilled.add(sid)
        else:
            self.chunks.pop(sid, None)
            self.dropped.pop(sid, None)
        return table

    # ---- partial-window residency (page-granular eviction) -----------------
    def _ring_contents(self, sid: int) -> Dict[int, Optional[int]]:
        """Table ring entry (1..W) -> absolute chunk it currently holds,
        or None for an entry no chunk has reached yet."""
        w = self.pages_per_stream - 1
        n = self.chunks.get(sid, 0)
        held: Dict[int, Optional[int]] = {
            e: None for e in range(1, self.pages_per_stream)}
        for c in range(max(0, n - w), n):
            held[kvcache.page_of_chunk(c, w)] = c
        return held

    def page_eviction_entry(self, sid: int) -> Optional[int]:
        """Ring entry the partial-window ladder would free next for
        ``sid``, or None when the stream is at its residency floor.
        Preference order: an entry no chunk has reached yet (zero
        quality cost), else the entry holding the OLDEST retained chunk
        — never the newest chunk (always visible, most valuable) and
        never the last allocated ring entry (so an append into a hole
        can always self-heal by stealing a sibling page)."""
        table = self.tables.get(sid)
        if table is None:
            return None
        alloc = [e for e in range(1, len(table)) if int(table[e]) >= 0]
        if len(alloc) <= 1:
            return None
        held = self._ring_contents(sid)
        unwritten = [e for e in alloc if held[e] is None]
        if unwritten:
            return unwritten[-1]
        newest = self.chunks.get(sid, 0) - 1
        olds = sorted((held[e], e) for e in alloc if held[e] != newest)
        return olds[0][1] if olds else None

    def evict_page(self, sid: int) -> Optional[int]:
        """Free ONE of ``sid``'s ring pages (partial-window residency:
        the stream stays resident with its effective window reduced by
        one chunk).  The page's KV is DISCARDED, not spilled — the
        chunk it held joins ``dropped`` and the masks stop attending to
        it.  Returns the dropped absolute chunk index (or -1 for an
        unwritten entry), None when the stream is at its floor."""
        entry = self.page_eviction_entry(sid)
        if entry is None:
            return None
        held = self._ring_contents(sid)
        table = self.tables[sid]
        self._free.append(int(table[entry]))
        table[entry] = -1
        self.accounting.release_pages(sid, 1)
        c = held[entry]
        if c is not None:
            self.dropped.setdefault(sid, set()).add(c)
        return c if c is not None else -1

    def prune_dropped(self, sid: int) -> None:
        """Forget dropped chunks that aged out of the ring — they are
        no longer addressable, degraded window or not."""
        d = self.dropped.get(sid)
        if d:
            floor = self.chunks.get(sid, 0) - (self.pages_per_stream - 1)
            d.difference_update({c for c in d if c < floor})
            if not d:
                self.dropped.pop(sid, None)

    def append_page(self, sid: int) -> int:
        """Destination page of ``sid``'s next chunk (ring entry).  When
        the entry is a hole (its page was individually evicted), the
        append HEALS it: a free page if one exists, else the stream
        steals its own least-valuable sibling ring page (whose chunk
        joins ``dropped`` — degradation stays page-granular and
        self-contained)."""
        table = self.tables[sid]
        entry = kvcache.page_of_chunk(self.chunks[sid],
                                      self.pages_per_stream - 1)
        if int(table[entry]) < 0:
            if self._free:
                table[entry] = self._free.pop()
                ok = self.accounting.alloc(sid, 1)
                assert ok
            else:
                donor = self._steal_entry(sid, entry)
                table[entry] = int(table[donor])
                table[donor] = -1
        return int(table[entry])

    def _steal_entry(self, sid: int, target: int) -> int:
        """Sibling ring entry whose page a hole-append steals under a
        dry free list: an unreached entry first, else the oldest
        retained chunk's entry (which joins ``dropped``)."""
        table = self.tables[sid]
        alloc = [e for e in range(1, len(table))
                 if e != target and int(table[e]) >= 0]
        assert alloc, f"stream {sid} has no ring page left to steal"
        held = self._ring_contents(sid)
        unwritten = [e for e in alloc if held[e] is None]
        if unwritten:
            return unwritten[-1]
        donor = min(alloc, key=lambda e: held[e])
        self.dropped.setdefault(sid, set()).add(held[donor])
        return donor

    def check(self) -> None:
        """Pool invariants: page conservation, unique ownership, and
        agreement with the mirrored state-plane accounting."""
        allocated = [int(p) for t in self.tables.values()
                     for p in t if int(p) >= 0]
        assert len(set(allocated)) == len(allocated), \
            "page owned by two streams"
        assert len(set(self._free)) == len(self._free), \
            "duplicate page in free list (double-free)"
        assert not set(allocated) & set(self._free), \
            "page both free and allocated"
        assert len(allocated) + len(self._free) == self.n_pages, \
            "page leak: used + free != n_pages"
        assert not self.spilled & set(self.tables), \
            "stream both spilled and resident"
        for sid, t in self.tables.items():
            assert int(t[0]) >= 0, f"stream {sid} lost its sink page"
            assert len(t) == 1 or any(int(p) >= 0 for p in t[1:]), \
                f"stream {sid} degraded below the one-ring-page floor"
        assert self.accounting.used == len(allocated)
        self.accounting.check()


class KVPool:
    """Page-granular device KV pool (the ROADMAP "paged-KV
    defragmentation" item).

    KV lives as one head-major [L, n_pages, Hkv, page_tokens, Dh] pair
    (``kvcache`` page layout: one contiguous [page_tokens, Dh] slab per
    page and head, the TPU paged kernel's DMA block); a resident
    stream owns ``1 + window_chunks`` pages recorded in its page table
    (cond sink page + ring of chunk pages; chunk c lands in table entry
    ``1 + c % window_chunks``).  Sub-batches assemble their contiguous
    sink+ring context by gathering pages through the tables
    (``kvcache.gather_pages``), bitwise-identical to the stacked
    whole-stream rings this replaces.  On admission pressure ``admit``
    does NOT raise: the stream is parked host-side (evict-or-defer
    signal) and the executor decides — evict a victim via
    ``queues.pick_eviction`` and ``restore``, or defer.  Evicted
    streams spill their pages to host memory and are restored
    bit-exactly on re-admission, so oversubscription (more streams than
    the pool holds) never loses context.
    """

    def __init__(self, cfg: ModelConfig, params: Any, max_streams: int,
                 engine: Optional[AsyncTransferEngine] = None,
                 device: Optional[Any] = None):
        self.cfg, self.params = cfg, params
        self._tc = A.chunk_tokens(cfg)
        self._w = cfg.ardit_window_chunks
        self.page_tokens = A.page_tokens(cfg)
        pps = kvcache.pages_per_stream(self._w)
        self.ledger = PageLedger(max_streams * pps, pps)
        shape = (cfg.n_layers, self.ledger.n_pages, cfg.n_kv_heads,
                 self.page_tokens, cfg.head_dim)
        dt = jnp.dtype(cfg.kv_dtype)
        # a device-backed pool COMMITS its buffers to its lane's device
        # (``jax.devices()[lane]`` under a multi-device runtime), so a
        # cross-lane page move is a real ``jax.device_put`` between
        # device buffers, not a host-array relabel.  A program placed on
        # that device fills them there: ``jnp.zeros(..., device=)`` fills
        # on the default device and copies, so every lane's pool would
        # pass through device 0's memory
        self.device = device
        zeros = jax.jit(functools.partial(jnp.zeros, shape, dt),
                        out_shardings=(None if device is None else
                                       SingleDeviceSharding(device)))
        self.k, self.v = zeros(), zeros()
        self._spill: Dict[int, Dict[str, Any]] = {}   # sid -> host pages
        # device-side per-stream page tables, built once per residency
        # epoch (invalidated on admit/evict/restore/retire) instead of
        # np.stack + host->device upload on every boundary
        self._dev_tables: Dict[int, jax.Array] = {}
        # spill/restore traffic goes through the state plane's async
        # transfer engine so residency churn is charged the paper's
        # async-stream protocol latency (ROADMAP "transfer-engine
        # timing"); the log doubles as the benchmark's transfer report.
        # A multi-lane session injects ONE shared engine so migrations
        # and SP head-partition moves land on one metrics surface.
        self.engine = engine or AsyncTransferEngine(n_layers=cfg.n_layers)
        # directional byte counters: what this pool RECEIVED vs what it
        # SENT AWAY.  A cross-lane move charges the source's ``out`` and
        # the destination's ``in`` — never the same pool twice — so
        # per-lane benchmark rows attribute traffic to the lane that
        # actually carried it (spill = out, restore = in)
        self.transfer_bytes_in = 0
        self.transfer_bytes_out = 0

    @property
    def transfer_bytes(self) -> int:
        """Total KV bytes moved through this pool's boundary (in + out):
        the back-compat aggregate the benchmark's transfer report keys
        on."""
        return self.transfer_bytes_in + self.transfer_bytes_out

    # ---- ledger views ------------------------------------------------------
    @property
    def n_pages(self) -> int:
        return self.ledger.n_pages

    @property
    def pages_per_stream(self) -> int:
        return self.ledger.pages_per_stream

    @property
    def free_pages(self) -> int:
        return self.ledger.free_pages

    @property
    def chunks(self) -> Dict[int, int]:
        """Per-stream chunk counts (resident and spilled streams)."""
        return self.ledger.chunks

    def can_admit(self) -> bool:
        return self.ledger.can_admit()

    def resident(self, sid: int) -> bool:
        return self.ledger.resident(sid)

    def resident_sids(self) -> List[int]:
        return self.ledger.resident_sids()

    def spilled(self, sid: int) -> bool:
        return sid in self._spill

    # ---- device writes / gathers -------------------------------------------
    def _write(self, pages: np.ndarray, nk: jax.Array,
               nv: jax.Array) -> None:
        """Write page-layout blocks [L, b, Hkv, T, Dh] at token 0 of
        ``pages``."""
        pg = jnp.asarray(np.asarray(pages), jnp.int32)
        if self.device is not None:
            # incoming rows may be committed to ANOTHER lane's device
            # (batch-axis SP shipback, migration import): land them here
            # first — a same-device put is a no-op, a cross-device put
            # is the real move
            nk = jax.device_put(nk, self.device)
            nv = jax.device_put(nv, self.device)
            pg = jax.device_put(pg, self.device)
        self.k = kvcache.pool_write_pages(self.k, nk, pg)
        self.v = kvcache.pool_write_pages(self.v, nv, pg)

    def _sink_kv(self, cond: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """The cond (sink) KV in page layout [L, 1, Hkv, COND, Dh]."""
        k, v = A.cond_kv(self.cfg, self.params, cond)
        return kvcache.to_pages(k), kvcache.to_pages(v)

    def table_rows(self, sid: int) -> np.ndarray:
        """Physical page rows of ``sid``'s table with holes (-1:
        individually evicted ring pages) mapped to the stream's own
        sink page — a valid, fully-masked stand-in: the visibility
        masks never attend to a dropped chunk's tokens, so the gather /
        kernel may read anything there."""
        t = self.ledger.tables[sid]
        return np.where(t < 0, t[0], t)

    def device_table(self, sid: int) -> jax.Array:
        """This stream's page table as a device int32 [1 + W] array,
        cached for the residency epoch (the table only changes on
        admit/evict/restore/retire/page-evict, so re-uploading it per
        boundary — let alone per step — is pure waste)."""
        t = self._dev_tables.get(sid)
        if t is None:
            t = jnp.asarray(self.table_rows(sid), jnp.int32)
            if self.device is not None:
                t = jax.device_put(t, self.device)
            self._dev_tables[sid] = t
        return t

    def tables_for(self, sids: Sequence[int]) -> jax.Array:
        """Stacked [b, 1 + W] block table of a sub-batch (device)."""
        return jnp.stack([self.device_table(sid) for sid in sids])

    def gather(self, sids: Sequence[int],
               n_ring: int) -> Tuple[jax.Array, jax.Array]:
        """Contiguous [L, b, COND + n_ring*tc, Hkv, Dh] context for a
        sub-batch, assembled through the page tables (the ``gather``
        context backend — the paged backend never materializes this)."""
        tables = self.tables_for(sids)
        k = kvcache.gather_pages(self.k, tables, A.COND_TOKENS,
                                 self._tc, n_ring)
        v = kvcache.gather_pages(self.v, tables, A.COND_TOKENS,
                                 self._tc, n_ring)
        return k, v

    # ---- residency lifecycle -----------------------------------------------
    def admit(self, sid: int, cond: jax.Array) -> bool:
        """Admit one stream: write its cond (sink) KV into a fresh page
        set.  Returns False when the pool is full — the stream is parked
        host-side and the caller must evict-and-``restore`` or defer
        (no exception)."""
        sk, sv = self._sink_kv(cond)
        if self.can_admit():
            table = self.ledger.take(sid)
            self._dev_tables.pop(sid, None)
            self._write(table[:1], sk, sv)
            return True
        dt = self.k.dtype
        pages = np.zeros((self.cfg.n_layers, self.pages_per_stream)
                         + self.k.shape[2:], dt)
        pages_v = np.zeros_like(pages)
        pages[:, 0, :, :A.COND_TOKENS] = np.asarray(sk[:, 0].astype(dt))
        pages_v[:, 0, :, :A.COND_TOKENS] = np.asarray(sv[:, 0].astype(dt))
        self._spill[sid] = {"k": pages, "v": pages_v}
        self.ledger.spilled.add(sid)
        self.ledger.chunks[sid] = 0
        return False

    def _charge_transfer(self, n_bytes: int, direction: str) -> None:
        """Record one spill/restore on the async transfer engine (the
        paper's async-stream protocol: the dispatcher only waits for the
        first layer; later layers overlap with compute).  ``direction``
        attributes the bytes: ``"out"`` = left this pool (spill),
        ``"in"`` = arrived (restore / import)."""
        if direction == "out":
            self.transfer_bytes_out += n_bytes
        else:
            self.transfer_bytes_in += n_bytes
        self.engine.transfer(time.perf_counter(), n_bytes,
                             cross_node=False)

    def evict(self, sid: int) -> int:
        """Spill a resident stream's pages to host memory and free them.
        Returns the number of pages released (credit-aware victim
        selection is the caller's job — ``queues.pick_eviction``).  A
        partially-degraded stream spills with its hole slices zeroed
        (their KV is already gone; ``ledger.dropped`` keeps masking the
        lost chunks after restore)."""
        table = self.ledger.tables[sid]
        holes = np.flatnonzero(np.asarray(table) < 0)
        rows = jnp.asarray(self.table_rows(sid), jnp.int32)
        # materialize on host BEFORE the pages are reused
        spill_k = np.asarray(self.k[:, rows])
        spill_v = np.asarray(self.v[:, rows])
        if holes.size:
            # np.asarray of a device buffer is a read-only view
            spill_k = spill_k.copy()
            spill_v = spill_v.copy()
            spill_k[:, holes] = 0
            spill_v[:, holes] = 0
        self._spill[sid] = {"k": spill_k, "v": spill_v}
        self.ledger.drop(sid, spill=True)
        self._dev_tables.pop(sid, None)
        self._charge_transfer(spill_k.nbytes + spill_v.nbytes, "out")
        return self.pages_per_stream

    def evict_page(self, sid: int) -> bool:
        """Free ONE ring page of ``sid`` (partial-window residency: the
        degradation ladder's first rung).  The page's KV is discarded —
        no host spill and NO transfer charge: nothing moved anywhere,
        the stream simply trades its effective window down by a chunk.
        False when the stream is at its residency floor."""
        if self.ledger.evict_page(sid) is None:
            return False
        self._dev_tables.pop(sid, None)
        return True

    def has_evictable_page(self, sid: int) -> bool:
        return self.ledger.page_eviction_entry(sid) is not None

    def effective_window(self, sid: int, window: int) -> int:
        """Chunks of context actually visible to ``sid``'s next chunk:
        the fidelity window clipped by fill and ring size, minus
        visible chunks lost to page-granular eviction."""
        n = self.ledger.chunks.get(sid, 0)
        w_vis = min(int(window), n, self._w)
        dropped = self.ledger.dropped.get(sid, ())
        lost = sum(1 for c in dropped if n - w_vis <= c < n)
        return w_vis - lost

    def restore(self, sid: int, *, charge: bool = True) -> bool:
        """Bring a spilled stream back resident (bit-exact: its pages
        are written back verbatim).  False when the pool is full.
        ``charge=False`` skips the transfer-engine accounting — used
        when the caller already charged the movement (a cross-lane
        migration models ONE src->dst transfer, not a host round
        trip)."""
        if not self.can_admit():
            return False
        sp = self._spill.pop(sid)
        table = self.ledger.take(sid, chunks=self.ledger.chunks[sid])
        self._dev_tables.pop(sid, None)
        self._write(table, jnp.asarray(sp["k"]), jnp.asarray(sp["v"]))
        if charge:
            self._charge_transfer(sp["k"].nbytes + sp["v"].nbytes, "in")
        return True

    def export_spill(self, sid: int, *,
                     to_host: bool = True) -> Tuple[Dict[str, Any], int]:
        """Detach one stream's KV as pages + chunk count (the migration
        export half): a resident stream's pages are materialized to host
        and freed, a spilled stream hands over its existing spill buffer
        verbatim.  ``to_host=False`` keeps a RESIDENT stream's pages as
        device arrays (no host round trip) so the caller can
        ``jax.device_put`` them straight onto the destination lane's
        device — the real cross-device migration path.  No transfer is
        charged — the caller owns the movement (``import_spill`` /
        ``import_pages`` on the destination pool is where the cross-lane
        transfer is accounted)."""
        n_chunks = self.ledger.chunks.get(sid, 0)
        if self.ledger.resident(sid):
            holes = np.flatnonzero(
                np.asarray(self.ledger.tables[sid]) < 0)
            rows = jnp.asarray(self.table_rows(sid), jnp.int32)
            if to_host:
                pages = {"k": np.asarray(self.k[:, rows]),
                         "v": np.asarray(self.v[:, rows])}
                if holes.size:
                    # np.asarray of a device buffer is a read-only view
                    pages = {n: a.copy() for n, a in pages.items()}
                    pages["k"][:, holes] = 0
                    pages["v"][:, holes] = 0
            else:
                # hole rows read the sink page: garbage, but the
                # dropped-chunk masks travel with the stream and keep
                # those slices invisible on the destination lane
                pages = {"k": self.k[:, rows], "v": self.v[:, rows]}
            self.ledger.drop(sid, spill=False)
        else:
            pages = self._spill.pop(sid)
            self.ledger.spilled.discard(sid)
            self.ledger.chunks.pop(sid, None)
        self._dev_tables.pop(sid, None)
        return pages, n_chunks

    def import_spill(self, sid: int, pages: Dict[str, Any],
                     n_chunks: int) -> None:
        """Adopt an exported stream host-side (spilled, re-admittable):
        the inverse of ``export_spill`` on the destination pool.  The
        stream becomes resident through the normal ``restore`` path, so
        the round trip is bit-exact."""
        assert not self.ledger.resident(sid) and sid not in self._spill, \
            f"stream {sid} already present in destination pool"
        self._spill[sid] = pages
        self.ledger.spilled.add(sid)
        self.ledger.chunks[sid] = n_chunks

    def import_pages(self, sid: int, pages: Dict[str, Any],
                     n_chunks: int) -> None:
        """Adopt an exported DEVICE page set directly into a fresh page
        table (the real cross-device migration landing: the caller
        already moved the pages to this pool's device with
        ``jax.device_put``).  Unlike ``import_spill`` the stream becomes
        page-resident immediately — no host-side parking."""
        assert not self.ledger.resident(sid) and sid not in self._spill, \
            f"stream {sid} already present in destination pool"
        assert self.can_admit(), \
            "direct import requires space (caller checks can_admit)"
        table = self.ledger.take(sid, chunks=n_chunks)
        self._dev_tables.pop(sid, None)
        self._write(table, pages["k"], pages["v"])

    def release(self, sid: int) -> None:
        """Retire a stream entirely (resident or spilled).  Idempotent."""
        self.ledger.drop(sid, spill=False)
        self._spill.pop(sid, None)
        self._dev_tables.pop(sid, None)

    def append(self, sids: Sequence[int], new_kv: Dict[str, jax.Array],
               quant: str) -> None:
        """Ring-write one finished chunk of KV per stream into its page
        and advance its chunk count (``new_kv`` rows align with
        ``sids``)."""
        if quant == "fp8":
            new_kv = {k: v.astype(jnp.float8_e4m3fn)
                      for k, v in new_kv.items()}
        for sid in sids:
            # an append into a hole heals the table (free page or a
            # stolen sibling): the cached device table goes stale
            if np.any(np.asarray(self.ledger.tables[sid]) < 0):
                self._dev_tables.pop(sid, None)
        pages = np.asarray([self.ledger.append_page(sid) for sid in sids])
        self._write(pages, kvcache.to_pages(new_kv["k"]),
                    kvcache.to_pages(new_kv["v"]))
        for sid in sids:
            self.ledger.chunks[sid] += 1
            self.ledger.prune_dropped(sid)


@dataclasses.dataclass
class SPLink:
    """One stream's active elastic-SP2 borrow (SS4.3): the donor lane id
    and the donor lane's KV pool.  Two serving modes:

    * ``"solo"`` — same-device lanes: the donor page set carries the
      stream's UPPER half KV heads (Ulysses head partition, App. C.4)
      and the home lane runs the fused head-split step
      ``ardit.denoise_step_paged_sp`` reading BOTH pools in one jitted
      call, dispatched solo with the donor's step slot reserved.
    * ``"batch"`` — device-backed lanes (one jitted call cannot read two
      pools committed to different devices): the donor page set carries
      FULL heads and the stream is served ON the donor lane as an
      ordinary extra row of the donor's own micro-batch (one fused
      jitted call co-serving donor streams + the borrowed stream — no
      solo dispatch slot consumed), bit-identical to the SP1 step.

    Either way the home pool stays the full-head system of record
    (batch mode ships each completed chunk's KV home), so releasing a
    link frees the donor pages and nothing moves back."""
    donor: int
    pool: KVPool
    mode: str = "solo"


@dataclasses.dataclass
class SPGuest:
    """Donor-side view of one batch-axis SP borrow: the borrowed stream
    runs HERE as a guest batch row over full-head donor pages, while
    ``pool`` (the HOME lane's pool) stays the system of record — each
    completed guest chunk's full-head KV is shipped back into it."""
    home: int
    pool: KVPool


@dataclasses.dataclass
class InflightChunk:
    """One stream's chunk mid-generation (step-granular state)."""
    x: jax.Array                      # [1, T_c, LATENT_CH] latents
    fidelity: FidelityConfig
    step: int = 0                     # denoise steps completed
    started: float = 0.0              # session clock at chunk start
    active_s: float = 0.0             # wall spent in steps (not held out)

    @property
    def phase(self) -> str:
        """'denoise' while steps remain, then one 'clean' KV pass."""
        return "denoise" if self.step < self.fidelity.steps else "clean"


class BatchedChunkExecutor(ChunkExecutor):
    """Multi-stream executor over a shared KV pool.

    ``run_step`` advances one same-fidelity sub-batch by a single
    denoise step (or the clean-context pass that finishes a chunk), so
    the scheduler can recompose the batch between any two steps.

    ``context_backend`` selects how a sub-batch sees its cached KV:

    * ``"paged"`` (default) — page-table-native: the jitted step
      receives the pool itself plus per-stream block tables and
      page-coordinate visibility masks (``ardit.denoise_step_paged`` ->
      ``attention.paged_mha`` -> ``kernels/paged_attention``).  No
      [L, b, COND + W*tc, ...] context is ever materialized.
    * ``"gather"`` — the executable reference: contiguous context
      gathered through the tables once per chunk boundary, exactly the
      PR 2 data path.  The two backends agree numerically on every
      parity scenario (``tests/test_paged_backend.py``).
    """

    def __init__(self, cfg: Optional[ModelConfig] = None,
                 params: Optional[Any] = None, seed: int = 0,
                 max_streams: int = 16,
                 context_backend: str = "paged",
                 engine: Optional[AsyncTransferEngine] = None,
                 device: Optional[Any] = None,
                 page_evict: bool = False):
        super().__init__(cfg=cfg, params=params, seed=seed)
        assert context_backend in ("gather", "paged"), context_backend
        self.context_backend = context_backend
        # partial-window residency: under pool pressure, evict single
        # ring pages from high-credit residents (effective window trades
        # down smoothly) before whole-stream spill.  Opt-in: page
        # eviction DISCARDS the page's KV, so numerical parity with an
        # unconstrained run no longer holds once it fires.
        self.page_evict = page_evict
        # a device-backed lane commits its params replica and pool
        # buffers to its own device, so every jitted step runs there and
        # cross-lane state movement is a real device-to-device copy
        self.device = device
        if device is not None:
            self.params = jax.device_put(self.params, device)
        self.pool = KVPool(self.cfg, self.params, max_streams,
                           engine=engine, device=device)
        self.inflight: Dict[int, InflightChunk] = {}
        self.chunks: Dict[int, List[jax.Array]] = {}
        self.fidelity_log: Dict[int, List[str]] = {}
        # noise-sequence counter per stream: tracks generated chunks
        # but RESETS on a prompt switch (generation restarts under the
        # new condition, so the post-switch chunk equals a fresh
        # stream's first chunk bit-exactly), while ``chunks`` keeps the
        # full playout history
        self.chunk_seq: Dict[int, int] = {}
        # active elastic-SP2 borrows: sid -> (donor lane, donor pool).
        # Set/cleared by the LanePool apply layer; run_step takes the
        # head-split path for a solo stream with a link.
        self.sp_links: Dict[int, SPLink] = {}
        # sids whose pages in THIS pool are another lane's live SP
        # half-head mirror (the stream is inflight on its HOME lane, so
        # the inflight filter alone would not protect it here)
        self.sp_mirrors: set = set()
        # batch-axis SP borrows served ON this lane: sid -> SPGuest
        # (guest rows join this lane's micro-batches; completed chunks
        # ship full-head KV back to the guest's home pool)
        self.sp_guests: Dict[int, SPGuest] = {}
        self.step_ema: Dict[str, float] = {}      # per-step wall seconds
        self.evictions = 0
        self.restores = 0
        self.deferrals = 0      # residency requests that had to wait
        self.page_evictions = 0   # single ring pages freed (ladder rung 1)
        self.dispatch_count = 0   # jitted step launches issued
        # content-adaptive step cache (fifth fidelity knob): lazily
        # built on the first cache-on chunk so cache=off executors pay
        # ZERO memory or dispatch overhead (the off path is untouched)
        self.max_streams = max_streams
        self.stepcache: Optional[StepCacheManager] = None
        self.cache_skipped_launches = 0   # whole launches never issued
        # per-stream effective-window history: one entry per completed
        # chunk = chunks of context its generation actually attended to
        # (fidelity window clipped by fill, minus page-evicted chunks)
        self.effective_window_log: Dict[int, List[int]] = {}
        # peak bytes of per-sub-batch context state staged for the
        # jitted step: gathered [L,b,ctx,...] copies for "gather",
        # tables + masks for "paged" (the acceptance metric)
        self.peak_ctx_bytes = 0
        # modeled async-stream transfer wait not yet charged to a
        # stream's measured chunk latency (spill/restore protocol cost)
        self._pending_wait: Dict[int, float] = {}
        self.transfer_wait_s = 0.0
        # per-sub-batch context + masks are constant across the steps of
        # a chunk (they change only when a stream's chunk count or page
        # table does), so they are cached per (group, fill, fidelity)
        # chunk boundary
        self._boundary_cache: Dict[tuple, Dict[str, Any]] = {}
        self._staging_cache: Dict[tuple, tuple] = {}

    # ---- stream lifecycle --------------------------------------------------
    def admit(self, sid: int, seed: int = 0,
              streams: Optional[Dict[int, Stream]] = None,
              protect: Sequence[int] = ()) -> bool:
        """Admit a stream.  On a full pool, evict the highest-credit
        evictable resident first (``streams`` supplies the credit view);
        without a credit view or an evictable victim the stream is
        parked host-side (defer) and False is returned — it joins later
        via ``ensure_resident``.  Never raises on exhaustion."""
        key = jax.random.PRNGKey(1000 + seed)
        cond = jax.random.normal(
            key, (1, A.COND_TOKENS, self.cfg.d_model)) * 0.02
        self.chunks[sid] = []
        self.fidelity_log[sid] = []
        self.effective_window_log[sid] = []
        self.chunk_seq[sid] = 0
        # boundary keys are (sids, fills, fid) and would collide with a
        # previous stream of the same id at the same fill — drop them
        self._boundary_cache.clear()
        mark = len(self.pool.engine.log)
        while not self.pool.can_admit():
            if not self._evict_one(streams, protect=set(protect) | {sid}):
                break
        ok = self.pool.admit(sid, cond)      # parks host-side when full
        if not ok:
            self.deferrals += 1
        self._charge_transfer_wait(sid, mark)
        return ok

    def _charge_transfer_wait(self, sid: int, log_mark: int) -> None:
        """Charge the dispatcher wait of any spill/restore transfers
        issued since ``log_mark`` to ``sid``'s next completed chunk, so
        residency churn shows up in the measured latency EMAs (the
        async-stream protocol only blocks until the first layer is
        resident; the rest overlaps with compute)."""
        new = self.pool.engine.log[log_mark:]
        if new:
            w = sum(t.residual_wait for t in new)
            self._pending_wait[sid] = self._pending_wait.get(sid, 0.0) + w
            self.transfer_wait_s += w

    def _evict_one(self, streams: Optional[Dict[int, Stream]],
                   protect: set) -> bool:
        """Free one stream's pages: credit-aware victim selection over
        the evictable residents.  In-flight streams are protected (their
        chunk is mid-denoise and rejoins the batch at the next step);
        so are live SP half-head mirrors (``sp_mirrors``) — the owning
        stream is inflight on its HOME lane, invisible to this lane's
        inflight set, and evicting its mirror would break the linked
        SP2 step mid-borrow.  A stream with a live SP link (home side)
        or borrowed onto this lane as a batch-axis guest is protected
        for the same reason: its pages on BOTH lanes must survive the
        borrow."""
        if streams is None:
            return False
        victims = [s for s in self.pool.resident_sids()
                   if s not in self.inflight and s not in self.sp_mirrors
                   and s not in self.sp_links and s not in self.sp_guests]
        if self.page_evict:
            # degradation ladder rung 1: free ONE ring page from the
            # highest-credit resident that still has one to give —
            # its effective window shrinks by a chunk, nothing spills
            victim = queues.pick_page_eviction(
                victims, streams, protect=protect,
                has_evictable=self.pool.has_evictable_page)
            if victim is not None:
                self.pool.evict_page(victim)
                self.page_evictions += 1
                self._boundary_cache.clear()
                return True
        # rung 2: whole-stream spill (host round trip, bit-exact)
        victim = queues.pick_eviction(victims, streams, protect=protect)
        if victim is None:
            return False
        self.pool.evict(victim)
        if self.stepcache is not None:
            # cache state is per-chunk transient: a spilled stream
            # rejoins at a chunk boundary, where it is stale anyway
            self.stepcache.drop(victim)
        self.evictions += 1
        self._boundary_cache.clear()
        return True

    def ensure_resident(self, sid: int,
                        streams: Optional[Dict[int, Stream]] = None,
                        protect: Sequence[int] = ()) -> bool:
        """Re-admit a spilled stream through the join/leave machinery
        (spilled streams rejoin at chunk boundaries, bit-exactly).
        False means the stream must wait this tick (defer)."""
        if self.pool.resident(sid):
            return True
        assert self.pool.spilled(sid), f"stream {sid} was never admitted"
        mark = len(self.pool.engine.log)
        while not self.pool.can_admit():
            if not self._evict_one(streams, protect=set(protect) | {sid}):
                self.deferrals += 1
                return False
        ok = self.pool.restore(sid)
        assert ok
        self.restores += 1
        self._charge_transfer_wait(sid, mark)
        # the restored stream owns DIFFERENT physical pages now: any
        # cached boundary still naming its old block table is stale
        # (the gathered backend tolerated this — restored data is
        # bit-identical — but the paged backend reads through tables)
        self._boundary_cache.clear()
        return True

    def abort_chunk(self, sid: int) -> None:
        """Drop an in-flight chunk at a step boundary (prompt switch):
        the partial denoise work is discarded.  Pool state needs no
        rollback — KV is only appended at the clean pass — and any
        pending transfer wait stays charged to the stream's next
        completed chunk (the restore really happened)."""
        self.inflight.pop(sid, None)
        if self.stepcache is not None:
            self.stepcache.reset_chunk(sid)

    def retire(self, sid: int, drop_history: bool = False) -> None:
        """Retire a stream: free its pages and per-stream counters.
        ``drop_history=True`` also drops the generated-chunk and
        fidelity history — used for the warm-up calibration stream
        (sid -1), whose residue would otherwise leak into lane 0's
        per-stream dicts forever."""
        assert sid not in self.sp_links, \
            f"stream {sid} retired with a live SP link (release first)"
        self.pool.release(sid)
        self.inflight.pop(sid, None)
        if self.stepcache is not None:
            self.stepcache.drop(sid)
        self._pending_wait.pop(sid, None)
        self.chunk_seq.pop(sid, None)
        if drop_history:
            self.chunks.pop(sid, None)
            self.fidelity_log.pop(sid, None)
            self.effective_window_log.pop(sid, None)
        self._boundary_cache.clear()

    def reset_condition(self, sid: int, seed: int) -> bool:
        """Prompt switch (SS3.3): re-encode a FRESH conditioning and
        rewrite the stream's sink page through the normal
        ``KVPool.admit`` path (release + re-admit), discarding the old
        prompt's ring KV (its chunks conditioned on the old prompt) and
        resetting the noise sequence — the post-switch chunk is
        bit-identical to a fresh stream's first chunk under the same
        conditioning seed.  Generated chunks/logs keep the playout
        history.  Returns False when the pool is full and the stream
        parked host-side (it rejoins via ``ensure_resident``)."""
        self.inflight.pop(sid, None)
        if self.stepcache is not None:
            self.stepcache.reset_chunk(sid)
        key = jax.random.PRNGKey(1000 + seed)
        cond = jax.random.normal(
            key, (1, A.COND_TOKENS, self.cfg.d_model)) * 0.02
        mark = len(self.pool.engine.log)
        self.pool.release(sid)
        ok = self.pool.admit(sid, cond)
        if not ok:
            self.deferrals += 1
        self._charge_transfer_wait(sid, mark)
        self.chunk_seq[sid] = 0
        self._boundary_cache.clear()
        return ok

    def export_stream(self, sid: int, *,
                      to_host: bool = True) -> Dict[str, Any]:
        """Detach a stream for cross-lane migration (KV pages, counters,
        generated chunks).  Only legal at a chunk boundary with no live
        SP link — exactly the streams ``rehoming.plan_rehoming`` deems
        movable.  ``to_host=False`` hands over device arrays (the real
        cross-device path; see ``KVPool.export_spill``).  No transfer is
        charged here; ``import_stream`` on the destination accounts the
        src->dst move."""
        assert sid not in self.inflight, f"stream {sid} is mid-chunk"
        assert sid not in self.sp_links, f"stream {sid} has a live SP link"
        if self.stepcache is not None:
            # step-cache state deliberately does NOT travel: it is
            # per-chunk transient and a migration lands at a chunk
            # boundary; motion recomputes from the chunk history below
            self.stepcache.drop(sid)
        dropped = sorted(self.pool.ledger.dropped.get(sid, ()))
        pages, n_chunks = self.pool.export_spill(sid, to_host=to_host)
        self._boundary_cache.clear()
        return {"pages": pages, "chunk_count": n_chunks,
                "chunks": self.chunks.pop(sid),
                "fidelity_log": self.fidelity_log.pop(sid),
                "chunk_seq": self.chunk_seq.pop(sid, 0),
                "pending_wait": self._pending_wait.pop(sid, 0.0),
                "dropped": dropped,
                "effective_window_log":
                    self.effective_window_log.pop(sid, [])}

    def import_stream(self, sid: int, state: Dict[str, Any], *,
                      cross_node: bool = False,
                      direct: bool = False) -> None:
        """Adopt an exported stream (the re-homing apply half): ONE
        src->dst transfer is charged on the shared engine (cross-node
        bandwidth when the lanes' nodes differ), and the dispatcher
        wait rides on the stream's next completed chunk.
        ``direct=True`` means ``state["pages"]`` are device arrays the
        caller already moved onto this lane's device — they are written
        straight into a fresh page table (immediately resident);
        otherwise the KV arrives host-side and the stream becomes
        page-resident through the normal restore path, bit-exactly."""
        self.chunks[sid] = state["chunks"]
        self.fidelity_log[sid] = state["fidelity_log"]
        self.chunk_seq[sid] = state["chunk_seq"]
        # degradation history travels too: the per-stream mean
        # effective window in SessionResult must span lane moves
        self.effective_window_log[sid] = \
            list(state.get("effective_window_log", []))
        if state.get("dropped"):
            # degradation history travels with the stream: the lost
            # chunks' slices (zeros / garbage) stay masked here too
            self.pool.ledger.dropped[sid] = set(state["dropped"])
        if direct:
            self.pool.import_pages(sid, state["pages"],
                                   state["chunk_count"])
        else:
            self.pool.import_spill(sid, state["pages"],
                                   state["chunk_count"])
        n_bytes = state["pages"]["k"].nbytes + state["pages"]["v"].nbytes
        self.pool.transfer_bytes_in += n_bytes
        t = self.pool.engine.transfer(time.perf_counter(), n_bytes,
                                      cross_node=cross_node)
        w = state["pending_wait"] + t.residual_wait
        self._pending_wait[sid] = self._pending_wait.get(sid, 0.0) + w
        self.transfer_wait_s += t.residual_wait
        self._boundary_cache.clear()

    def begin_chunk(self, sid: int, fidelity: FidelityConfig,
                    now: float) -> None:
        """Start a chunk at a step boundary (noise seeding matches the
        sequential path so the two executors are comparable)."""
        key = jax.random.PRNGKey(self.chunk_seq[sid] * 7919 + sid)
        tc = A.chunk_tokens(self.cfg)
        noise = jax.random.normal(key, (1, tc, A.LATENT_CH))
        self.inflight[sid] = InflightChunk(x=noise, fidelity=fidelity,
                                           started=now)
        if fidelity.cache != "off":
            self._stepcache().begin_chunk(sid, self.chunks.get(sid))

    def _stepcache(self) -> StepCacheManager:
        """Lazy step-cache manager: one residual-pool slot per possible
        concurrent stream, on this lane's device."""
        if self.stepcache is None:
            self.stepcache = StepCacheManager(
                self.max_streams + 1, A.chunk_tokens(self.cfg),
                A.LATENT_CH, self.cfg.n_layers, device=self.device)
        return self.stepcache

    def steps_left(self, sid: int) -> int:
        """Remaining forwards for the in-flight chunk (incl. clean pass)."""
        f = self.inflight[sid]
        return f.fidelity.steps + 1 - f.step

    # ---- the batched step --------------------------------------------------
    def _boundary(self, sids: Sequence[int], chunk_idx: np.ndarray,
                  fids: Sequence[FidelityConfig],
                  sp: Optional[SPLink] = None) -> Dict[str, Any]:
        """Per-chunk-boundary state of a sub-batch (constant across the
        chunk's steps): positions, denoise/clean visibility, and the
        backend's context handle — a gathered [L, b, extent, ...] copy
        for ``gather``, or the block tables + page-coordinate masks the
        paged step reads the pool through (both sliced to the group's
        resident extent, so compute scales with fill either way).
        ``fids`` is per-row: a fused heterogeneous-fidelity group hands
        each row the window/sparsity mask its own fidelity dictates —
        bit-identical per row to a split same-fidelity dispatch.  An
        active SP2 link adds the donor pool's block table — the
        head-split step reads its upper half heads through it."""
        key = (tuple(sids), tuple(chunk_idx.tolist()),
               tuple(f.key for f in fids),
               sp.donor if sp is not None else None)
        bnd = self._boundary_cache.get(key)
        if bnd is not None:
            return bnd
        tc = A.chunk_tokens(self.cfg)
        w_max = self.cfg.ardit_window_chunks
        n_ring = int(min(chunk_idx.max(initial=0), w_max))
        extent = A.COND_TOKENS + n_ring * tc
        # sparsity applies to denoise steps only; the clean-context pass
        # sees the full fidelity window.
        windows = np.asarray([f.window for f in fids], np.int64)
        dn = A.batched_context_mask_multi(
            self.cfg, chunk_idx, windows,
            np.asarray([f.sparsity for f in fids]))[:, :extent]
        cl = A.batched_context_mask_multi(
            self.cfg, chunk_idx, windows,
            np.zeros(len(fids)))[:, :extent]
        self._mask_dropped(sids, chunk_idx, dn, cl)
        bnd = {
            "q_offset": jnp.asarray(A.COND_TOKENS + chunk_idx * tc,
                                    jnp.int32),
        }
        if self.context_backend == "paged":
            # no gather: hand the step the tables and the masks mapped
            # into page coordinates.  dn all-true (homogeneous fill,
            # full window, no sparsity) drops BOTH masks — each page's
            # static valid prefix is visible and the step skips
            # per-score masking, like the gathered path's slices (cl is
            # a superset of dn, so dn all-true implies cl all-true);
            # an unsparsified fidelity's clean mask IS the denoise mask
            # — cl=None then means "reuse dn"
            tables = self.pool.tables_for(sids)[:, :1 + n_ring]
            bnd["tables"] = tables
            if sp is not None:
                bnd["tables_d"] = sp.pool.tables_for(sids)[:, :1 + n_ring]
            if dn.all():
                bnd["dn"] = None
                bnd["cl"] = None
            else:
                bnd["dn"] = jnp.asarray(kvcache.mask_to_pages(
                    dn, n_ring, A.COND_TOKENS, tc,
                    self.pool.page_tokens))
                bnd["cl"] = None if np.array_equal(dn, cl) else \
                    jnp.asarray(kvcache.mask_to_pages(
                        cl, n_ring, A.COND_TOKENS, tc,
                        self.pool.page_tokens))
            staged = (tables.nbytes
                      + (0 if bnd["dn"] is None else bnd["dn"].nbytes)
                      + (0 if bnd["cl"] is None else bnd["cl"].nbytes))
        else:
            # all-true masks (homogeneous fill, no sparsity, full
            # window) are dropped so the jitted step skips per-score
            # masking, like the sequential path's slices
            ctx_k, ctx_v = self.pool.gather(sids, n_ring)
            bnd["ctx_k"] = ctx_k
            bnd["ctx_v"] = ctx_v
            bnd["dn"] = None if dn.all() else jnp.asarray(dn)
            bnd["cl"] = None if cl.all() else jnp.asarray(cl)
            staged = ctx_k.nbytes + ctx_v.nbytes
        self.peak_ctx_bytes = max(self.peak_ctx_bytes, staged)
        if len(self._boundary_cache) >= 8:
            self._boundary_cache.pop(next(iter(self._boundary_cache)))
        self._boundary_cache[key] = bnd
        return bnd

    def _mask_dropped(self, sids: Sequence[int], chunk_idx: np.ndarray,
                      dn: np.ndarray, cl: np.ndarray) -> None:
        """Zero the token slices of page-evicted chunks in BOTH
        visibility masks (partial-window residency: the KV is gone, so
        no phase may attend to it).  Runs before the all-true fast-path
        check, forcing a degraded row onto the explicit-mask path —
        which is what keeps the sink-page stand-in rows of
        ``table_rows`` unread."""
        tc = A.chunk_tokens(self.cfg)
        w_max = self.cfg.ardit_window_chunks
        for i, sid in enumerate(sids):
            dropped = self.pool.ledger.dropped.get(sid)
            if not dropped:
                continue
            n = int(chunk_idx[i])
            for c in dropped:
                if n - w_max <= c < n:
                    lo = A.COND_TOKENS + (c % w_max) * tc
                    dn[i, lo:lo + tc] = False
                    cl[i, lo:lo + tc] = False

    def _staging(self, fids: Sequence[FidelityConfig],
                 steps: Tuple[int, ...], denoising: Tuple[bool, ...]):
        """Cached per-step staging arrays (t, dt, is_denoise): these
        repeat identically for every chunk of a given fidelity mix, so
        the tiny host->device uploads happen once, not every step.
        Per-row fidelity: each row walks its OWN sigma grid — a fused
        group's rows advance exactly as they would in split dispatch
        (rows whose chunk already completed simply leave the batch at
        the step boundary, so no padding rows are ever launched)."""
        key = (tuple(f.key for f in fids), steps, denoising)
        st = self._staging_cache.get(key)
        if st is None:
            grids = [A.sigma_schedule(f.steps) for f in fids]
            t = jnp.asarray([float(g[s]) if d else 0.0
                             for g, s, d in zip(grids, steps, denoising)],
                            jnp.float32)
            dt = jnp.asarray([float(g[s] - g[s + 1]) if d else 0.0
                              for g, s, d in zip(grids, steps, denoising)],
                             jnp.float32)
            st = (t, dt, jnp.asarray(denoising))
            if len(self._staging_cache) >= 64:
                self._staging_cache.pop(next(iter(self._staging_cache)))
            self._staging_cache[key] = st
        return st

    def run_step(self, sids: Sequence[int],
                 sp_serve: bool = False) -> Tuple[List[int], float]:
        """Advance one sub-batch by one step — same-fidelity (split
        dispatch) or mixed-fidelity sharing one KV quantization dtype
        (fused dispatch): window, sparsity, sigma grid, and phase are
        all per-row data, so each row computes exactly what its own
        fidelity's split launch would.

        ``sp_serve=True`` marks a dispatch that RESERVED the linked
        stream's donor step slot (the scheduler's solo SP2 dispatch):
        only then does a solo linked stream take the head-split path.
        An unreserved dispatch — even a singleton fidelity group — runs
        the SP1 step, so donor compute is never consumed twice (or zero
        times) in one round.

        Streams in their denoise phase take an Euler step; streams in
        their clean phase produce context KV, append it to the pool, and
        complete their chunk.  Both phases share ONE jitted batched
        call (``ardit.denoise_step``; phase differences are data).

        The host does NOT sync on intermediate steps — dispatch is
        asynchronous, so staging pipelines with compute; the executor
        syncs once per completed chunk, which also yields the measured
        whole-chunk wall latency fed into ``latency_ema``/``step_ema``
        (online re-profiling).  Returns (completed sids, wall seconds
        of this call).
        """
        flights = [self.inflight[sid] for sid in sids]
        fids = [f.fidelity for f in flights]
        quant = fids[0].quant
        # fused heterogeneous-fidelity dispatch: steps/window/sparsity
        # are per-row data (masks, sigma grids), but the KV quantization
        # dtype is a property of the append path shared by the whole
        # launch — groups must not mix dtypes
        assert all(f.quant == quant for f in fids), \
            "sub-batch must share one KV quantization dtype"
        assert all(self.pool.resident(sid) for sid in sids), \
            "sub-batch contains a non-resident (spilled) stream"
        chunk_idx = np.asarray([self.pool.chunks[sid] for sid in sids],
                               np.int64)
        # a batch-mode link is served on the DONOR lane (the stream is
        # a guest row there); its home lane must never also step it, or
        # the two page sets would diverge
        assert not any(s in self.sp_links
                       and self.sp_links[s].mode == "batch"
                       for s in sids), \
            "batch-axis SP: linked stream must be served on its donor lane"
        # elastic SP2 takes the head-split step for a SOLO linked stream
        # whose dispatch reserved the donor slot; a linked stream folded
        # into a normal batch falls back to the SP1 step — the home pool
        # holds full heads, so SP is an acceleration path, never a
        # correctness dependency
        sp = (self.sp_links.get(sids[0])
              if sp_serve and len(sids) == 1
              and self.context_backend == "paged"
              else None)
        if sp is not None and sp.mode != "solo":
            sp = None

        denoising = tuple(f.phase == "denoise" for f in flights)
        # content-adaptive step cache (fifth fidelity knob): decide
        # per-row reuse BEFORE staging.  A group whose rows are all
        # cache=off takes the exact legacy path below — zero tracker
        # calls, bit-identical launches (the safety rail).
        sc = self.stepcache
        cache_hits: Dict[int, float] = {}    # row -> dt of the reuse
        if any(fid.cache != "off" for fid in fids):
            sc = self._stepcache()
            for i, (f, fid) in enumerate(zip(flights, fids)):
                if fid.cache != "off" and denoising[i] \
                        and sc.should_hit(sids[i], fid.cache):
                    # uniform sigma grid (linspace 1 -> 0): dt = 1/S,
                    # host-side — no device read on the decision path
                    cache_hits[i] = 1.0 / fid.steps

        t0 = time.perf_counter()
        if cache_hits and len(cache_hits) == len(sids):
            # every row reuses its cached velocity: skip the jitted
            # launch entirely — the attention+MLP stack is replaced by
            # per-row AXPYs (this is the step cache's throughput win;
            # ``dispatch_count`` does not advance)
            self.cache_skipped_launches += 1
            for i, (sid, f) in enumerate(zip(sids, flights)):
                f.x = sc.apply_hit(sid, f.x, cache_hits[i])
                f.step += 1
            dt = time.perf_counter() - t0
            for f in flights:
                f.active_s += dt
            return [], dt

        bnd = self._boundary(sids, chunk_idx, fids, sp=sp)
        x = (flights[0].x if len(flights) == 1
             else jnp.concatenate([f.x for f in flights], axis=0))
        t, dt_sig, is_dn = self._staging(
            fids, tuple(f.step for f in flights), denoising)
        self.dispatch_count += 1
        if sp is not None:
            x_new, new_kv = A.denoise_step_paged_sp(
                self.cfg, self.params, x, t, dt_sig, self.pool.k,
                self.pool.v, sp.pool.k, sp.pool.v, bnd["tables"],
                bnd["tables_d"], bnd["dn"], bnd["cl"],
                bnd["q_offset"], is_dn)
        elif self.context_backend == "paged":
            # context stays IN the pool: the step reads the current
            # device buffers through the cached block tables (appends
            # only ever touch pages outside every in-flight window, so
            # the live read equals the boundary snapshot)
            x_new, new_kv = A.denoise_step_paged(
                self.cfg, self.params, x, t, dt_sig, self.pool.k,
                self.pool.v, bnd["tables"], bnd["dn"], bnd["cl"],
                bnd["q_offset"], is_dn)
        else:
            x_new, new_kv = A.denoise_step(
                self.cfg, self.params, x, t, dt_sig, bnd["ctx_k"],
                bnd["ctx_v"], bnd["q_offset"], bnd["dn"], bnd["cl"],
                is_dn)

        completed: List[int] = []
        clean_rows: List[int] = []
        for i, (sid, f) in enumerate(zip(sids, flights)):
            if denoising[i]:
                if i in cache_hits:
                    # masked no-op row of a mixed launch: the row rode
                    # along for shape stability; its output is the
                    # cached AXPY — identical to the skipped-launch
                    # path, so a hit never depends on group composition
                    f.x = sc.apply_hit(sid, f.x, cache_hits[i])
                else:
                    if fids[i].cache != "off":
                        sc.record_step(sid, f.x, x_new[i:i + 1],
                                       1.0 / fids[i].steps,
                                       new_kv["k"][:, i])
                    f.x = x_new[i:i + 1]
                f.step += 1
            else:
                clean_rows.append(i)
                completed.append(sid)
        if clean_rows:
            # effective window BEFORE the append advances chunk counts:
            # the context this chunk's generation actually attended to
            eff_w = {sids[i]: self.pool.effective_window(
                sids[i], fids[i].window) for i in clean_rows}
            rows = np.asarray(clean_rows)
            self.pool.append([sids[i] for i in clean_rows],
                             {"k": new_kv["k"][:, rows],
                              "v": new_kv["v"][:, rows]}, quant)
            for i in clean_rows:
                row = {"k": new_kv["k"][:, i:i + 1],
                       "v": new_kv["v"][:, i:i + 1]}
                link = self.sp_links.get(sids[i])
                if link is not None:
                    # the donor's half-head mirror must track the home
                    # pool: ring-write this chunk's upper half into the
                    # donor page set so the next SP2 boundary sees
                    # consistent halves (solo mode only — the assertion
                    # above keeps batch-linked streams off this lane)
                    self._append_sp_half(link, sids[i], row, quant)
                guest = self.sp_guests.get(sids[i])
                if guest is not None:
                    # batch-axis SP shipback: the guest's home pool is
                    # the system of record — append the full-head chunk
                    # there too (a real cross-device put when the lanes
                    # are device-backed), so release never moves state
                    guest.pool.append([sids[i]], row, quant)
            now_wall = None
            for i in clean_rows:
                sid = sids[i]
                fid = fids[i]
                f = self.inflight.pop(sid)
                self.chunks[sid].append(f.x)
                self.fidelity_log[sid].append(fid.key)
                self.effective_window_log.setdefault(sid, []).append(
                    eff_w[sid])
                self.chunk_seq[sid] = self.chunk_seq.get(sid, 0) + 1
                if now_wall is None:        # one sync per completion step
                    f.x.block_until_ready()
                    now_wall = time.perf_counter()
                # measured chunk wall -> timing priors, attributed to
                # each completing row's OWN fidelity key: under fused
                # dispatch ``active_s`` accrued per launch the row was
                # live in, so a fused launch's latency lands on member
                # keys weighted by the steps each member actually rode
                # — BMPR budgets and routing see the same per-fidelity
                # costs as under split dispatch.  Only time spent IN
                # the batch counts (a stream held out mid-chunk accrues
                # no active time).  Spill/restore dispatcher waits
                # charged by the transfer engine ride on the chunk they
                # delayed.
                lat = (f.active_s + (now_wall - t0)
                       + self._pending_wait.pop(sid, 0.0))
                self.latency_ema[fid.key] = (
                    EMA_DECAY * self.latency_ema.get(fid.key, lat)
                    + (1.0 - EMA_DECAY) * lat)
                step = lat / (fid.steps + 1)
                self.step_ema[fid.key] = (
                    EMA_DECAY * self.step_ema.get(fid.key, step)
                    + (1.0 - EMA_DECAY) * step)
        dt = time.perf_counter() - t0
        for sid in sids:
            f = self.inflight.get(sid)
            if f is not None:               # still mid-chunk
                f.active_s += dt
        return completed, dt

    def _append_sp_half(self, link: SPLink, sid: int,
                        new_kv: Dict[str, jax.Array], quant: str) -> None:
        """Ring-write one chunk's UPPER half KV heads into the donor
        pool's page set for ``sid`` (kept in lockstep with the home
        pool's full-head append)."""
        h2 = self.cfg.n_kv_heads // 2
        nk = kvcache.to_pages(new_kv["k"])[:, :, h2:]
        nv = kvcache.to_pages(new_kv["v"])[:, :, h2:]
        if quant == "fp8":
            nk = nk.astype(jnp.float8_e4m3fn)
            nv = nv.astype(jnp.float8_e4m3fn)
        page = jnp.asarray([link.pool.ledger.append_page(sid)], jnp.int32)
        link.pool.k = kvcache.pool_write_pages(link.pool.k, nk, page, h2)
        link.pool.v = kvcache.pool_write_pages(link.pool.v, nv, page, h2)
        link.pool.ledger.chunks[sid] += 1

    def remaining_estimate(self, sid: int) -> float:
        """R_u from the measured step EMA (not the offline profile)."""
        f = self.inflight.get(sid)
        if f is None:
            return 0.0
        per_step = self.step_ema.get(
            f.fidelity.key,
            self.latency_ema.get(f.fidelity.key, 0.0)
            / (f.fidelity.steps + 1))
        return self.steps_left(sid) * per_step


def serve_session_batched(n_streams: int = 4, chunks_per_stream: int = 4,
                          max_batch: int = 4,
                          realtime_budget: Optional[float] = None,
                          fidelity_policy=None,
                          pool_streams: Optional[int] = None,
                          context_backend: str = "paged",
                          verbose: bool = True) -> List[ServedStream]:
    """Legacy batched entry point — now a thin wrapper over the unified
    ``repro.serve.session.StreamingSession`` (all streams arrive at
    t=0, exact per-stream chunk counts).

    The session is driven by ``core.control_plane.ControlPlane.tick()``
    — the SAME Algorithm 2 decision code the discrete-event simulator
    runs — with this module's ``BatchedChunkExecutor`` as the apply
    layer; playout/stall state lives in ONE per-stream record
    (``core.types.Stream``) and the returned ``ServedStream``s are
    views over it.  Fidelity budgets follow Eq. 1
    (``B = max(P_u - R_u, 0)``) through the session's host-calibrated
    unit conversion — the old hand-tuned magic budget scale is gone.

    ``pool_streams`` caps co-resident streams (oversubscription when
    < n_streams: extra streams spill to host and rejoin at chunk
    boundaries); defaults to n_streams + 1, i.e. everyone resident.
    ``context_backend``: ``"paged"`` (default) serves attention straight
    from the page pool through block tables; ``"gather"`` materializes
    the contiguous context per boundary (executable reference).
    """
    from repro.serve.session import (SessionConfig, StreamingSession,
                                     uniform_specs)
    session = StreamingSession(
        SessionConfig(executor="batched", max_batch=max_batch,
                      pool_streams=pool_streams or (n_streams + 1),
                      context_backend=context_backend,
                      realtime_budget=realtime_budget, verbose=verbose),
        fidelity_policy=fidelity_policy)
    for spec in uniform_specs(n_streams, chunks_per_stream):
        session.submit(spec)
    session.run()
    return session.served_streams()
