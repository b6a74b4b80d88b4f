"""Page-table-native attention backend conformance suite.

Four angles on the ``paged`` context backend (the serving default):
  * mask layout — ``kvcache.mask_to_pages`` maps the contiguous
    sink+ring visibility mask into table coordinates exactly, with page
    tails always invalid;
  * attention math — the chunk-query paged partials (jnp oracle and the
    Pallas kernel under ``REPRO_FORCE_PALLAS_INTERPRET=1``) merged with
    the in-chunk segment reproduce dense masked attention over the
    gathered context;
  * backend parity — ``BatchedChunkExecutor(context_backend="paged")``
    matches the ``gather`` backend numerically across fidelity windows,
    fp8/bf16 KV, sparsity, ring wrap-around, and join/leave sequences
    (the PR 2 parity matrix);
  * oversubscription conformance — an oversubscribed paged-backend
    executor completes every stream numerically on the trajectory of an
    unconstrained gather-backend run (spill/restore + page-table
    indirection lose nothing).

The single-chunk parity test runs in the fast tier; matrix sweeps are
slow-tier.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fidelity import FidelityConfig
from repro.models import ardit as A
from repro.models import kvcache
from repro.models.attention import mha, paged_mha
from repro.serve.batcher import BatchedChunkExecutor

from test_batcher import nondegenerate_params, tiny_cfg

KEY = jax.random.PRNGKey(0)

RTOL, ATOL = 1e-4, 2e-4          # fp32 online-softmax merge-order slack


# ---------------------------------------------------------------------------
# mask layout: contiguous sink+ring -> page/table coordinates
# ---------------------------------------------------------------------------

def test_mask_to_pages_layout():
    sink, tc, page = 5, 3, 7
    mask = np.zeros((2, sink + 2 * tc), bool)
    mask[0, :sink] = True                      # sink only
    mask[1, :] = True                          # everything
    mask[1, sink + 1] = False                  # ... minus one ring token
    out = kvcache.mask_to_pages(mask, n_ring=2, sink=sink,
                                chunk_tokens=tc, page_tokens=page)
    assert out.shape == (2, 3 * page)
    # sink page: first `sink` tokens mirror the mask, tail invalid
    np.testing.assert_array_equal(out[:, :sink], mask[:, :sink])
    assert not out[:, sink:page].any()
    for r in range(2):
        lo = (1 + r) * page
        np.testing.assert_array_equal(
            out[:, lo:lo + tc], mask[:, sink + r * tc:sink + (r + 1) * tc])
        assert not out[:, lo + tc:lo + page].any()   # ring page tails


def test_mask_to_pages_zero_ring():
    out = kvcache.mask_to_pages(np.ones((1, 4), bool), n_ring=0, sink=4,
                                chunk_tokens=3, page_tokens=6)
    assert out.shape == (1, 6)
    np.testing.assert_array_equal(out[0], [1, 1, 1, 1, 0, 0])


# ---------------------------------------------------------------------------
# attention math: paged partials + in-chunk merge == dense masked mha
# ---------------------------------------------------------------------------

def _paged_case(seed=0, B=2, Sq=6, Hq=4, Hkv=2, D=8, n=3, page=7,
                p_total=9):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, Sq, Hq, D)), jnp.float32)
    # head-major page layout [P_total, Hkv, page, D]
    kp = jnp.asarray(rng.normal(size=(p_total, Hkv, page, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(p_total, Hkv, page, D)), jnp.float32)
    bt = jnp.asarray(rng.choice(p_total, size=(B, n), replace=False)
                     if B * n <= p_total else
                     rng.integers(0, p_total, size=(B, n)), jnp.int32)
    mask = rng.random((B, n * page)) < 0.7
    mask[0, page:2 * page] = False             # a fully-masked page
    mask[1, :] = False
    mask[1, :4] = True                         # nearly-empty stream
    ck = jnp.asarray(rng.normal(size=(B, Sq, Hkv, D)), jnp.float32)
    cv = jnp.asarray(rng.normal(size=(B, Sq, Hkv, D)), jnp.float32)
    return q, kp, vp, bt, jnp.asarray(mask), ck, cv


def _dense_reference(q, kp, vp, bt, mask, ck, cv, Hkv):
    b, n = bt.shape
    _, _, page, d = kp.shape
    kg = kp[bt.reshape(-1)].swapaxes(1, 2).reshape(b, n * page, Hkv, d)
    vg = vp[bt.reshape(-1)].swapaxes(1, 2).reshape(b, n * page, Hkv, d)
    k_all = jnp.concatenate([kg, ck], axis=1)
    v_all = jnp.concatenate([vg, cv], axis=1)
    kv_mask = jnp.concatenate(
        [mask, jnp.ones((b, q.shape[1]), bool)], axis=1)
    return mha(q, k_all, v_all, n_kv_heads=Hkv, causal=False,
               kv_mask=kv_mask)


def test_paged_mha_matches_dense_masked_mha():
    q, kp, vp, bt, mask, ck, cv = _paged_case()
    out = paged_mha(q, kp, vp, bt, mask, ck, cv, n_kv_heads=2)
    ref = _dense_reference(q, kp, vp, bt, mask, ck, cv, Hkv=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ref_compact_layout_equals_full_pages():
    """The sink/chunk_tokens layout hint (oracle skips always-masked
    page tails) must not change the partials — given a mask whose page
    tails are indeed dead."""
    from repro.kernels.paged_attention.ref import paged_chunk_attention_ref
    q, kp, vp, bt, mask, _, _ = _paged_case(seed=5)
    page = kp.shape[2]
    sink, tc = page - 2, page - 3
    m = np.asarray(mask).copy().reshape(q.shape[0], -1, page)
    m[:, 0, sink:] = False                     # dead sink-page tail
    m[:, 1:, tc:] = False                      # dead ring-page tails
    m = jnp.asarray(m.reshape(q.shape[0], -1))
    full = paged_chunk_attention_ref(q, kp, vp, bt, m)
    compact = paged_chunk_attention_ref(q, kp, vp, bt, m, sink=sink,
                                        chunk_tokens=tc)
    for f, c, name in zip(full, compact, ("m", "l", "acc")):
        np.testing.assert_allclose(np.asarray(f), np.asarray(c),
                                   rtol=1e-6, atol=1e-6, err_msg=name)


def test_all_visible_fast_path_equals_explicit_mask():
    """page_mask=None (every valid-prefix token visible) must equal the
    explicit prefix mask — jnp oracle and interpret-mode kernel both."""
    from repro.kernels.paged_attention.kernel import \
        paged_chunk_attention_pallas
    from repro.kernels.paged_attention.ref import paged_chunk_attention_ref
    q, kp, vp, bt, _, _, _ = _paged_case(seed=9)
    b, page, n = q.shape[0], kp.shape[2], bt.shape[1]
    sink, tc = page - 1, page - 3
    m = np.zeros((b, n, page), bool)
    m[:, 0, :sink] = True
    m[:, 1:, :tc] = True
    m = jnp.asarray(m.reshape(b, -1))
    want = paged_chunk_attention_ref(q, kp, vp, bt, m)
    got_ref = paged_chunk_attention_ref(q, kp, vp, bt, None, sink=sink,
                                        chunk_tokens=tc)
    got_krn = paged_chunk_attention_pallas(q, kp, vp, bt, None,
                                           sink=sink, chunk_tokens=tc,
                                           interpret=True)
    for g in (got_ref, got_krn):
        for a, w, name in zip(g, want, ("m", "l", "acc")):
            np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                       rtol=2e-5, atol=2e-5,
                                       err_msg=name)


@pytest.mark.slow
def test_paged_chunk_kernel_matches_ref_interpret(monkeypatch):
    """The chunk-query Pallas kernel (interpret mode, forced through the
    ops dispatcher env switch) agrees with the jnp oracle — partials
    and the merged paged_mha output."""
    from repro.kernels.paged_attention import ops
    from repro.kernels.paged_attention.ref import paged_chunk_attention_ref
    q, kp, vp, bt, mask, ck, cv = _paged_case(seed=3)
    want = paged_chunk_attention_ref(q, kp, vp, bt, mask)
    monkeypatch.setenv("REPRO_FORCE_PALLAS_INTERPRET", "1")
    got = ops.paged_chunk_attention(q, kp, vp, bt, mask)
    for g, w, name in zip(got, want, ("m", "l", "acc")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-5, atol=2e-5, err_msg=name)
    out = paged_mha(q, kp, vp, bt, mask, ck, cv, n_kv_heads=2)
    ref = _dense_reference(q, kp, vp, bt, mask, ck, cv, Hkv=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.slow
@pytest.mark.parametrize("B,Sq,Hq,Hkv,D,n,page", [
    (1, 4, 2, 2, 16, 2, 5),       # MHA, tiny pages
    (3, 8, 8, 2, 8, 4, 6),        # GQA group of 4
    (2, 5, 6, 3, 4, 1, 9),        # single-page table
])
def test_paged_chunk_kernel_shape_sweep(B, Sq, Hq, Hkv, D, n, page):
    from repro.kernels.paged_attention.kernel import \
        paged_chunk_attention_pallas
    from repro.kernels.paged_attention.ref import paged_chunk_attention_ref
    rng = np.random.default_rng(B * 100 + n)
    p_total = max(B * n, n + 2)
    q = jnp.asarray(rng.normal(size=(B, Sq, Hq, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(p_total, Hkv, page, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(p_total, Hkv, page, D)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, p_total, size=(B, n)), jnp.int32)
    mask = jnp.asarray(rng.random((B, n * page)) < 0.6)
    got = paged_chunk_attention_pallas(q, kp, vp, bt, mask,
                                       interpret=True)
    want = paged_chunk_attention_ref(q, kp, vp, bt, mask)
    for g, w, name in zip(got, want, ("m", "l", "acc")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-5, atol=2e-5, err_msg=name)


# ---------------------------------------------------------------------------
# backend parity: paged executor == gather executor
# ---------------------------------------------------------------------------

def _run_backend(cfg, p, backend, schedule, max_streams=4):
    """Drive an executor through ``schedule`` = list of (sids, fid)
    chunk rounds (each round runs every listed stream to completion,
    stepped together) and return the generated chunks."""
    ex = BatchedChunkExecutor(cfg=cfg, params=p, max_streams=max_streams,
                              context_backend=backend)
    admitted = set()
    for sids, fid in schedule:
        for sid in sids:
            if sid not in admitted:
                assert ex.admit(sid, seed=sid)
                admitted.add(sid)
            ex.begin_chunk(sid, fid, 0.0)
        while any(sid in ex.inflight for sid in sids):
            grp = [sid for sid in sids if sid in ex.inflight]
            ex.run_step(grp)
    return {sid: [np.asarray(c) for c in ex.chunks[sid]]
            for sid in admitted}


def _assert_same(got, want):
    assert set(got) == set(want)
    for sid in want:
        assert len(got[sid]) == len(want[sid])
        for a, b in zip(got[sid], want[sid]):
            np.testing.assert_allclose(a, b, rtol=RTOL, atol=ATOL)


def test_paged_backend_matches_gather_single_chunk():
    """Fast-tier core parity claim: one two-stream chunk, paged ==
    gather (the matrix sweep is slow-tier)."""
    cfg = tiny_cfg(window_chunks=2)
    p = nondegenerate_params(cfg, KEY)
    fid = FidelityConfig(2, 0.0, 2, "bf16")
    schedule = [([0, 1], fid)]
    _assert_same(_run_backend(cfg, p, "paged", schedule),
                 _run_backend(cfg, p, "gather", schedule))


@pytest.mark.slow
@pytest.mark.parametrize("window_chunks", [2, 3])
def test_paged_backend_parity_matrix(window_chunks):
    """The tentpole parity claim on the PR 2 matrix: windows x fp8/bf16
    x sparsity x ring wrap-around, served end-to-end by both context
    backends."""
    cfg = tiny_cfg(window_chunks=window_chunks)
    p = nondegenerate_params(cfg, KEY)
    fids = [FidelityConfig(2, 0.0, 2, "bf16"),
            FidelityConfig(2, 0.9, 1, "fp8"),
            FidelityConfig(2, 0.6, window_chunks, "bf16"),
            FidelityConfig(2, 0.0, 2, "bf16")]   # wraps the ring
    schedule = [([0, 1], fid) for fid in fids]
    _assert_same(_run_backend(cfg, p, "paged", schedule),
                 _run_backend(cfg, p, "gather", schedule))


@pytest.mark.slow
def test_paged_backend_join_leave_matches_gather():
    """Join/leave: stream 0 runs two chunks alone (heterogeneous fills),
    then stream 1 joins mid-session — the paged backend must stay on the
    gather backend's trajectory throughout."""
    cfg = tiny_cfg(window_chunks=3)
    p = nondegenerate_params(cfg, KEY)
    fid = FidelityConfig(2, 0.0, 2, "bf16")
    schedule = [([0], fid), ([0], fid), ([0, 1], fid)]
    _assert_same(_run_backend(cfg, p, "paged", schedule),
                 _run_backend(cfg, p, "gather", schedule))


# ---------------------------------------------------------------------------
# oversubscription conformance across backends
# ---------------------------------------------------------------------------

def _drive_round_robin(ex, sids, n_chunks, fid, streams=None):
    for _ in range(n_chunks):
        for sid in sids:
            if streams is not None:
                for s in sids:
                    streams[s].credit = float(len(ex.chunks[s]))
            assert ex.ensure_resident(sid, streams, protect=[sid])
            ex.begin_chunk(sid, fid, 0.0)
            while sid in ex.inflight:
                ex.run_step([sid])
    return {sid: [np.asarray(c) for c in ex.chunks[sid]] for sid in sids}


@pytest.mark.slow
def test_oversubscribed_paged_matches_unconstrained_gather():
    """2x pool capacity through the PAGED backend (page tables change on
    every spill/restore) completes with chunks numerically identical to
    an everyone-resident GATHER run — the acceptance bar combining both
    PR mechanisms."""
    from repro.core.types import Stream
    cfg = tiny_cfg(window_chunks=2)
    p = nondegenerate_params(cfg, KEY)
    fid = FidelityConfig(2, 0.0, 2, "bf16")
    sids = [0, 1, 2, 3]
    n_chunks = 2

    full = BatchedChunkExecutor(cfg=cfg, params=p, max_streams=4,
                                context_backend="gather")
    for sid in sids:
        assert full.admit(sid, seed=sid)
    want = _drive_round_robin(full, sids, n_chunks, fid)

    over = BatchedChunkExecutor(cfg=cfg, params=p, max_streams=2,
                                context_backend="paged")
    streams = {sid: Stream(sid=sid, arrival=0.0, target_chunks=n_chunks,
                           chunk_seconds=1.0, home=0, ttfc_slack=1e9)
               for sid in sids}
    admitted = [over.admit(sid, seed=sid) for sid in sids]
    assert admitted == [True, True, False, False]   # overflow defers
    got = _drive_round_robin(over, sids, n_chunks, fid, streams=streams)

    assert over.evictions > 0 and over.restores > 0
    # satellite: spill/restore went through the async transfer engine
    assert len(over.pool.engine.log) == over.evictions + over.restores
    assert over.pool.transfer_bytes > 0
    assert over.transfer_wait_s > 0.0
    _assert_same(got, want)
    over.pool.ledger.check()


# ---------------------------------------------------------------------------
# device-side page-table caching (per-step upload fix)
# ---------------------------------------------------------------------------

def test_device_tables_cached_and_invalidated():
    """``tables_for`` reuses one device array per residency epoch and
    rebuilds only after admit/evict/restore/retire change the table."""
    cfg = tiny_cfg(window_chunks=2)
    ex = BatchedChunkExecutor(cfg=cfg, max_streams=2)
    ex.admit(0, seed=0)
    t1 = ex.pool.device_table(0)
    assert ex.pool.device_table(0) is t1        # cached, no re-upload
    np.testing.assert_array_equal(np.asarray(t1),
                                  ex.pool.ledger.tables[0])
    ex.admit(1, seed=1)
    assert ex.pool.device_table(0) is t1        # untouched by others
    ex.pool.evict(0)
    assert 0 not in ex.pool._dev_tables         # invalidated
    ex.pool.restore(0)
    t2 = ex.pool.device_table(0)
    np.testing.assert_array_equal(np.asarray(t2),
                                  ex.pool.ledger.tables[0])
    ex.retire(0)
    assert 0 not in ex.pool._dev_tables
