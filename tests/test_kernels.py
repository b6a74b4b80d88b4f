"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_mha_pallas
from repro.kernels.flash_attention.ref import flash_mha_ref
from repro.kernels.paged_attention.kernel import paged_decode_attention_pallas
from repro.kernels.paged_attention.ref import paged_decode_attention_ref
from repro.kernels.fp8_matmul.kernel import fp8_matmul_pallas
from repro.kernels.fp8_matmul.ref import fp8_matmul_ref, quantize_fp8_ref
from repro.kernels.ssd_scan.kernel import ssd_pallas
from repro.kernels.ssd_scan.ref import ssd_ref, ssd_decode_ref

pytestmark = pytest.mark.slow     # Pallas/JAX-compiling kernel sweeps: slow tier

KEY = jax.random.PRNGKey(0)


def _qkv(B, Sq, Skv, Hq, Hkv, D, dtype=jnp.float32):
    ks = jax.random.split(KEY, 3)
    return (jax.random.normal(ks[0], (B, Sq, Hq, D), dtype),
            jax.random.normal(ks[1], (B, Skv, Hkv, D), dtype),
            jax.random.normal(ks[2], (B, Skv, Hkv, D), dtype))


class TestFlashAttention:
    @pytest.mark.parametrize("B,Sq,Skv,Hq,Hkv,D", [
        (2, 64, 64, 4, 2, 16),
        (1, 128, 128, 2, 2, 32),
        (1, 96, 96, 8, 1, 64),
    ])
    def test_causal_gqa(self, B, Sq, Skv, Hq, Hkv, D):
        q, k, v = _qkv(B, Sq, Skv, Hq, Hkv, D)
        out = flash_mha_pallas(q.swapaxes(1, 2), k.swapaxes(1, 2),
                               v.swapaxes(1, 2), block_q=32, block_kv=32,
                               interpret=True).swapaxes(1, 2)
        ref = flash_mha_ref(q, k, v, n_kv_heads=Hkv,
                            block_q=32, block_kv=32)
        np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)

    def test_non_causal(self):
        q, k, v = _qkv(2, 64, 64, 4, 4, 16)
        out = flash_mha_pallas(q.swapaxes(1, 2), k.swapaxes(1, 2),
                               v.swapaxes(1, 2), causal=False,
                               block_q=32, block_kv=32,
                               interpret=True).swapaxes(1, 2)
        ref = flash_mha_ref(q, k, v, n_kv_heads=4, causal=False)
        np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)

    def test_chunk_offset(self):
        q, k, v = _qkv(1, 64, 128, 4, 4, 16)
        out = flash_mha_pallas(q.swapaxes(1, 2), k.swapaxes(1, 2),
                               v.swapaxes(1, 2), q_offset=64,
                               block_q=32, block_kv=32,
                               interpret=True).swapaxes(1, 2)
        ref = flash_mha_ref(q, k, v, n_kv_heads=4, q_offset=64,
                            block_q=32, block_kv=32)
        np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("window,sink", [(48, 16), (40, 0), (96, 32)])
    def test_sink_window(self, window, sink):
        q, k, v = _qkv(1, 128, 128, 2, 1, 16)
        out = flash_mha_pallas(q.swapaxes(1, 2), k.swapaxes(1, 2),
                               v.swapaxes(1, 2), window=window, sink=sink,
                               block_q=32, block_kv=32,
                               interpret=True).swapaxes(1, 2)
        ref = flash_mha_ref(q, k, v, n_kv_heads=1, window=window,
                            sink=sink, block_q=32, block_kv=32)
        np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("rho", [0.6, 0.7, 0.9])
    def test_block_sparse(self, rho):
        q, k, v = _qkv(1, 256, 256, 4, 2, 16)
        out = flash_mha_pallas(q.swapaxes(1, 2), k.swapaxes(1, 2),
                               v.swapaxes(1, 2), sparsity=rho,
                               block_q=32, block_kv=32,
                               interpret=True).swapaxes(1, 2)
        ref = flash_mha_ref(q, k, v, n_kv_heads=2, sparsity=rho,
                            block_q=32, block_kv=32)
        np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)

    def test_bf16(self):
        q, k, v = _qkv(1, 64, 64, 2, 2, 32, jnp.bfloat16)
        out = flash_mha_pallas(q.swapaxes(1, 2), k.swapaxes(1, 2),
                               v.swapaxes(1, 2), block_q=32, block_kv=32,
                               interpret=True).swapaxes(1, 2)
        ref = flash_mha_ref(q, k, v, n_kv_heads=2)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=3e-2, atol=3e-2)


class TestPagedAttention:
    @pytest.mark.parametrize("B,Hq,Hkv,D,page,npg,ptot", [
        (2, 4, 2, 16, 8, 4, 16),
        (3, 8, 8, 32, 16, 3, 12),
        (1, 4, 1, 64, 8, 6, 8),
    ])
    def test_vs_ref(self, B, Hq, Hkv, D, page, npg, ptot):
        ks = jax.random.split(KEY, 5)
        q = jax.random.normal(ks[0], (B, Hq, D))
        kp = jax.random.normal(ks[1], (ptot, page, Hkv, D))
        vp = jax.random.normal(ks[2], (ptot, page, Hkv, D))
        bt = jax.random.randint(ks[3], (B, npg), 0, ptot)
        lengths = jax.random.randint(ks[4], (B,), 1, npg * page + 1)
        out = paged_decode_attention_pallas(q, kp, vp, bt, lengths,
                                            interpret=True)
        ref = paged_decode_attention_ref(q, kp, vp, bt, lengths)
        np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)

    def test_length_one(self):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (2, 4, 16))
        kp = jax.random.normal(ks[1], (4, 8, 2, 16))
        vp = jax.random.normal(ks[2], (4, 8, 2, 16))
        bt = jnp.zeros((2, 2), jnp.int32)
        lengths = jnp.ones((2,), jnp.int32)
        out = paged_decode_attention_pallas(q, kp, vp, bt, lengths,
                                            interpret=True)
        ref = paged_decode_attention_ref(q, kp, vp, bt, lengths)
        np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


class TestPagedChunkAttention:
    """Chunk-query generalization (q [B,Sq,Hq,D], token-granular page
    masks, partials out) — the serving executor's paged backend."""

    @pytest.mark.parametrize("B,Sq,Hq,Hkv,D,page,npg,ptot", [
        (2, 4, 4, 2, 16, 8, 4, 16),
        (3, 6, 8, 2, 8, 16, 3, 12),
        (1, 5, 4, 1, 64, 8, 6, 8),
    ])
    def test_vs_ref(self, B, Sq, Hq, Hkv, D, page, npg, ptot):
        from repro.kernels.paged_attention.kernel import \
            paged_chunk_attention_pallas
        from repro.kernels.paged_attention.ref import \
            paged_chunk_attention_ref
        ks = jax.random.split(KEY, 5)
        q = jax.random.normal(ks[0], (B, Sq, Hq, D))
        kp = jax.random.normal(ks[1], (ptot, Hkv, page, D))   # head-major
        vp = jax.random.normal(ks[2], (ptot, Hkv, page, D))
        bt = jax.random.randint(ks[3], (B, npg), 0, ptot)
        mask = jax.random.uniform(ks[4], (B, npg * page)) < 0.6
        mask = mask.at[0, :page].set(False)     # a fully-masked page
        got = paged_chunk_attention_pallas(q, kp, vp, bt, mask,
                                           interpret=True)
        want = paged_chunk_attention_ref(q, kp, vp, bt, mask)
        for g, w, name in zip(got, want, ("m", "l", "acc")):
            np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-3,
                                       err_msg=name)

    @pytest.mark.parametrize("tq,page,d,kv_bytes,masked,tiles", [
        # the tiled test below: the last sub-tile cut at 1,000 tokens
        (528, 1152, 8, 4, True, [(0, 512, 512), (512, 512, 488)]),
        # ardit-causal-forcing: 880-row query tiles, 2,688-token pages
        (880, 2688, 96, 2, False, [(lo, 512, 512) for lo in
                                   range(0, 2560, 512)]
         + [(2560, 128, 80)]),
        # ardit-self-forcing at 480p: 944-row query tiles, 4,736-token
        # (37 x 128) pages, bf16 and fp8 pools
        (944, 4736, 128, 2, False, [(lo, 512, 512) for lo in
                                    range(0, 4608, 512)]
         + [(4608, 128, 72)]),
        (944, 4736, 128, 1, True, [(lo, 512, 512) for lo in
                                   range(0, 4608, 512)]
         + [(4608, 128, 72)]),
    ])
    def test_sub_tile_rule(self, tq, page, d, kv_bytes, masked, tiles):
        """The kernel walks a page's valid prefix (ring pages: the
        chunk, here 4,680 tokens for the 4,736-token page, 2,640 for the
        2,688-token page, 1,000 for the 1,152-token one) in sub-tiles
        sized from the shapes and the scoped-VMEM budget; the sink's 77
        tokens take one 128-token sub-tile; a page that is not
        128-aligned is one sub-tile."""
        from repro.kernels.paged_attention.kernel import (
            context_tile, sub_tiles)
        extent = {1152: 1000, 2688: 2640, 4736: 4680}[page]
        tk = context_tile(tq, page, d, 2 if kv_bytes < 4 else 4, kv_bytes,
                          masked)
        assert list(sub_tiles(extent, page, tk)) == tiles
        assert sub_tiles(77, page, tk) == ((0, 128, 77),)
        assert context_tile(tq, page + 8, d, 2, kv_bytes, masked) == page + 8
        assert sub_tiles(70, 72, 72) == ((0, 72, 70),)

    @pytest.mark.parametrize("masked", [True, False])
    def test_tiled_layer_stacked_vs_ref(self, masked):
        """Two query tiles (r = 1,040 rows: two 528-row tiles, the last
        16 rows padding) over 1,152-token pages, reading layer 1 of a
        layer-stacked pool in place; a ring page's 1,000 valid tokens
        are two 512-token sub-tiles, the last cut at 488, and the sink's
        77 one 128-token sub-tile; the page tails are never read."""
        from repro.kernels.paged_attention.kernel import (
            context_tile, paged_chunk_attention_pallas, query_tile,
            sub_tiles)
        from repro.kernels.paged_attention.ref import \
            paged_chunk_attention_ref
        B, Sq, Hq, Hkv, D, page, npg, ptot = 2, 520, 4, 2, 8, 1152, 3, 8
        sink, tc = 77, 1000
        assert query_tile(Sq * Hq // Hkv) == (528, 1056)
        tk = context_tile(528, page, D, 4, 4, masked)
        assert sub_tiles(tc, page, tk) == ((0, 512, 512), (512, 512, 488))
        ks = jax.random.split(KEY, 5)
        q = jax.random.normal(ks[0], (B, Sq, Hq, D))
        kp = jax.random.normal(ks[1], (2, ptot, Hkv, page, D))
        vp = jax.random.normal(ks[2], (2, ptot, Hkv, page, D))
        bt = jax.random.randint(ks[3], (B, npg), 0, ptot)
        mask = None
        if masked:
            m = np.array(jax.random.uniform(ks[4], (B, npg, page)) < 0.6)
            m[:, 0, sink:] = False
            m[:, 1:, tc:] = False
            m[1, 2] = False                     # a fully-masked page
            mask = jnp.asarray(m.reshape(B, -1))
        got = paged_chunk_attention_pallas(
            q, kp, vp, bt, mask, jnp.int32(1), sink=sink, chunk_tokens=tc,
            interpret=True)
        want = paged_chunk_attention_ref(q, kp[1], vp[1], bt, mask,
                                         sink=sink, chunk_tokens=tc)
        for g, w, name in zip(got, want, ("m", "l", "acc")):
            np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-3,
                                       err_msg=name)

    @pytest.mark.parametrize("D", [128, 96])
    def test_published_head_shape_vs_ref(self, D):
        """One KV head at published widths (2,640-token chunk queries,
        2,688-token pages, head_dim 128 or 96 — the two AR-DiT
        configs): three 880-row query tiles, each page's 2,640-token
        prefix in five 512-token context sub-tiles and a sixth cut at 80
        tokens, a sparsified mask."""
        from repro.kernels.paged_attention.kernel import \
            paged_chunk_attention_pallas
        from repro.kernels.paged_attention.ref import \
            paged_chunk_attention_ref
        sq, page, n, sink = 2640, 2688, 2, 77
        ks = jax.random.split(KEY, 4)
        q = jax.random.normal(ks[0], (1, sq, 1, D), jnp.bfloat16)
        kp = jax.random.normal(ks[1], (3, 1, page, D), jnp.bfloat16)
        vp = jax.random.normal(ks[2], (3, 1, page, D), jnp.bfloat16)
        bt = jnp.asarray([[2, 0]], jnp.int32)
        m = np.array(jax.random.uniform(ks[3], (1, n, page)) < 0.5)
        m[:, 0, sink:] = False
        m[:, 1:, sq:] = False
        mask = jnp.asarray(m.reshape(1, -1))
        got = paged_chunk_attention_pallas(q, kp, vp, bt, mask, sink=sink,
                                           chunk_tokens=sq, interpret=True)
        want = paged_chunk_attention_ref(q, kp, vp, bt, mask, sink=sink,
                                         chunk_tokens=sq)
        for g, w, name in zip(got, want, ("m", "l", "acc")):
            np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-3,
                                       err_msg=name)


    @pytest.mark.parametrize("masked", [True, False])
    def test_sink_wider_than_ring_vs_ref(self, masked):
        """A sink prefix (300 tokens) longer than the ring pages' (100) on
        384-token pages: the sink is one 384-token sub-tile, a ring page
        one of 128, and the sink's visible tokens all lie past the ring
        sub-tile's end."""
        from repro.kernels.paged_attention.kernel import \
            paged_chunk_attention_pallas
        from repro.kernels.paged_attention.ref import \
            paged_chunk_attention_ref
        B, Sq, D, page, n, sink, tc = 2, 8, 16, 384, 3, 300, 100
        ks = jax.random.split(KEY, 4)
        q = jax.random.normal(ks[0], (B, Sq, 2, D))
        kp = jax.random.normal(ks[1], (6, 1, page, D))
        vp = jax.random.normal(ks[2], (6, 1, page, D))
        bt = jnp.asarray([[0, 1, 2], [3, 4, 5]], jnp.int32)
        mask = None
        if masked:
            m = np.zeros((B, n, page), bool)
            m[:, 0, 200:sink] = True
            m[:, 1:, :tc] = np.array(jax.random.uniform(ks[3], (B, 2, tc))
                                     < 0.5)
            mask = jnp.asarray(m.reshape(B, -1))
        got = paged_chunk_attention_pallas(q, kp, vp, bt, mask, sink=sink,
                                           chunk_tokens=tc, interpret=True)
        want = paged_chunk_attention_ref(q, kp, vp, bt, mask, sink=sink,
                                         chunk_tokens=tc)
        for g, w, name in zip(got, want, ("m", "l", "acc")):
            np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-3,
                                       err_msg=name)

    @pytest.mark.parametrize("masked", [True, False])
    @pytest.mark.parametrize("kv_dtype", ["bfloat16", "float8_e4m3fn"])
    def test_prime_page_vs_ref(self, kv_dtype, masked):
        """The served 480p page geometry at one KV head: 64 query rows
        against 4,736-token pages, 37 x 128, for which no large divisor
        exists, from a bf16 or an fp8 pool.  Ring pages are walked in
        512-token sub-tiles whose last slice is cut at the 4,680-token
        chunk, the sink page in one 128-token sub-tile cut at 77.  The
        masked case sparsifies every page and leaves stream 1's last page
        dead (its blocks re-pointed, its sub-tiles skipped)."""
        from repro.kernels.paged_attention.kernel import \
            paged_chunk_attention_pallas
        from repro.kernels.paged_attention.ref import \
            paged_chunk_attention_ref
        B, sq, page, n, sink, tc, D = 2, 64, 4736, 3, 77, 4680, 128
        ks = jax.random.split(KEY, 4)
        q = jax.random.normal(ks[0], (B, sq, 1, D), jnp.bfloat16)
        kp = jax.random.normal(ks[1], (5, 1, page, D)).astype(kv_dtype)
        vp = jax.random.normal(ks[2], (5, 1, page, D)).astype(kv_dtype)
        bt = jnp.asarray([[4, 0, 2], [1, 3, 0]], jnp.int32)
        mask = None
        if masked:
            m = np.array(jax.random.uniform(ks[3], (B, n, page)) < 0.5)
            m[:, 0, sink:] = False
            m[:, 1:, tc:] = False
            m[1, 2] = False                     # a dead page
            mask = jnp.asarray(m.reshape(B, -1))
        got = paged_chunk_attention_pallas(q, kp, vp, bt, mask, sink=sink,
                                           chunk_tokens=tc, interpret=True)
        want = paged_chunk_attention_ref(q, kp, vp, bt, mask, sink=sink,
                                         chunk_tokens=tc)
        for g, w, name in zip(got, want, ("m", "l", "acc")):
            np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-3,
                                       err_msg=name)

@pytest.mark.parametrize("backend,env,want", [
    ("cpu", None, "ref"),
    ("cpu", "1", "interpret"),
    ("tpu", None, "pallas"),
    ("tpu", "1", RuntimeError),
])
def test_kernel_mode(monkeypatch, backend, env, want):
    """On a TPU the compiled kernel always runs (the interpret switch is
    an error there); off it, the oracle or interpret mode."""
    from repro.kernels import mode
    monkeypatch.setattr(mode.jax, "default_backend", lambda: backend)
    if env is None:
        monkeypatch.delenv(mode.INTERPRET_ENV, raising=False)
    else:
        monkeypatch.setenv(mode.INTERPRET_ENV, env)
    if want is RuntimeError:
        with pytest.raises(RuntimeError):
            mode.kernel_mode()
    else:
        assert mode.kernel_mode() == want


class TestFp8Matmul:
    @pytest.mark.parametrize("M,K,N", [(64, 64, 64), (128, 256, 64),
                                       (32, 32, 32)])
    def test_vs_ref(self, M, K, N):
        ks = jax.random.split(KEY, 2)
        x = jax.random.normal(ks[0], (M, K))
        w = jax.random.normal(ks[1], (K, N))
        xq, sx = quantize_fp8_ref(x, 1)
        wq, sw = quantize_fp8_ref(w, 0)
        out = fp8_matmul_pallas(xq, wq, sx, sw, block_m=32, block_n=32,
                                block_k=32, interpret=True)
        ref = fp8_matmul_ref(xq, wq, sx, sw)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_quantization_error_bounded(self):
        x = jax.random.normal(KEY, (64, 128))
        xq, sx = quantize_fp8_ref(x, 1)
        deq = xq.astype(jnp.float32) * sx
        # e4m3 relative error within a scaled block is < 2^-2 of the max
        err = jnp.max(jnp.abs(deq - x))
        amax = jnp.max(jnp.abs(x))
        assert float(err) < float(amax) * 0.07


class TestSSD:
    @pytest.mark.parametrize("B,S,H,P,N,chunk", [
        (2, 64, 4, 16, 8, 16),
        (1, 100, 2, 8, 16, 32),     # non-divisible padding path
        (2, 33, 3, 8, 4, 8),
    ])
    def test_vs_ref(self, B, S, H, P, N, chunk):
        ks = jax.random.split(KEY, 5)
        x = jax.random.normal(ks[0], (B, S, H, P))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
        A = -jnp.exp(jax.random.normal(ks[2], (H,)))
        Bm = jax.random.normal(ks[3], (B, S, 1, N))
        Cm = jax.random.normal(ks[4], (B, S, 1, N))
        y1, f1 = ssd_pallas(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
        y2, f2 = ssd_ref(x, dt, A, Bm, Cm, chunk=chunk)
        np.testing.assert_allclose(y1, y2, rtol=5e-4, atol=5e-4)
        np.testing.assert_allclose(f1, f2, rtol=5e-4, atol=5e-4)

    def test_init_state_continuation(self):
        ks = jax.random.split(KEY, 6)
        B, S, H, P, N = 2, 48, 2, 8, 4
        x = jax.random.normal(ks[0], (B, S, H, P))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
        A = -jnp.exp(jax.random.normal(ks[2], (H,)))
        Bm = jax.random.normal(ks[3], (B, S, 1, N))
        Cm = jax.random.normal(ks[4], (B, S, 1, N))
        s0 = jax.random.normal(ks[5], (B, H, P, N))
        y1, f1 = ssd_pallas(x, dt, A, Bm, Cm, chunk=16, init_state=s0,
                            interpret=True)
        y2, f2 = ssd_ref(x, dt, A, Bm, Cm, chunk=16, init_state=s0)
        np.testing.assert_allclose(y1, y2, rtol=5e-4, atol=5e-4)
        np.testing.assert_allclose(f1, f2, rtol=5e-4, atol=5e-4)

    def test_chunked_equals_sequential(self):
        """SSD chunked scan == naive per-token recurrence."""
        ks = jax.random.split(KEY, 5)
        B, S, H, P, N = 1, 19, 2, 4, 4
        x = jax.random.normal(ks[0], (B, S, H, P))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
        A = -jnp.exp(jax.random.normal(ks[2], (H,)))
        Bm = jax.random.normal(ks[3], (B, S, 1, N))
        Cm = jax.random.normal(ks[4], (B, S, 1, N))
        st = jnp.zeros((B, H, P, N))
        ys = []
        for t in range(S):
            y, st = ssd_decode_ref(x[:, t], dt[:, t], A, Bm[:, t],
                                   Cm[:, t], st)
            ys.append(y)
        y_seq = jnp.stack(ys, 1)
        y_chunk, f_chunk = ssd_ref(x, dt, A, Bm, Cm, chunk=8)
        np.testing.assert_allclose(y_chunk, y_seq, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(f_chunk, st, rtol=2e-4, atol=2e-4)
