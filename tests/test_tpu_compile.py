"""The serving path compiles for a TPU v5e chip at published widths.

No chip is needed: the TPU compiler compiles for a described (not
attached) ``v5e:2x2`` topology, which refuses what interpret mode
accepts — illegal block shapes, more scoped VMEM than a kernel may use,
programs that do not fit the chip's HBM.  The topology is described in
a module fixture (never at import time), and every test here skips when
it cannot be.  Code that asks ``jax.default_backend()`` still sees the
CPU, so the step test steers the kernel dispatcher to the Pallas path.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels.paged_attention.kernel import paged_chunk_attention_pallas
from repro.models import ardit as A

ARCHS = ["ardit-self-forcing", "ardit-causal-forcing"]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", enabled)
    mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("arch", ARCHS)
def test_paged_chunk_kernel_compiles(one_chip, arch, masked):
    """One layer's chunk queries against a full window of head-major
    pages (sink + 7 ring pages), all-visible and masked."""
    cfg = get_config(arch)
    tc, page = A.chunk_tokens(cfg), A.page_tokens(cfg)
    n = 1 + cfg.ardit_window_chunks
    q = _spec((1, tc, cfg.n_heads, cfg.head_dim), jnp.bfloat16, one_chip)
    pages = _spec((n + 1, cfg.n_kv_heads, page, cfg.head_dim),
                  jnp.bfloat16, one_chip)
    table = _spec((1, n), jnp.int32, one_chip)
    mask = _spec((1, n * page), jnp.bool_, one_chip) if masked else None
    compiled = jax.jit(lambda q, k, v, t, m: paged_chunk_attention_pallas(
        q, k, v, t, m, sink=A.COND_TOKENS, chunk_tokens=tc)).lower(
            q, pages, pages, table, mask).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_denoise_step_paged_compiles(one_chip, monkeypatch):
    """The fused denoise step of ``ardit-self-forcing`` (batch of 1,
    full window, one stream's pool) with the Pallas kernel inside."""
    import repro.kernels.paged_attention.ops as ops
    monkeypatch.setattr(ops, "kernel_mode", lambda: "pallas")
    cfg = get_config("ardit-self-forcing")
    params = jax.tree_util.tree_map(
        lambda s: _spec(s.shape, s.dtype, one_chip),
        jax.eval_shape(lambda: A.init_params(cfg, jax.random.PRNGKey(0))))
    tc, page = A.chunk_tokens(cfg), A.page_tokens(cfg)
    n = 1 + cfg.ardit_window_chunks
    pool = _spec((cfg.n_layers, n, cfg.n_kv_heads, page, cfg.head_dim),
                 jnp.bfloat16, one_chip)
    f32 = _spec((1,), jnp.float32, one_chip)
    compiled = A.denoise_step_paged.lower(
        cfg, params, _spec((1, tc, A.LATENT_CH), jnp.float32, one_chip),
        f32, f32, pool, pool, _spec((1, n), jnp.int32, one_chip),
        _spec((1, n * page), jnp.bool_, one_chip), None,
        _spec((1,), jnp.int32, one_chip),
        _spec((1,), jnp.bool_, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _body_ops(hlo: str):
    """(instruction, op_name) of the top-level instructions of the
    compiled layer loop's body."""
    import re
    body = re.search(r"body=%([\w.\-]+)", hlo).group(1)
    comp = hlo.split(f"\n%{body} ", 1)[1].split("\n}", 1)[0]
    out = []
    for line in comp.split("\n")[1:]:
        name = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line)
        op_name = re.search(r'op_name="([^"]*)"', line)
        if name and op_name:
            out.append((name.group(1), op_name.group(1)))
    return out


def test_denoise_step_device_scopes(one_chip, monkeypatch):
    """The benchmark's step shape (batch of 2, masked, 4,680-token
    chunks on 15 layers) as the TPU compiler fuses it: every operation of
    the layer body lies in one of the five ``ardit/*`` scopes that a
    device trace is read by, the Pallas kernel in ``ardit/paged_attn``
    and both MLP matmuls in ``ardit/mlp``.  Only the loop's own slicing
    of the stacked weights and KV outputs is outside them."""
    import dataclasses
    import re
    import repro.kernels.paged_attention.ops as ops
    monkeypatch.setattr(ops, "kernel_mode", lambda: "pallas")
    cfg = dataclasses.replace(get_config("ardit-self-forcing"),
                              n_layers=15, ardit_frame_tokens=1560)
    params = jax.tree_util.tree_map(
        lambda s: _spec(s.shape, s.dtype, one_chip),
        jax.eval_shape(lambda: A.init_params(cfg, jax.random.PRNGKey(0))))
    tc, page = A.chunk_tokens(cfg), A.page_tokens(cfg)
    n, b = 1 + cfg.ardit_window_chunks, 2
    pool = _spec((cfg.n_layers, b * n + 1, cfg.n_kv_heads, page,
                  cfg.head_dim), jnp.bfloat16, one_chip)
    f32 = _spec((b,), jnp.float32, one_chip)
    hlo = A.denoise_step_paged.lower(
        cfg, params, _spec((b, tc, A.LATENT_CH), jnp.float32, one_chip),
        f32, f32, pool, pool, _spec((b, n), jnp.int32, one_chip),
        _spec((b, n * page), jnp.bool_, one_chip), None,
        _spec((b,), jnp.int32, one_chip),
        _spec((b,), jnp.bool_, one_chip)).compile().as_text()
    scope = re.compile(r"ardit/(qkv|paged_attn|segment_attn|out_proj|mlp)/")
    ops_ = _body_ops(hlo)
    layer = [(i, o) for i, o in ops_ if "/closed_call/" in o]
    assert layer and all(len(scope.findall(o)) == 1 for _, o in layer)
    assert {scope.search(o).group(1) for _, o in layer} == {
        "qkv", "paged_attn", "segment_attn", "out_proj", "mlp"}
    outside = {o.rsplit("/", 1)[1] for i, o in ops_ if (i, o) not in layer}
    assert outside <= {"dynamic_slice", "dynamic_update_slice", "add"}
    pallas = [o for i, o in layer if i.startswith("paged_chunk_attention")]
    assert pallas and all("ardit/paged_attn/" in o for o in pallas)
    assert sum(o.endswith("ardit/mlp/dot_general") for _, o in layer) == 2
