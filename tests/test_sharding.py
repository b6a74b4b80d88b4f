"""Sharding rules, logical axes, HLO cost analyzer, small-mesh lowering."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import ShapeConfig, get_config
from repro.distributed import sharding as shd
from repro.distributed.logical import resolve_spec
from repro.launch.hlo_cost import analyze_text

pytestmark = pytest.mark.slow     # JAX-lowering/compiling sharding tests: slow tier

AUTO = jax.sharding.AxisType.Auto


class TestParamRules:
    def test_rank_padding_for_stacked_layers(self):
        # scan-stacked [L, d, f] weights get a leading None
        assert shd.param_pspec(("layers", "mlp", "w_gate"), 3) == \
            P(None, "data", "model")
        assert shd.param_pspec(("layers", "attn", "wo"), 3) == \
            P(None, "model", "data")

    def test_serve_rules_drop_fsdp(self):
        assert shd.param_pspec(("layers", "attn", "wq"), 3, serve=True) == \
            P(None, None, "model")

    def test_unknown_params_replicated(self):
        assert shd.param_pspec(("final_norm",), 1) == P(None)

    def test_vocab_padding_divisible(self):
        for arch in ("minicpm-2b", "whisper-medium", "mamba2-780m",
                     "granite-moe-1b-a400m", "internvl2-26b"):
            cfg = get_config(arch)
            assert cfg.padded_vocab % 256 == 0
            assert cfg.padded_vocab >= cfg.vocab_size


class TestLogicalRules:
    def test_duplicate_mesh_axis_dropped(self):
        spec = resolve_spec(["batch", None, "heads"],
                            {"batch": ("data",), "heads": ("data",)})
        assert spec == P("data", None, None)

    def test_multi_axis_batch(self):
        spec = resolve_spec(["batch", None],
                            {"batch": ("pod", "data")})
        assert spec == P(("pod", "data"), None)


class TestHloCost:
    def test_matmul_exact(self):
        M, N, K = 64, 32, 128
        c = jax.jit(lambda a, b: a @ b).lower(
            jnp.zeros((M, K)), jnp.zeros((K, N))).compile()
        assert analyze_text(c.as_text()).flops == 2 * M * N * K

    def test_scan_trip_count_multiplied(self):
        L, M = 5, 32
        def f(x, ws):
            return jax.lax.scan(lambda h, w: (h @ w, None), x, ws)[0]
        c = jax.jit(f).lower(jnp.zeros((M, M)),
                             jnp.zeros((L, M, M))).compile()
        assert analyze_text(c.as_text()).flops == L * 2 * M ** 3

    def test_nested_scan(self):
        L, R, M = 4, 3, 16
        def f(x, ws):
            def outer(h, w):
                h2, _ = jax.lax.scan(lambda a, _: (a @ w, None), h,
                                     None, length=R)
                return h2, None
            return jax.lax.scan(outer, x, ws)[0]
        c = jax.jit(f).lower(jnp.zeros((M, M)),
                             jnp.zeros((L, M, M))).compile()
        assert analyze_text(c.as_text()).flops == L * R * 2 * M ** 3

    def test_hbm_bytes_positive_and_scan_scaled(self):
        L, M = 8, 64
        def f(x, ws):
            return jax.lax.scan(lambda h, w: (h @ w, None), x, ws)[0]
        c = jax.jit(f).lower(jnp.zeros((M, M)),
                             jnp.zeros((L, M, M))).compile()
        cost = analyze_text(c.as_text())
        # traffic should be ~ L * (weight slice + activations), i.e.
        # far below L * full-stack bytes and above one iteration's
        lo = 2 * M * M * 4
        hi = 3 * L * (L * M * M * 4)
        assert lo < cost.hbm_bytes < hi


class TestSmallMeshLowering:
    """The full lowering path on a 1x1 debug mesh (reduced configs)."""

    @pytest.mark.parametrize("arch", ["minitron-8b", "mamba2-780m",
                                      "jamba-v0.1-52b"])
    def test_lower_train_reduced(self, arch):
        from repro.launch.lowering import lower_cell
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(AUTO, AUTO))
        cfg = get_config(arch).reduced()
        shape = ShapeConfig("t", "train", 32, 2)
        lowered = lower_cell(cfg, mesh, shape)
        compiled = lowered.compile()
        assert compiled.cost_analysis() is not None

    @pytest.mark.parametrize("arch,kind", [("minitron-8b", "decode"),
                                           ("mamba2-780m", "decode"),
                                           ("whisper-medium", "prefill")])
    def test_lower_serving_reduced(self, arch, kind):
        from repro.launch.lowering import lower_cell
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(AUTO, AUTO))
        cfg = get_config(arch).reduced()
        shape = ShapeConfig("t", kind, 64, 2)
        compiled = lower_cell(cfg, mesh, shape).compile()
        assert compiled is not None
