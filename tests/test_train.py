"""Train substrate: optimizer, schedules, checkpoint/restart with
elastic resharding, gradient compression, data pipeline."""
import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ShapeConfig, get_config
from repro.data import pipeline as dp
from repro.models import registry
from repro.train import checkpoint as ckpt
from repro.train import compression as comp
from repro.train import loop as train_loop
from repro.train import optimizer as opt

pytestmark = pytest.mark.slow     # JAX-compiling train-step tests: slow tier

KEY = jax.random.PRNGKey(0)
AUTO = jax.sharding.AxisType.Auto


def _small_state(arch="minicpm-2b"):
    cfg = get_config(arch).reduced()
    api = registry.get_api(cfg)
    params = api.init(cfg, KEY)
    return cfg, train_loop.TrainState(params, opt.init_opt_state(params))


class TestOptimizer:
    def test_loss_decreases(self):
        cfg, state = _small_state()
        ocfg = opt.OptConfig(lr=1e-3, warmup_steps=2, total_steps=50)
        step = jax.jit(train_loop.make_train_step(cfg, ocfg))
        tokens = jax.random.randint(KEY, (4, 32), 0, cfg.vocab_size)
        batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
        losses = []
        for _ in range(6):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]

    def test_microbatch_equivalence(self):
        """Grad accumulation over 2 microbatches == full batch step."""
        cfg, state = _small_state()
        ocfg = opt.OptConfig(lr=1e-3, warmup_steps=1, total_steps=10)
        tokens = jax.random.randint(KEY, (4, 16), 0, cfg.vocab_size)
        batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
        s1, m1 = train_loop.make_train_step(cfg, ocfg, microbatches=1)(
            state, batch)
        s2, m2 = train_loop.make_train_step(cfg, ocfg, microbatches=2)(
            state, batch)
        # CE normalizes per-microbatch; losses agree, grads within tol
        assert float(m1["loss"]) == pytest.approx(float(m2["loss"]),
                                                  rel=1e-3)
        for a, b in zip(jax.tree_util.tree_leaves(s1.params),
                        jax.tree_util.tree_leaves(s2.params)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=2e-2, atol=2e-5)

    def test_grad_clip(self):
        g = {"w": jnp.full((10,), 100.0)}
        p = {"w": jnp.zeros((10,))}
        st = opt.init_opt_state(p)
        cfg = opt.OptConfig(grad_clip=1.0, lr=1.0, warmup_steps=0,
                            total_steps=1)
        _, _, metrics = opt.adamw_update(cfg, p, g, st)
        assert float(metrics["grad_norm"]) > 100.0   # pre-clip norm logged

    def test_wsd_schedule_shape(self):
        cfg = opt.OptConfig(lr=1.0, schedule="wsd", warmup_steps=10,
                            total_steps=100, decay_frac=0.2)
        lrs = [float(opt.lr_at(cfg, jnp.asarray(s)))
               for s in [0, 5, 10, 50, 79, 90, 100]]
        assert lrs[0] == 0.0
        assert lrs[1] == pytest.approx(0.5)
        assert lrs[2] == lrs[3] == pytest.approx(1.0)     # stable phase
        assert lrs[4] == pytest.approx(1.0, abs=0.05)
        assert lrs[5] < 1.0                                # decaying
        assert lrs[6] == pytest.approx(0.1, abs=0.02)     # floor

    def test_weight_decay_mask(self):
        assert opt._decay_mask([jax.tree_util.DictKey("wq")])
        assert not opt._decay_mask([jax.tree_util.DictKey("attn_norm")])
        assert not opt._decay_mask([jax.tree_util.DictKey("dt_bias")])


class TestCheckpoint:
    def test_roundtrip_and_latest(self):
        _, state = _small_state()
        with tempfile.TemporaryDirectory() as d:
            assert ckpt.latest_step(d) is None
            ckpt.save(d, 7, state)
            ckpt.save(d, 12, state)
            assert ckpt.latest_step(d) == 12
            restored = ckpt.restore(d, 12, state)
            for a, b in zip(jax.tree_util.tree_leaves(state),
                            jax.tree_util.tree_leaves(restored)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_async_save(self):
        _, state = _small_state()
        with tempfile.TemporaryDirectory() as d:
            t = ckpt.save(d, 3, state, blocking=False)
            t.join()
            assert ckpt.latest_step(d) == 3

    def test_elastic_resharding_restore(self):
        """Restore under a (trivially different) mesh sharding."""
        _, state = _small_state()
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(AUTO, AUTO))
        from repro.distributed import sharding as shd
        shardings = train_loop.TrainState(
            shd.param_shardings(state.params, mesh),
            opt.OptState(
                jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec()),
                shd.param_shardings(state.opt_state.m, mesh),
                shd.param_shardings(state.opt_state.v, mesh)))
        with tempfile.TemporaryDirectory() as d:
            ckpt.save(d, 1, state)
            restored = ckpt.restore(d, 1, state, shardings=shardings)
            for a, b in zip(jax.tree_util.tree_leaves(state),
                            jax.tree_util.tree_leaves(restored)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_resume_is_bitwise(self):
        """Kill/restart equivalence: step k..n from a checkpoint equals
        an uninterrupted run (same data, same state)."""
        cfg, state = _small_state()
        shape = ShapeConfig("t", "train", 16, 4)
        ocfg = opt.OptConfig(lr=1e-3, warmup_steps=1, total_steps=10)
        step = jax.jit(train_loop.make_train_step(cfg, ocfg))

        def run(state, lo, hi):
            for s in range(lo, hi):
                state, _ = step(state, dp.global_batch(cfg, shape, s))
            return state

        full = run(state, 0, 4)
        with tempfile.TemporaryDirectory() as d:
            mid = run(state, 0, 2)
            ckpt.save(d, 2, mid)
            resumed = ckpt.restore(d, 2, mid)
            part = run(resumed, 2, 4)
        for a, b in zip(jax.tree_util.tree_leaves(full.params),
                        jax.tree_util.tree_leaves(part.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestCompression:
    def test_roundtrip_error_bounded(self):
        g = jax.random.normal(KEY, (333, 7)) * 3.0
        q, scale, resid = comp.compress(g)
        deq = comp.decompress(q, scale, g.shape)
        np.testing.assert_allclose(deq + resid, g, rtol=1e-5, atol=1e-6)
        # per-block error <= scale/2 (round-to-nearest int8)
        assert float(jnp.max(jnp.abs(resid))) <= float(jnp.max(scale))

    def test_error_feedback_converges(self):
        """With EF, the accumulated applied update tracks the true sum
        of gradients (bias-free), unlike plain quantization."""
        gs = [jax.random.normal(jax.random.PRNGKey(i), (64,)) * 0.1
              for i in range(30)]
        err = jnp.zeros((64,))
        applied = jnp.zeros((64,))
        for g in gs:
            q, scale, err = comp.compress(g + err)
            applied += comp.decompress(q, scale, g.shape)
        true = sum(gs)
        # residual bounded by one quantization step, not O(T) drift
        assert float(jnp.max(jnp.abs(applied - true))) <= \
            float(jnp.max(jnp.abs(err))) + 1e-5


class TestData:
    def test_dp_layout_invariance(self):
        cfg = get_config("minitron-8b").reduced()
        shape = ShapeConfig("t", "train", 16, 8)
        full = dp.global_batch(cfg, shape, step=3)
        parts = [dp.global_batch(cfg, shape, step=3,
                                 rows=dp.shard_rows(8, r, 4))
                 for r in range(4)]
        np.testing.assert_array_equal(
            full["tokens"], np.concatenate([p["tokens"] for p in parts]))

    def test_steps_differ(self):
        cfg = get_config("minitron-8b").reduced()
        shape = ShapeConfig("t", "train", 16, 2)
        a = dp.global_batch(cfg, shape, step=0)
        b = dp.global_batch(cfg, shape, step=1)
        assert not np.array_equal(a["tokens"], b["tokens"])

    def test_targets_are_shifted_tokens(self):
        cfg = get_config("minitron-8b").reduced()
        shape = ShapeConfig("t", "train", 16, 2)
        batch = dp.global_batch(cfg, shape, step=0)
        np.testing.assert_array_equal(batch["tokens"][:, 1:],
                                      batch["targets"][:, :-1])
