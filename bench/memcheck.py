#!/usr/bin/env python3
"""Does the paged denoise step fit one v5e chip?  Compiled for a described
(not attached) v5e, no chip needed:

    JAX_PLATFORMS=cpu python3 bench/memcheck.py ardit-causal-forcing 1 1
    JAX_PLATFORMS=cpu python3 bench/memcheck.py ardit-causal-forcing 2 2

Arguments: configuration name, batch rows, pool streams.  Compiles
``denoise_step_paged`` with a full window (sink + 7 ring pages per row,
explicit masks) over a pool of that many streams and prints the compiler's
memory analysis, or the compiler's refusal when the program does not fit.
This is why ``ardit-causal-forcing`` has its pool pinned at one stream:
at head_dim 96 XLA copies the pool into the kernel's tiled layout on every
launch, and a 2-stream pool at batch 2 does not fit.  And why
``ardit-self-forcing`` has its pinned at two: at the three streams the
program would size, a batch-3 step leaves no room for a spill's gather.

    JAX_PLATFORMS=cpu python3 bench/memcheck.py ardit-self-forcing 3 3
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def check(name: str, batch: int, streams: int) -> dict:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    import repro.kernels.paged_attention.ops as ops
    from repro.configs.base import get_config
    from repro.models import ardit as A
    from bench.reference.ardit import _make_params, dims_of

    jax.config.update("jax_enable_compilation_cache", False)
    ops.kernel_mode = lambda: "pallas"     # the CPU would pick the oracle
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        conf = json.load(f)
    cfg = dataclasses.replace(get_config(conf["arch"]), **conf["model"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    sh = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    d = dims_of(conf)
    params = jax.tree_util.tree_map(
        lambda s: spec(s.shape, s.dtype),
        jax.eval_shape(lambda: _make_params(d, jax.random.PRNGKey(0),
                                            conf["model"]["param_dtype"])))
    tc, page = A.chunk_tokens(cfg), A.page_tokens(cfg)
    n = 1 + cfg.ardit_window_chunks
    pool = spec((cfg.n_layers, streams * n, cfg.n_kv_heads, page,
                 cfg.head_dim), jnp.dtype(cfg.kv_dtype))
    f32 = spec((batch,), jnp.float32)
    out = {"config": name, "batch": batch, "pool_streams": streams}
    try:
        compiled = A.denoise_step_paged.lower(
            cfg, params, spec((batch, tc, A.LATENT_CH), jnp.float32), f32,
            f32, pool, pool, spec((batch, n), jnp.int32),
            spec((batch, n * page), jnp.bool_), None,
            spec((batch,), jnp.int32), spec((batch,), jnp.bool_)).compile()
    except Exception as e:                 # the compiler's refusal
        out.update(fits=False, error=str(e).splitlines()[0][:300])
        return out
    m = compiled.memory_analysis()
    out.update(fits=True,
               argument_gib=m.argument_size_in_bytes / 2 ** 30,
               temp_gib=m.temp_size_in_bytes / 2 ** 30,
               output_gib=m.output_size_in_bytes / 2 ** 30)
    return out


if __name__ == "__main__":
    print(json.dumps(check(sys.argv[1], int(sys.argv[2]), int(sys.argv[3]))))
