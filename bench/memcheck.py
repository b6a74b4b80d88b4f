#!/usr/bin/env python3
"""Does the paged denoise step fit one v5e chip?  Compiled for a described
(not attached) v5e, no chip needed:

    JAX_PLATFORMS=cpu python3 bench/memcheck.py ardit-causal-forcing 1 1
    JAX_PLATFORMS=cpu python3 bench/memcheck.py ardit-causal-forcing 2 2

Arguments: configuration name (a file under ``bench/configs/``, in a cell
or not), batch rows, pool streams.  Compiles the step with a full window
(sink + W ring pages per row, explicit masks) over a pool of that many
streams and prints the compiler's memory analysis, or the compiler's
refusal when the program does not fit.  The weights' shapes come from the
configuration's own reference module (``dims_of`` and ``make_params``
under ``jax.eval_shape``); the step and its other arguments from the
program (``step_specs``).
This is why ``ardit-causal-forcing`` has its pool pinned at one stream:
at head_dim 96 XLA copies the pool into the kernel's tiled layout on every
launch, and a 2-stream pool at batch 2 does not fit.  And why
``ardit-self-forcing`` has its pinned at two: at the three streams the
program would size, a batch-3 step leaves no room for a spill's gather.

    JAX_PLATFORMS=cpu python3 bench/memcheck.py ardit-self-forcing 3 3
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import os
import sys
from typing import Dict, Union

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def step_specs(A, cfg, batch: int, streams: int):
    """(step, its arguments after ``cfg`` and the weights) at a full window
    over a pool of ``streams`` streams, as unplaced ``ShapeDtypeStruct``s
    (``None`` for an argument left out).  The program's own
    ``full_window_step_specs(cfg, batch, streams)`` where it has one, so a
    block with more per-launch inputs states them itself; else these, of
    ``denoise_step_paged``."""
    import jax
    import jax.numpy as jnp
    own = getattr(A, "full_window_step_specs", None)
    if own is not None:
        return own(cfg, batch, streams)
    S = jax.ShapeDtypeStruct
    tc, page = A.chunk_tokens(cfg), A.page_tokens(cfg)
    n = 1 + cfg.ardit_window_chunks
    pool = S((cfg.n_layers, streams * n, cfg.n_kv_heads, page, cfg.head_dim),
             jnp.dtype(cfg.kv_dtype))
    f32 = S((batch,), jnp.float32)
    return A.denoise_step_paged, (
        S((batch, tc, A.LATENT_CH), jnp.float32), f32, f32, pool, pool,
        S((batch, n), jnp.int32), S((batch, n * page), jnp.bool_), None,
        S((batch,), jnp.int32), S((batch,), jnp.bool_))


def check(conf: Union[str, Dict], batch: int, streams: int) -> dict:
    """Memory analysis of the step for ``conf`` (a configuration's name or
    its parsed file) on one described v5e, or the compiler's refusal."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    import repro.kernels.paged_attention.ops as ops
    from repro.configs.base import get_config
    from repro.models import ardit as A

    if isinstance(conf, str):
        with open(os.path.join(ROOT, "bench", "configs",
                               f"{conf}.json")) as f:
            conf = json.load(f)
    reference = importlib.import_module(f"bench.reference.{conf['reference']}")
    cfg = dataclasses.replace(get_config(conf["arch"]), **conf["model"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    sh = SingleDeviceSharding(topo.devices[0])
    d = reference.dims_of(conf)
    params = jax.eval_shape(lambda: reference.make_params(
        d, 0, conf["model"]["param_dtype"]))
    step, args = step_specs(A, cfg, batch, streams)
    params, args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        (params, args))
    out = {"config": conf["name"], "batch": batch, "pool_streams": streams}
    cache_on, kernel_mode = jax.config.jax_enable_compilation_cache, \
        ops.kernel_mode
    # a described chip's compile cannot be read back from the cache; the
    # CPU would pick the oracle over the kernel; and a step traced for one
    # kernel mode is reused for the other unless the traces are dropped
    jax.config.update("jax_enable_compilation_cache", False)
    ops.kernel_mode = lambda: "pallas"
    jax.clear_caches()
    try:
        compiled = step.lower(cfg, params, *args).compile()
    except Exception as e:                 # the compiler's refusal
        out.update(fits=False, error=str(e).splitlines()[0][:300])
        return out
    finally:
        ops.kernel_mode = kernel_mode
        jax.clear_caches()
        jax.config.update("jax_enable_compilation_cache", cache_on)
    m = compiled.memory_analysis()
    out.update(fits=True,
               argument_gib=m.argument_size_in_bytes / 2 ** 30,
               temp_gib=m.temp_size_in_bytes / 2 ** 30,
               output_gib=m.output_size_in_bytes / 2 ** 30)
    return out


if __name__ == "__main__":
    print(json.dumps(check(sys.argv[1], int(sys.argv[2]), int(sys.argv[3]))))
