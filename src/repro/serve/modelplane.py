"""Model plane: registry-backed bundles for heterogeneous co-serving.

A ``ModelBundle`` is everything the serving stack needs to run streams of
ONE model on a lane pool: the registry config, initialized params, the
uniform :mod:`repro.models.registry` API, the paged-pool geometry derived
from the config, the offline latency/quality profile, and the relative
placement costs (per-chunk step cost, per-page KV footprint) that let the
control plane weigh a cheap stream against a heavy one when choosing a
home (GENSERVE-style co-serving; see serve/README.md).

The serving stack is a *map over bundles*: ``LanePool`` commits one paged
``KVPool`` + params per bundle per lane, ``compose_batch`` keys sub-batches
by ``(model, kv_dtype)``, and re-homing / elastic SP stay same-model-only
because every source/target executor is resolved through the stream's
bundle.  A single-bundle session degenerates to exactly the pre-refactor
objects in the same construction order, so single-model runs are
bit-identical to the old path.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from repro.configs.base import ModelConfig, get_config
from repro.models import ardit as A
from repro.models import kvcache
from repro.models.registry import ModelAPI, get_api
from repro.profiler.profiles import MODEL_COST, ModelProfile, get_profile

# share of a device's memory kept back from the KV pools when they are
# sized (``fit_pool_streams``): compiled step programs, their
# activations and the per-chunk KV outputs live there.  At published
# widths on a 16 GB chip one fused step of two streams needs ~1.6 GiB
# beyond its arguments (v5e compile of ``denoise_step_paged``).
ACTIVATION_HEADROOM = 0.25
# co-resident streams per pool when the memory does not bound them (or
# the backend reports no memory limit, as the host CPU does)
MAX_POOL_STREAMS = 16

# Registry arch id -> profile surface name.  The analytic profile is keyed
# by the paper's model columns; registry ids not listed here use their own
# name (falling through to the default quality ceiling in ``Q_MAX`` and
# the per-model cost prior in ``MODEL_COST``).
PROFILE_NAME: Dict[str, str] = {
    "ardit-self-forcing": "self-forcing",
    "ardit-causal-forcing": "causal-forcing",
}


def profile_name_of(arch: str) -> str:
    return PROFILE_NAME.get(arch, arch)


@dataclasses.dataclass
class ModelBundle:
    """Config + params + profile + pool geometry for one served model."""
    name: str                 # registry arch id (e.g. "ardit-self-forcing")
    cfg: ModelConfig
    api: ModelAPI
    params: Any
    profile: ModelProfile
    # paged-pool geometry (mirrors KVPool's derivation; bundles own it so
    # placement can weigh footprints without instantiating a pool)
    page_tokens: int
    pages_per_stream: int
    kv_dtype: str
    # placement weights, relative to the session's primary bundle
    step_cost: float = 1.0    # per-chunk compute multiplier
    page_cost: float = 1.0    # per-page KV bytes multiplier
    # per-model warm-up calibration, filled in by StreamingSession
    top_latency: float = 0.0
    time_scale: float = 1.0

    @property
    def placement_weight(self) -> float:
        """Scalar load weight of one stream of this model.

        Service time dominates worker occupancy, residency pressure is
        secondary: ``step_cost * sqrt(page_cost)``.  The primary bundle
        weighs 1.0, so single-model placement reduces to the old
        integer queue-depth argmin."""
        return self.step_cost * float(np.sqrt(self.page_cost))

    @property
    def page_bytes(self) -> int:
        """KV bytes of one page of this bundle's pool, K and V."""
        itemsize = np.dtype(self.kv_dtype).itemsize
        return (2 * self.cfg.n_layers * self.page_tokens
                * self.cfg.n_kv_heads * self.cfg.head_dim * itemsize)

    @property
    def stream_bytes(self) -> int:
        """KV bytes of one fully-resident stream (sink + ring pages)."""
        return self.pages_per_stream * self.page_bytes


def _pool_geometry(cfg: ModelConfig):
    page_tokens = A.page_tokens(cfg)
    pps = kvcache.pages_per_stream(cfg.ardit_window_chunks)
    return page_tokens, pps


def resolve_bundle(model: Union[str, ModelConfig, ModelBundle], *,
                   seed: int = 0, reduced: bool = True,
                   step_cache: bool = False,
                   params: Any = None) -> ModelBundle:
    """Resolve one registry arch (or explicit config) into a bundle; a
    ``ModelBundle`` (e.g. with weights the caller made) passes through.

    Live serving drives the AR-DiT denoise path, so the config must be
    ``family == "ardit"``; other registry families are co-served
    analytically in the simulator (per-model cost priors) only."""
    if isinstance(model, ModelBundle):
        return model
    if isinstance(model, str):
        cfg = get_config(model)
        if reduced:
            cfg = cfg.reduced()
        arch = model
    else:
        cfg = model
        arch = cfg.name[:-len("-reduced")] \
            if cfg.name.endswith("-reduced") else cfg.name
    if cfg.family != "ardit":
        raise ValueError(
            f"live co-serving requires an ardit-family config, got "
            f"{arch!r} (family {cfg.family!r}); non-ardit models are "
            f"simulated via per-model cost priors instead")
    api = get_api(cfg)
    if params is None:
        import jax
        params = api.init(cfg, jax.random.PRNGKey(seed))
    page_tokens, pps = _pool_geometry(cfg)
    pname = profile_name_of(arch)
    return ModelBundle(
        name=arch, cfg=cfg, api=api, params=params,
        profile=get_profile(pname, step_cache=step_cache),
        page_tokens=page_tokens, pages_per_stream=pps,
        kv_dtype=cfg.kv_dtype,
        step_cost=MODEL_COST.get(pname, 1.0))


def fit_pool_streams(bundles: Sequence[ModelBundle],
                     lanes: int = 1) -> int:
    """Co-resident streams per lane (``KVPool`` ``max_streams``) that
    fit the device: the memory left after what is already in use (the
    bundles' weights) and ``ACTIVATION_HEADROOM``, over the KV bytes of
    one stream of every co-served bundle (each bundle's pool holds that
    many streams) times the lanes that share a device.  Capped at
    ``MAX_POOL_STREAMS``, which is also the answer on a backend that
    reports no memory limit (the host CPU).  Raises when not even one
    stream fits.
    """
    import jax
    devs = jax.devices()
    stats = devs[0].memory_stats()
    if not stats or "bytes_limit" not in stats:
        return MAX_POOL_STREAMS
    per_lane = sum(b.stream_bytes for b in bundles)
    sharing = -(-lanes // len(devs))          # lanes per device
    free = (stats["bytes_limit"] * (1.0 - ACTIVATION_HEADROOM)
            - stats.get("bytes_in_use", 0))
    fit = int(free // (per_lane * sharing))
    if fit < 1:
        raise ValueError(
            f"no stream fits {devs[0].device_kind}: {free / 2**30:.2f} GiB "
            f"free for KV, {per_lane * sharing / 2**30:.2f} GiB per stream")
    return min(MAX_POOL_STREAMS, fit)


def resolve_bundles(models: Sequence[Union[str, ModelConfig, ModelBundle]],
                    *,
                    seed: int = 0, reduced: bool = True,
                    step_cache: bool = False) -> List[ModelBundle]:
    """Resolve a co-served model set; weights are normalized so the FIRST
    bundle (the session primary) has step_cost == page_cost == 1.0."""
    if not models:
        raise ValueError("need at least one model")
    bundles = [resolve_bundle(m, seed=seed, reduced=reduced,
                              step_cache=step_cache) for m in models]
    names = [b.name for b in bundles]
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate models in co-serve set: {names}")
    ref = bundles[0]
    ref_step = ref.step_cost or 1.0
    ref_page = float(ref.page_bytes) or 1.0
    for b in bundles:
        b.step_cost = b.step_cost / ref_step
        b.page_cost = float(b.page_bytes) / ref_page
    return bundles
