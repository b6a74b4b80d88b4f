#!/usr/bin/env python3
"""Smoke check: the streaming-video server runs on a TPU at published widths.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # only the four-chip lane phase

One chip.  ``ardit-self-forcing`` at its published widths (30 layers,
d_model 1536, 12 heads of 128, 2,640-token chunks, a 7-chunk window;
random weights from ``--seed``) is served through ``StreamingSession``
on the batched paged executor at the real 0.75 s playout cadence: 2
streams x 3 chunks in a KV pool sized to the chip, the fidelity
alternating between the top one and a sparsified, windowed one.  Checks:

  * the lowered ``denoise_step_paged`` calls the Pallas paged kernel
    (``tpu_custom_call``): neither the jnp reference nor interpret mode;
  * every generated chunk is finite;
  * the kernel's online-softmax partials agree with the float32 oracle
    ``paged_chunk_attention_ref`` on full-width inputs (``KERNEL_TOL``);
  * stream 0's first chunks, as the timed paged path served them, agree
    with a replay on the ``gather`` backend with one stream resident
    (``CHUNK_TOL``).

It prints the session's Summary row, a host-clock chunk latency fenced
with ``block_until_ready`` (a smoke reading, not a benchmark), peak
device memory and compile seconds; the last line is one JSON object.

``--four-chips`` runs only a 4-lane ``LanePool`` (one lane per chip) at
published widths: a forced migration lane 0 -> 3 and a batch-axis SP
expand, append and release (lane 1 -> donor lane 2), each compared with
a one-lane run of the same stream in this process, and the measured
``jax.device_put`` bandwidth.

Exits non-zero, printing no result line, when JAX finds no TPU or any
check fails.  One process drives every chip; it starts no other.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ARCH = "ardit-self-forcing"
# kernel partials vs the float32 oracle: max |kernel - oracle| over
# max |oracle|, for each of m, l and acc (bf16 K/V on the MXU, fp32
# accumulation)
KERNEL_TOL = 1e-2
# served chunks vs the gather-backend replay, and lane runs vs one-lane
# runs: ||a - b|| / ||b|| over a whole chunk of latents (bf16 weights
# and KV; the two attention paths round and accumulate differently)
CHUNK_TOL = 2e-2


def require_tpu():
    """The JAX module, once a TPU backend is up; exit otherwise."""
    import jax
    try:
        backend = jax.default_backend()
    except RuntimeError as e:
        sys.exit(f"no TPU found: {e}")
    if backend != "tpu":
        sys.exit(f"no TPU found: JAX runs on {backend!r}")
    return jax


class CompileClock:
    """Sums JAX's backend-compile durations (persistent-cache reads
    included) from the moment it is installed."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


def rel_err(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def run_chunks(ex, sid, fids):
    """Generate one chunk per fidelity for ``sid`` on executor ``ex``."""
    import numpy as np
    out = []
    for fid in fids:
        ex.begin_chunk(sid, fid, 0.0)
        while sid in ex.inflight:
            ex.run_step([sid])
        out.append(np.asarray(ex.chunks[sid][-1]))
    return out


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def kernel_vs_oracle(jax, cfg, seed: int) -> dict:
    """The Pallas kernel against the float32 oracle on full-width inputs:
    a sink page and three ring pages of random KV, all-visible and
    sparsely masked."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.paged_attention.kernel import \
        paged_chunk_attention_pallas
    from repro.kernels.paged_attention.ref import paged_chunk_attention_ref
    from repro.models import ardit as A
    tc, page = A.chunk_tokens(cfg), A.page_tokens(cfg)
    sink, n = A.COND_TOKENS, 4
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    dt = jnp.dtype(cfg.kv_dtype)
    q = jax.random.normal(ks[0], (1, tc, cfg.n_heads, cfg.head_dim), dt)
    pool = (1, n + 2, cfg.n_kv_heads, page, cfg.head_dim)
    kp = jax.random.normal(ks[1], pool, dt)
    vp = jax.random.normal(ks[2], pool, dt)
    table = jnp.asarray([[5, 0, 3, 1]], jnp.int32)
    dense = np.zeros((1, n, page), bool)
    dense[:, 0, :sink] = True
    dense[:, 1:, :tc] = True
    sparse = dense & np.asarray(jax.random.uniform(ks[3], dense.shape) < 0.5)
    lowered = jax.jit(lambda *a: paged_chunk_attention_pallas(
        *a, sink=sink, chunk_tokens=tc)).lower(q, kp, vp, table, None, 0)
    check("tpu_custom_call" in lowered.as_text(),
          "paged_chunk_attention_pallas lowered without the Pallas kernel")
    errs = {}
    for name, mask in (("all_visible", None),
                       ("masked", jnp.asarray(sparse.reshape(1, -1)))):
        got = paged_chunk_attention_pallas(q, kp, vp, table, mask, 0,
                                           sink=sink, chunk_tokens=tc)
        with jax.default_matmul_precision("highest"):
            want = paged_chunk_attention_ref(q, kp, vp, table, mask, 0,
                                             sink=sink, chunk_tokens=tc)
        for part, g, w in zip(("m", "l", "acc"), got, want):
            g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
            check(np.isfinite(g).all(), f"kernel {part} not finite")
            err = float(np.abs(g - w).max() / np.abs(w).max())
            errs[f"{name}.{part}"] = err
            check(err <= KERNEL_TOL,
                  f"kernel {name} {part}: error {err:.3g} > {KERNEL_TOL}")
    return errs


class CyclingFidelity:
    """Fidelity policy that alternates between the given configs, one per
    chunk decision — a fixed mix of the top fidelity and a degraded one,
    whatever the budget."""

    def __init__(self, fids, profile):
        from repro.core.bmpr import BMPRDecision
        self._dec = [BMPRDecision(f, profile.latency(f), profile.quality(f),
                                  "static") for f in fids]
        self.by_key = {f.key: f for f in fids}
        self._i = 0

    def select(self, budget):
        dec = self._dec[self._i % len(self._dec)]
        self._i += 1
        return dec


def one_chip(jax, seed: int) -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.base import get_config
    from repro.core.fidelity import HIGHEST_QUALITY, FidelityConfig
    from repro.models import ardit as A
    from repro.sched_sim.metrics import summarize
    from repro.serve.batcher import BatchedChunkExecutor
    from repro.serve.modelplane import resolve_bundle
    from repro.serve.session import (SessionConfig, StreamingSession,
                                     uniform_specs)
    clock = CompileClock(jax)
    dev = jax.devices()[0]

    cfg = get_config(ARCH)
    bundle = resolve_bundle(ARCH, reduced=False, params=A.init_params(
        cfg, jax.random.PRNGKey(seed), open_gates=True))
    print(f"model: {ARCH} at published widths: {cfg.n_layers} layers, "
          f"d_model {cfg.d_model}, {cfg.n_heads} heads x {cfg.head_dim}, "
          f"{A.chunk_tokens(cfg)}-token chunks, window "
          f"{cfg.ardit_window_chunks}, KV pages of {bundle.page_tokens} "
          f"tokens, {bundle.stream_bytes / 2**30:.2f} GiB KV per stream",
          flush=True)

    errs = kernel_vs_oracle(jax, cfg, seed)
    print("kernel vs float32 oracle (max|d|/max|ref|): "
          + " ".join(f"{k}={v:.3g}" for k, v in errs.items())
          + f" (tol {KERNEL_TOL})", flush=True)

    mixed = FidelityConfig(3, 0.6, 3, "bf16")
    policy = CyclingFidelity([HIGHEST_QUALITY, mixed], bundle.profile)
    t0 = time.perf_counter()
    session = StreamingSession(
        SessionConfig(models=[bundle], published_widths=True, max_batch=2,
                      verbose=True),
        fidelity_policy=policy)
    ex = session.executor
    print(f"session: pool of {ex.max_streams} streams, playout "
          f"{session.chunk_seconds} s/chunk, warm-up "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    n_streams, n_chunks = 2, 3
    handles = [session.submit(s)
               for s in uniform_specs(n_streams, n_chunks)]
    res = session.run()
    summ = summarize(res)
    print(f"summary: {summ.row()}")
    for line in summ.model_rows():
        print(line)
    served = {h.sid: ([np.asarray(c) for c in h.chunks],
                      list(h.fidelity_log)) for h in handles}
    for sid, (chunks, log) in served.items():
        check(len(chunks) == n_chunks, f"stream {sid}: {len(chunks)} chunks")
        for i, c in enumerate(chunks):
            check(np.isfinite(c).all(), f"stream {sid} chunk {i} not finite")
        print(f"stream {sid}: fidelities {log}")
    check(any(k == HIGHEST_QUALITY.key for _, log in served.values()
              for k in log) and any(k == mixed.key for _, log
                                    in served.values() for k in log),
          "the session did not serve both fidelities")
    print(f"peak_bytes_in_use after the session: "
          f"{(dev.memory_stats() or {}).get('peak_bytes_in_use', 0)}")

    # the jitted step of the timed path calls the Pallas kernel
    z = jnp.zeros
    lowered = A.denoise_step_paged.lower(
        cfg, ex.params, z((1, A.chunk_tokens(cfg), A.LATENT_CH)),
        z((1,)), z((1,)), ex.pool.k, ex.pool.v, z((1, 2), jnp.int32),
        None, None, z((1,), jnp.int32), z((1,), bool))
    check("tpu_custom_call" in lowered.as_text(),
          "denoise_step_paged lowered without the Pallas paged kernel")
    print("denoise_step_paged: Pallas paged kernel present "
          "(tpu_custom_call)")

    # smoke reading: top-fidelity chunks of a fresh stream, host clock
    # fenced with block_until_ready, on the session's executor once a
    # first stream has compiled every shape the second one runs
    for sid in (100, 101):
        check(ex.admit(sid, seed=sid), "smoke stream did not fit the pool")
        lat = []
        for _ in range(n_chunks):
            before = clock.count
            t = time.perf_counter()
            ex.begin_chunk(sid, HIGHEST_QUALITY, 0.0)
            while sid in ex.inflight:
                ex.run_step([sid])
            ex.chunks[sid][-1].block_until_ready()
            lat.append((time.perf_counter() - t, clock.count - before))
            check(np.isfinite(np.asarray(ex.chunks[sid][-1])).all(),
                  "smoke chunk not finite")
        ex.retire(sid)
    print("top-fidelity chunk latency (smoke reading, host clock, not a "
          "benchmark): " + ", ".join(
              f"chunk {i}: {s:.3f} s ({c} compiles)"
              for i, (s, c) in enumerate(lat)))

    # paged (as served) vs gather replay of stream 0, one stream resident
    params = ex.params
    del session, ex, handles, res
    gc.collect()
    chunks0, log0 = served[0]
    n_cmp = 2
    gather = BatchedChunkExecutor(cfg=cfg, params=params, max_streams=1,
                                  context_backend="gather")
    check(gather.admit(0, seed=0), "replay stream did not fit")
    replay = run_chunks(gather, 0, [policy.by_key[k] for k in log0[:n_cmp]])
    cmp = [rel_err(chunks0[i], replay[i]) for i in range(n_cmp)]
    print("paged (served) vs gather replay, stream 0 (||d||/||ref||): "
          + ", ".join(f"chunk {i}: {e:.3g}" for i, e in enumerate(cmp))
          + f" (tol {CHUNK_TOL})")
    for i, e in enumerate(cmp):
        check(e <= CHUNK_TOL, f"stream 0 chunk {i}: paged vs gather {e:.3g}")
    del gather
    gc.collect()

    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", 0)
    print(f"peak_bytes_in_use: {peak} ({peak / 2**30:.2f} GiB of "
          f"{stats.get('bytes_limit', 0) / 2**30:.2f} GiB)")
    print(f"compile: {clock.seconds:.1f} s over {clock.count} programs")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def four_chips(jax, seed: int) -> None:
    """4 lanes, one per chip: migration 0 -> 3 and batch-axis SP
    1 -> 2, each against a one-lane run of the same stream."""
    from repro.configs.base import get_config
    from repro.core.fidelity import FidelityConfig
    from repro.models import ardit as A
    from repro.serve.lanes import LanePool
    check(len(jax.devices()) == 4, f"need 4 chips, have {len(jax.devices())}")
    cfg = get_config(ARCH)
    fid = FidelityConfig(2, 0.0, 2, "bf16")
    # a one-stream pool per chip: beside a 2-stream pool (11 GB with the
    # weights) a migration's or SP expand's page gathers leave too
    # little of a 16 GB chip
    lanes = LanePool(4, cfg=cfg, max_streams=1, params=A.init_params(
        cfg, jax.random.PRNGKey(seed), open_gates=True))
    check(len({str(d) for d in lanes.lane_devices}) == 4,
          "lanes are not on four chips")
    print("bytes_in_use per chip with the 4-lane pool: " + ", ".join(
        str((d.memory_stats() or {}).get("bytes_in_use", 0))
        for d in lanes.lane_devices), flush=True)

    # one-lane references: each stream served on its home lane alone,
    # then retired (a re-admitted sid draws the same noise)
    lanes.admit(5, 0, seed=5)
    ref5 = run_chunks(lanes.ex(0), 5, [fid] * 3)
    lanes.retire(5)
    lanes.admit(0, 1, seed=0)
    ref0 = run_chunks(lanes.ex(1), 0, [fid] * 4)
    lanes.retire(0)

    # forced migration lane 0 -> lane 3, mid-stream
    lanes.admit(5, 0, seed=5)
    got5 = run_chunks(lanes.ex(0), 5, [fid])
    check(lanes.migrate(5, 0, 3), "migration was not applied")
    mig = lanes.engine.measured[-1]
    check(mig.kind == "migration", "migration was not a measured move")
    got5 += run_chunks(lanes.ex(3), 5, [fid] * 2)
    # batch-axis SP: stream 0 homed on lane 1 borrows donor lane 2 and
    # runs there as a row of the donor's micro-batch, its KV appended on
    # the donor and shipped home
    lanes.admit(0, 1, seed=0)
    got0 = run_chunks(lanes.ex(1), 0, [fid])
    check(lanes.sp_expand(0, 2), "SP expand was not applied")
    link = lanes.sp_link(0)
    check(link is not None and link.mode == "batch",
          "cross-chip SP did not take the batch axis")
    got0 += run_chunks(lanes.ex(2), 0, [fid] * 2)
    lanes.sp_release(0)
    check(lanes.sp_link(0) is None, "SP link survived release")
    got0 += run_chunks(lanes.ex(1), 0, [fid])    # home again
    for lane in range(4):
        lanes.ex(lane).pool.ledger.check()

    errs = {"migration": [rel_err(a, b) for a, b in zip(got5, ref5)],
            "sp": [rel_err(a, b) for a, b in zip(got0, ref0)]}
    exact = {k: all(e == 0.0 for e in v) for k, v in errs.items()}
    for k, v in errs.items():
        print(f"{k} vs one-lane run (||d||/||ref|| per chunk): "
              + ", ".join(f"{e:.3g}" for e in v)
              + f" ({'bit-exact' if exact[k] else 'not bit-exact'}, "
              f"tol {CHUNK_TOL})")
        check(max(v) <= CHUNK_TOL, f"{k}: error {max(v):.3g} > {CHUNK_TOL}")
    ms = lanes.engine.measured_stats()
    print(f"device_put moves: n={ms['count']} bytes={ms['bytes']} "
          f"measured bandwidth {ms['bytes_per_s'] / 1e9:.2f} GB/s "
          f"(migration {mig.n_bytes} B in {mig.seconds:.4f} s)")
    print("peak_bytes_in_use per chip: " + ", ".join(
        str((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in lanes.lane_devices))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip lane phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    jax = require_tpu()
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.launch import compile_cache
    print(f"compile cache: {compile_cache.enable()}", flush=True)
    if args.four_chips:
        four_chips(jax, args.seed)
    else:
        one_chip(jax, args.seed)
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
