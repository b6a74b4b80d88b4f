#!/usr/bin/env python3
"""One run of one benchmark cell on the chip it is started on.

    python3 bench/run.py --workload sf-steady --seed 7 --seconds 51 --trace 0

Everything it needs is named in ``BENCHMARK.json`` and found by name under
``bench/``: the cell's configuration (``configs/<config>.json``), its
traffic (``traffic/<traffic>.json``), and one reader per per-layer metric
(``metrics/<metric>.py``).  The run

1. makes the weights from ``--seed`` on the device, builds the server
   (``StreamingSession`` over the batched paged executor, BMPR over the
   90-point fidelity space, the default front door, 0.75 s playout),
   compiles every program the window can launch, and starts the traffic's
   open-loop arrivals one ramp before the window (held, where the traffic
   sets ``live_cap``, until fewer than that many streams are live): all
   of that is set-up;
2. serves for ``--seconds`` seconds, stamping every chunk on its own clock;
3. scores the window with its own player model (``player.py``), reads
   the per-layer metrics (``--trace 1``: from a profiler trace of the
   window too), and frees the server;
4. replays a sample of the chunks made in the window, drawn from the
   seed, through the float32 reference (``reference/``) and compares
   each of them.

Its last line on standard output is one JSON object; the compared numbers
and their limits close standard error.  It exits non-zero, printing no
result, when JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional, Set, Tuple  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import generators, player, quality, trace  # noqa: E402
from bench.harness import CompileClock, require_tpu  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_trace")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def load_json(*parts: str) -> Dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def cell_files(workload: str):
    """(benchmark, cell, configuration, traffic) for a workload name."""
    bench = load_json("BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    conf = load_json(entry["file"])
    traffic = load_json("bench", "traffic", cell["traffic"] + ".json")
    return bench, cell, conf, traffic


@dataclasses.dataclass
class Context:
    """What the per-layer readers read."""
    dims: Any
    peak: Dict[str, float]
    window_s: float
    tick_s: List[float]
    compiles: int
    dispatches: int
    chunks_done: int
    kv_bytes: int
    delivered: List            # (fidelity key, chunk index) ready in window
    launch_rows: List[int]     # visible context tokens, per row per launch
    e2e: Dict[str, float]      # the player's scores of the window
    trace: Optional[trace.Trace] = None
    trace_window: Optional[tuple] = None


def chunk_features(key: str, index: int, launches, spilled: bool,
                   reference) -> Set[str]:
    """What one served chunk exercises, for the comparison's coverage:
    the paged ring of >= 2 chunks, rho, fp8 KV, a launch of >= 2 rows
    (with mixed fidelities), a stream that was spilled and restored."""
    f = reference.parse_fidelity(key)
    out = {"in_chunk"}
    if index >= 2 and f.window >= 2:
        out.add("ring_ge_2")
    if f.sparsity > 0:
        out.add("rho")
    if f.quant == "fp8":
        out.add("fp8")
    if any(b >= 2 for b, _ in launches):
        out.add("batch2")
    if any(b >= 2 and mixed for b, mixed in launches):
        out.add("batch2_mixed")
    if spilled:
        out.add("spilled_stream")
    return out


def sample_chunks(candidates: Dict[Tuple[int, int], Set[str]],
                  keys: Dict[int, List[str]], seed: int, budget: int,
                  reference) -> Dict[int, List[int]]:
    """{sid: chunk indices to compare}, drawn from the seed among the
    chunks made in the window (``candidates``, with what each exercises):
    each pick is the chunk that adds the most features not yet covered,
    then the one that costs the fewest reference passes, ties in a seeded
    order; picks go on while the pass budget lasts."""
    order = sorted(candidates)
    random.Random(seed).shuffle(order)
    picked: Dict[int, Set[int]] = {}
    covered: Set[str] = set()
    left = budget
    while True:
        best, best_score = None, None
        for c in order:
            sid, i = c
            have = picked.get(sid, set())
            if i in have:
                continue
            cost = (reference.passes_of(keys[sid], have | {i})
                    - reference.passes_of(keys[sid], have))
            if cost > left:
                continue
            score = (len(candidates[c] - covered), -cost)
            if best_score is None or score > best_score:
                best, best_score = c, score
        if best is None:
            break
        sid, i = best
        left -= -best_score[1]
        picked.setdefault(sid, set()).add(i)
        covered |= candidates[best]
    return {sid: sorted(v) for sid, v in sorted(picked.items())}


def stream_logs(specs, ready, records, chunks, outcomes, model):
    """The player's view of every submitted stream, and the served
    (chunks, fidelity keys) of those that got a chunk.  Built from the
    benchmark's ready stamps, the scheduled arrivals and each chunk's
    fidelity key only: the server's deadlines and slack are not read."""
    import numpy as np
    logs, served = [], {}
    for spec in specs:
        rec = records.get(spec.sid)
        keys = list(rec.fidelity_log) if rec is not None else []
        got = chunks.get(spec.sid, [])
        logs.append(player.StreamLog(
            spec.sid, spec.arrival, spec.chunks,
            ready=list(ready.get(spec.sid, [])),
            quality=[quality.of_key(k, model) for k in keys],
            refused=outcomes.get(spec.sid) == "rejected",
            finite=all(bool(np.isfinite(c).all()) for c in got)))
        if got:
            served[spec.sid] = (got, keys)
    return logs, served


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             require_chip: bool = True, conf_override: Optional[Dict] = None,
             traffic_override: Optional[Dict] = None,
             control: bool = False) -> Dict[str, Any]:
    """One run of ``workload``; returns the result line's fields plus the
    compared readings.  ``require_chip=False`` and ``conf_override`` let
    the CPU tests drive the whole run at reduced widths;
    ``traffic_override`` serves the knee sweep (``sweep.py``).  With
    ``control`` the control is put in the program's place: the sample is
    replayed through the reference with fp8 matmul operands as well, and
    ``correct`` and ``compared`` judge the control's chunks against the
    float32 reference by the same number and limit (``calibrate.py``)."""
    bench, cell, conf, traffic = cell_files(workload)
    conf = conf_override or conf
    traffic = traffic_override or traffic
    if require_chip:
        jax = require_tpu(cell["chips"])
    else:
        import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import numpy as np
    from repro.configs.base import get_config
    from repro.models import ardit as A
    from repro.sched_sim.frontdoor import FrontDoorConfig
    from repro.sched_sim.workloads import StreamSpec
    from repro.serve.modelplane import resolve_bundle
    from repro.serve.session import SessionConfig
    from bench.serve import BenchSession, warm_up
    reference = importlib.import_module(f"bench.reference.{conf['reference']}")

    clock = CompileClock(jax)
    dev = jax.devices()[0]
    peaks = load_json("bench", "peaks.json")["devices"]
    if require_chip and dev.device_kind not in peaks:
        raise SystemExit(f"no peaks for device kind {dev.device_kind!r}")
    peak = peaks.get(dev.device_kind, next(iter(peaks.values())))
    if (A.COND_TOKENS, A.LATENT_CH) != (conf["cond_tokens"],
                                        conf["latent_channels"]):
        raise SystemExit("the program's condition/latent sizes differ from "
                         "the configuration file")
    dims = reference.dims_of(conf)
    params = reference.make_params(dims, seed, conf["model"]["param_dtype"])
    jax.block_until_ready(params)
    mcfg = dataclasses.replace(get_config(conf["arch"]), name=conf["arch"],
                               **conf["model"])
    bundle = resolve_bundle(mcfg, params=params)
    cadence = traffic["playout_s_per_chunk"]
    ramp = traffic["ramp_s"]
    arrivals = generators.generate(traffic, seed, ramp + seconds)
    specs = [StreamSpec(i, a, f) for i, (a, f) in enumerate(arrivals)]

    marks: Dict[str, Any] = {}
    session: Any = None

    def counters():
        exs = session.lanes.all_executors
        return (sum(e.dispatch_count for e in exs),
                sum(e.pool.transfer_bytes for e in exs), clock.count,
                sum(e.evictions for e in exs), sum(e.restores for e in exs),
                sum(e.deferrals for e in exs))

    def on_open():
        marks["open_wall"] = time.perf_counter()
        marks["open"] = counters()
        if traced:
            with jax.profiler.TraceAnnotation("bench/window_open"):
                pass

    session = BenchSession(
        SessionConfig(models=[bundle], published_widths=True,
                      pool_streams=conf["pool_streams"],
                      realtime_budget=cadence, front_door=FrontDoorConfig(),
                      verbose=False),
        open_at=ramp, close_at=ramp + seconds, annotate=traced,
        on_open=on_open)
    cap = traffic.get("live_cap")
    session.live_cap = session.executor.max_streams if cap == "pool" else cap
    warm_launches = warm_up(session, session.cfg.max_batch,
                            spill=session.live_cap is None
                            or session.live_cap > session.executor.max_streams)
    compiles_setup = clock.count
    for spec in specs:
        session.submit(spec)
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    session.run()
    if session.clock() < session.close_at:
        if not session.in_window:
            time.sleep(max(0.0, session.open_at - session.clock()))
            on_open()
        time.sleep(max(0.0, session.close_at - session.clock()))
    closed = counters()
    window_s = time.perf_counter() - marks["open_wall"]
    tr = tr_window = None
    if traced:
        with jax.profiler.TraceAnnotation("bench/window_close"):
            pass
        jax.block_until_ready((session.executor.pool.k,))
        jax.profiler.stop_trace()
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))

    # ---- the window, scored by the benchmark's own player ----------------
    t_open, t_close = session.open_at, session.close_at
    model = conf["quality_model"]
    res = session.result()
    fd = session.front_door
    logs, served = stream_logs(
        specs, session.ready, res.streams,
        {s.sid: [np.asarray(c) for c in session.lanes.chunks_of(s.sid)]
         for s in specs},
        fd.outcomes if fd is not None else {}, model)
    e2e = player.end_to_end(logs, cadence, quality.top(model), t_open,
                            t_close)
    attempted, failed = player.outcome(logs, t_open, t_close,
                                       traffic["first_chunk_grace_s"])
    setup_s = marks["open_wall"] - T_START
    delivered = [(k, i) for s in logs for i, (r, k) in enumerate(
        zip(s.ready, served.get(s.sid, ([], []))[1]))
        if t_open <= r <= t_close]
    launch_rows = []
    for rows in session.launches:
        for n, key, phase in rows:
            f = reference.parse_fidelity(key)
            launch_rows.append(len(reference.visible_window_tokens(
                dims, n, f.window, f.sparsity if phase == "denoise" else 0.0))
                + dims.cond_tokens)
    if traced:
        tr = trace.load(TRACE_DIR)
        tr_window = tr.window()
    ctx = Context(dims=dims, peak=peak, window_s=window_s,
                  tick_s=session.tick_s,
                  compiles=closed[2] - marks["open"][2],
                  dispatches=closed[0] - marks["open"][0],
                  chunks_done=len(delivered),
                  kv_bytes=closed[1] - marks["open"][1],
                  delivered=delivered, launch_rows=launch_rows, e2e=e2e,
                  trace=tr, trace_window=tr_window)
    candidates = {
        (sid, i): chunk_features(keys[i], i,
                                 session.chunk_batches.get((sid, i), ()),
                                 sid in session.spilled, reference)
        for s in logs if s.sid in served
        for sid, keys in [(s.sid, served[s.sid][1])]
        for i, r in enumerate(s.ready[:len(keys)]) if t_open <= r < t_close}
    lag = sorted(session.arrival_lag)
    fd_stats = fd.stats() if fd is not None else {}
    gated_at_close = len(session.gated)
    pool_streams = session.executor.max_streams
    residency = {k: closed[3 + i] - marks["open"][3 + i] for i, k in
                 enumerate(("evictions", "restores", "deferrals"))}
    del session, res, bundle
    gc.collect()

    # ---- metrics -----------------------------------------------------------
    metrics: Dict[str, Dict[str, float]] = {}
    if traced:
        for m in bench["per_layer"]:
            if workload not in m.get("workloads", [workload]):
                continue
            value = importlib.import_module(
                f"bench.metrics.{m['name']}").read(ctx)
            if value is not None and math.isfinite(value):
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(e2e, setup_s=setup_s)
        for m in bench["end_to_end"]:
            if workload in m.get("workloads", [workload]):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}

    # ---- correctness: the float32 reference over a sample ----------------
    t_ref = time.perf_counter()
    check = conf["check"]
    picks = sample_chunks(candidates,
                          {sid: served[sid][1] for sid in served}, seed,
                          check["reference_passes"], reference)
    errs, ctrl, ring, cover = [], [], None, set()
    for sid, idx in picks.items():
        chunks, keys = served[sid]
        want, ring = reference.replay_stream(dims, params, sid, chunks, keys,
                                             idx, ring=ring)
        if control:
            low, ring = reference.replay_stream(dims, params, sid, chunks,
                                                keys, idx, matmul="fp8",
                                                ring=ring)
        for i in idx:
            noise = reference.chunk_noise(dims, sid, i)
            errs.append(reference.rel_err(
                np.reshape(chunks[i], want[i].shape), want[i], noise))
            if control:
                ctrl.append(reference.rel_err(low[i], want[i], noise))
            cover |= candidates[(sid, i)]
    ref_s = time.perf_counter() - t_ref
    limit = check["chunk_rel_err_limit"]
    judged = ctrl if control else errs
    worst = max(judged) if judged else float("inf")
    correct = bool(judged) and worst <= limit and all(
        math.isfinite(e) for e in judged)

    d = jax.devices()
    device = {"platform": d[0].platform, "kind": d[0].device_kind,
              "count": len(d), "memory_peak_bytes": memory_peak}
    if traced:
        lo, hi = tr_window
        device["busy_s"] = trace.busy_s(tr, lo, hi)
        device["window_s"] = hi - lo
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if traced:
        lo, hi = tr_window
        out["breakdown"] = {"device_ops": trace.top_ops(tr, lo, hi),
                            "idle_gaps": trace.idle_gaps(tr, lo, hi)}
    info = {"e2e": e2e, "setup_s": setup_s, "window_s": window_s,
            "warm_up_launches": warm_launches,
            "compiles_setup": compiles_setup,
            "compile_s": clock.seconds,
            "arrival_lag_s": {"max": lag[-1] if lag else 0.0,
                              "median": statistics.median(lag) if lag
                              else 0.0, "n": len(lag)},
            "front_door": fd_stats, "residency": residency,
            "gated_at_close": gated_at_close, "pool_streams": pool_streams,
            "compiles_in_window": ctx.compiles,
            "window_fidelities": dict(collections.Counter(
                k for k, _ in delivered)),
            "reference_s": ref_s, "compared_chunks": len(errs),
            "compared_streams": len(picks),
            "compared_index": {str(k): v for k, v in picks.items()},
            "coverage": sorted(cover),
            "window_features": sorted(set().union(*candidates.values())),
            "chunk_rel_err": errs, "control_rel_err": ctrl}
    out["compared"] = {"chunk_rel_err": {"value": worst, "limit": limit}}
    return {"result": out, "info": info}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    r = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    info, out = r["info"], r["result"]
    print("run: " + json.dumps({k: v for k, v in info.items()
                                if k not in ("chunk_rel_err",
                                             "control_rel_err")}),
          flush=True)
    cmp = out["compared"]["chunk_rel_err"]
    print(f"compared {info['compared_chunks']} chunks of "
          f"{info['compared_streams']} streams against the float32 "
          f"reference", file=sys.stderr)
    print(f"chunk_rel_err {cmp['value']!r} limit {cmp['limit']!r}",
          file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
