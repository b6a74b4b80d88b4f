"""Serving launcher.

Two modes, ONE workload spec and ONE metrics surface:

    --sim      cluster-scale discrete-event evaluation (the paper's SS7
               experiments): real control plane, modeled 16-worker
               cluster, any workload/policy.
    --real     real JAX AR-DiT execution on this host through the
               unified ``serve.session.StreamingSession``: the SAME
               ``ControlPlane.tick()`` decisions as --sim drive actual
               chunk generation (a reduced model, or the --models
               configs at their published widths with
               --published-widths: the chip path), over the same
               --workload/--rate/--seed StreamSpec generators, and the
               run prints the same one-line ``Summary.row()`` — so a
               workload can be compared sim-vs-real apples-to-apples.
               ``--lanes N`` serves through N device lanes (one batched
               executor + paged KV pool each) and re-enables re-homing
               and elastic SP: tick decisions become REAL cross-lane KV
               moves and Ulysses SP2 head splits; the run additionally
               reports decisions applied by the lane pool.

    PYTHONPATH=src python -m repro.launch.serve --sim \
        --workload steady --policy slackserve --streams 300
    PYTHONPATH=src python -m repro.launch.serve --real --streams 2
    PYTHONPATH=src python -m repro.launch.serve --real --batched \
        --workload burst --streams 6 --seed 0
    PYTHONPATH=src python -m repro.launch.serve --real --batched \
        --streams 4 --pool-streams 2        # oversubscribed page pool
    PYTHONPATH=src python -m repro.launch.serve --real --lanes 2 \
        --workload burst                    # multi-lane: migrations + SP
    PYTHONPATH=src python -m repro.launch.serve --real \
        --models ardit-self-forcing,ardit-causal-forcing \
        --streams 4                # heterogeneous co-serving, one pool
    PYTHONPATH=src python -m repro.launch.serve --real \
        --models ardit-self-forcing --published-widths \
        --streams 2 --chunks 3     # published widths, on an accelerator
"""
from __future__ import annotations

import argparse
import os
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--sim", action="store_true")
    mode.add_argument("--real", action="store_true")
    ap.add_argument("--workload", default="steady")
    ap.add_argument("--policy", default="slackserve")
    ap.add_argument("--streams", type=int, default=None,
                    help="stream count (default: 300 for --sim, 6 for "
                         "--real — the live tiny model is the demo)")
    ap.add_argument("--lanes", type=int, default=1,
                    help="device lanes for --real (> 1 implies the "
                         "batched executor and re-enables re-homing + "
                         "elastic SP); with > 1 visible devices each "
                         "lane commits its pool to its own device and "
                         "cross-lane moves are real jax.device_put")
    ap.add_argument("--device-count", type=int, default=0,
                    help="force N host platform devices before JAX "
                         "initializes (XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=N) so device-backed "
                         "lanes are testable on one CPU host")
    ap.add_argument("--workers-per-node", type=int, default=0,
                    help="lanes per node for --real --lanes "
                         "(0 -> all lanes in one node)")
    ap.add_argument("--budget-factor", type=float, default=0.0,
                    help="playout seconds per chunk as a multiple of "
                         "the measured top-fidelity latency (0 -> 4.0 "
                         "single-lane, 2.0 multi-lane: the tighter "
                         "budget keeps tail streams urgent so the "
                         "cross-lane mechanisms engage)")
    ap.add_argument("--rate", type=float, default=1.0)
    ap.add_argument("--model", default="causal-forcing")
    ap.add_argument("--models", default="",
                    help="comma-separated registry configs to CO-SERVE "
                         "on one lane pool (--real; implies --batched). "
                         "Streams are tagged round-robin; the first "
                         "model is the primary bundle and the report "
                         "adds per-model Summary rows")
    ap.add_argument("--published-widths", action="store_true",
                    help="serve the --models registry configs at their "
                         "published widths instead of cfg.reduced() "
                         "(--real; needs an accelerator's memory): the "
                         "pool is sized to the device and the playout "
                         "cadence is the real 0.75 s per chunk")
    ap.add_argument("--chunks", type=int, default=4,
                    help="per-stream chunk cap for --real (the tiny "
                         "model; --sim uses the spec lengths as-is)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batched", action="store_true",
                    help="credit-ordered micro-batch executor (--real)")
    ap.add_argument("--max-batch", type=int, default=0,
                    help="micro-batch cap per lane (0 -> 4, or 3 "
                         "multi-lane: a smaller batch keeps real "
                         "WAITING streams in loaded queues — the "
                         "congestion signal Algorithm 1 reads)")
    ap.add_argument("--arrival-scale", type=float, default=1.0,
                    help="multiply workload event times for --real "
                         "(< 1 compresses Poisson gaps / trace idles)")
    ap.add_argument("--pool-streams", type=int, default=0,
                    help="co-resident stream cap of the paged KV pool "
                         "(< --streams oversubscribes; 0 -> as many as "
                         "the device memory holds, at most 16)")
    ap.add_argument("--context-backend", choices=("gather", "paged"),
                    default="paged",
                    help="how sub-batches see cached KV: 'paged' serves "
                         "attention straight from the page pool through "
                         "block tables; 'gather' materializes the "
                         "contiguous context (reference path)")
    ap.add_argument("--front-door", action="store_true",
                    help="SLO-aware admission control in front of the "
                         "scheduler: predicted-TTFC admit/queue/reject "
                         "(+ autoscaling under --sim) and admission "
                         "stats in the report")
    ap.add_argument("--step-cache", action="store_true",
                    help="unlock the content-adaptive step cache as a "
                         "fifth fidelity axis: BMPR routes over the "
                         "270-point (cache-unlocked) frontier and "
                         "eligible denoise steps reuse cached residuals "
                         "(models/stepcache.py)")
    ap.add_argument("--calibrate", action="store_true",
                    help="after a --real run, fit the sim cost model to "
                         "the session's measured EMAs, replay the same "
                         "specs through the calibrated simulator, and "
                         "print the sim-vs-real QoE/TTFC agreement")
    args = ap.parse_args()

    if args.lanes > 1:
        args.batched = True          # lanes ride the batched executor
    if args.models:
        if not args.real:
            ap.error("--models only applies to --real (co-serving rides "
                     "the live batched executor)")
        args.batched = True          # co-serving rides the batched path
    if args.published_widths and not args.models:
        ap.error("--published-widths needs --models (the registry configs "
                 "to serve at their published widths)")
    if args.pool_streams and not (args.real and args.batched):
        ap.error("--pool-streams only applies to --real --batched")
    if any(a.startswith("--context-backend") for a in sys.argv[1:]) \
            and not (args.real and args.batched):
        ap.error("--context-backend only applies to --real --batched")
    if args.lanes > 1 and not args.real:
        ap.error("--lanes only applies to --real")
    if args.step_cache and not (args.real and args.batched):
        ap.error("--step-cache only applies to --real --batched (cache "
                 "hits ride the fused batched dispatch as no-op rows)")
    if args.calibrate and not args.real:
        ap.error("--calibrate only applies to --real (the sim IS the "
                 "model being calibrated)")
    if args.device_count:
        if not args.real:
            ap.error("--device-count only applies to --real")
        # must land in the environment BEFORE jax initializes its
        # backends (repro imports below pull jax in)
        flag = ("--xla_force_host_platform_device_count="
                f"{args.device_count}")
        os.environ["XLA_FLAGS"] = \
            (os.environ.get("XLA_FLAGS", "") + " " + flag).strip()

    from repro.sched_sim.metrics import summarize, transfer_stats
    from repro.sched_sim.workloads import WORKLOADS

    if args.real:
        from repro.launch import compile_cache
        from repro.serve.session import (SessionConfig, StreamingSession,
                                         cap_specs)
        compile_cache.enable()

        # multi-lane demo defaults: enough streams that each lane's
        # queue exceeds the micro-batch (genuinely WAITING streams are
        # what Algorithm 1 calls congestion), odd so the lanes drain
        # unevenly and a relaxed receiver appears
        n_streams = (args.streams if args.streams is not None
                     else 15 if args.lanes > 1 else 6)
        # multi-lane default budget: tight enough that a lane still
        # holding work keeps URGENT streams even at solo speed (~2x the
        # measured top latency vs the single-lane demo's 4x), so when
        # the other lane drains first the sender/receiver pair of
        # Algorithm 1 actually materializes
        budget_factor = (args.budget_factor
                         or (2.0 if args.lanes > 1 else 4.0))
        raw = WORKLOADS[args.workload](n=n_streams, rate=args.rate,
                                       seed=args.seed)
        # multi-lane keeps the workload's length DIVERSITY (scaled into
        # the chunk budget) — lanes then drain unevenly, which is what
        # re-homing and elastic SP exist to absorb
        from repro.serve.session import scale_specs
        specs = (scale_specs(raw, args.chunks) if args.lanes > 1
                 else cap_specs(raw, args.chunks))
        model_list = [m.strip() for m in args.models.split(",")
                      if m.strip()]
        if model_list:
            import dataclasses as _dc
            specs = [_dc.replace(sp, model=model_list[i % len(model_list)])
                     for i, sp in enumerate(specs)]
        fd_cfg = None
        if args.front_door:
            from repro.sched_sim.frontdoor import FrontDoorConfig
            fd_cfg = FrontDoorConfig()        # autoscale forced off live
        session = StreamingSession(SessionConfig(
            executor="batched" if args.batched else "sequential",
            models=model_list or None,
            published_widths=args.published_widths,
            max_batch=args.max_batch
            or (3 if args.lanes > 1 else 4),
            lanes=args.lanes,
            workers_per_node=args.workers_per_node,
            budget_factor=budget_factor,
            pool_streams=args.pool_streams or None,
            context_backend=args.context_backend,
            arrival_scale=args.arrival_scale,
            front_door=fd_cfg,
            step_cache=args.step_cache,
            verbose=True))   # --seed varies the workload, not the model
        for spec in specs:
            session.submit(spec)
        res = session.run()
        s = summarize(res)
        label = (f"real-{args.lanes}-lane" if args.lanes > 1 else
                 "real-batched" if args.batched else "real-sequential")
        if model_list:
            label += f"-coserve[{','.join(model_list)}]"
        print(f"{label} on {args.workload}: {s.row()}")
        for line in s.model_rows():
            print(line)
        print(f"  rehomings={s.n_rehomings} elastic_sp={s.n_sp_events} "
              f"transfers={transfer_stats(res)}")
        if args.front_door:
            print(f"  admission: {res.admission}")
        if args.step_cache:
            print(f"  step_cache: {res.step_cache} "
                  f"avg_effective_window={s.avg_effective_window:.2f}")
        if args.calibrate:
            from repro.sched_sim.calibration import agreement, fit_session
            from repro.sched_sim.policies import make_policy
            from repro.sched_sim.simulator import Simulator
            report = fit_session(session)
            sim_cfg = report.sim_config(
                n_workers=args.lanes,
                workers_per_node=args.workers_per_node or args.lanes)
            sim_res = Simulator(sim_cfg, specs, make_policy(
                "slackserve", model=report.model,
                profile=report.profile())).run()
            agr = agreement(s, summarize(sim_res))
            print(f"  calibration: scale={report.scale:.3f} "
                  f"ratios={ {k: round(v, 3) for k, v in report.ratios.items()} }")
            print(f"  sim-vs-real: qoe {agr['qoe_sim']} vs "
                  f"{agr['qoe_real']} (|d|={agr['qoe_delta']}, "
                  f"tol {agr['qoe_tol']}), ttfc {agr['ttfc_sim_s']}s vs "
                  f"{agr['ttfc_real_s']}s (rel={agr['ttfc_rel_err']}, "
                  f"tol {agr['ttfc_rel_tol']}) -> "
                  f"{'OK' if agr['ok'] else 'DISAGREE'}")
        if args.lanes > 1:
            print(f"  applied: migrations={res.n_migrations_applied} "
                  f"sp_expands={res.n_sp_expands_applied} "
                  f"sp_releases={res.n_sp_releases_applied}")
            import jax
            lanes = session.lanes
            placement = [str(d) if d is not None else "default"
                         for d in getattr(lanes, "lane_devices", [])]
            print(f"  devices: {jax.local_device_count()} visible, "
                  f"lanes -> {placement}")
            ms = res.engine.measured_stats()
            if ms["count"]:
                print(f"  measured moves: n={ms['count']} "
                      f"bytes={ms['bytes']} "
                      f"bw={ms['bytes_per_s']:.3g} B/s "
                      f"(model {ms['bw_intra_model']:.3g} -> "
                      f"calibrated {ms['bw_intra_calibrated']:.3g})")
        return

    from repro.sched_sim.policies import SDV2Policy, make_policy
    from repro.sched_sim.simulator import SimConfig, Simulator

    specs = WORKLOADS[args.workload](
        n=args.streams if args.streams is not None else 300,
        rate=args.rate, seed=args.seed)
    policy = make_policy(args.policy, model=args.model)
    sim_cfg = (SDV2Policy.sim_config() if args.policy == "sdv2"
               else SimConfig(model=args.model))
    if args.front_door:
        import dataclasses as _dc

        from repro.sched_sim.frontdoor import FrontDoorConfig
        sim_cfg = _dc.replace(sim_cfg, front_door=FrontDoorConfig())
    res = Simulator(sim_cfg, specs, policy).run()
    s = summarize(res)
    print(f"{args.policy} on {args.workload}: {s.row()}")
    for line in s.model_rows():          # mixed_models workload
        print(line)
    print(f"  rehomings={s.n_rehomings} elastic_sp={s.n_sp_events} "
          f"transfers={transfer_stats(res)}")
    if args.front_door:
        print(f"  admission: {res.admission} "
              f"(final workers: {res.n_workers_final})")


if __name__ == "__main__":
    main()
