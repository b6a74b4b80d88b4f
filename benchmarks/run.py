"""Benchmark orchestrator: one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full]

--full uses the 946-prompt workloads and all models/workloads (slower);
the default quick mode reproduces every trend in a few minutes.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

SECTIONS = [
    ("App. A  - Pareto frontier (90 fidelity configs)", "figA_pareto"),
    ("Fig. 10 - KV quality propagation (real tiny AR-DiT)",
     "fig10_kv_propagation"),
    ("Fig. 11 - end-to-end: models x workloads x systems",
     "fig11_end_to_end"),
    ("Fig. 12 - technique ablation", "fig12_ablation"),
    ("Fig. 13 - State-Plane transfer protocols", "fig13_transfer"),
    ("Fig. 14 - stall distribution", "fig14_stalls"),
    ("Fig. 15 - worker-type imbalance", "fig15_imbalance"),
    ("Fig. 16 - BMPR vs fixed-level switching", "fig16_bmpr_vs_fixed"),
    ("Fig. 17 - re-homing / elastic-SP triggers", "fig17_triggers"),
    ("Fig. 18 - selected fidelity configurations", "fig18_fidelity_dist"),
    ("Table 3 - sensitivity (alpha, arrival rate)", "table3_sensitivity"),
    ("Table 4 - Control-Plane scalability (real wall time)",
     "table4_controller"),
    ("Table 5 - State-Plane overheads", "table5_state_plane"),
    ("Kernels - correctness + arithmetic intensity", "kernel_bench"),
    ("Roofline - dry-run terms per (arch x shape x mesh)", "roofline"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="run a single section by module name")
    args = ap.parse_args()
    if args.full:
        os.environ["REPRO_FULL_SCALE"] = "1"
    quick = not args.full

    import importlib
    t0 = time.time()
    failed = []
    for title, mod_name in SECTIONS:
        if args.only and args.only != mod_name:
            continue
        print(f"\n{'='*78}\n{title}\n{'='*78}")
        t1 = time.time()
        try:
            importlib.import_module(f"benchmarks.{mod_name}").main(
                quick=quick)
        except Exception as e:  # report every section, then fail the run
            print(f"SECTION FAILED: {type(e).__name__}: {e}")
            failed.append(mod_name)
        print(f"[{mod_name}: {time.time()-t1:.1f}s]")
    print(f"\ntotal: {time.time()-t0:.1f}s")
    if failed:
        sys.exit(f"{len(failed)} section(s) failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
