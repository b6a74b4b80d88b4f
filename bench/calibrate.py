#!/usr/bin/env python3
"""Readings for the correctness limit, many seeds in one process:

    python3 bench/calibrate.py sf-steady 20 1201 1202 ...

For each seed it makes a run of the cell with a window of the given
seconds, with the control in the program's place (``run_cell(...,
control=True)``): the sampled chunks are replayed through the float32
reference and through the reference with fp8 matmul operands.  Prints one
JSON line per seed with the worst reading of the program's chunks against
the reference (a sound run's), the control's, and the control run's
``correct``; ``PERF.md`` records them and the limit set from them.  The
benchmark's own runs never run the control.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench.run import run_cell  # noqa: E402

if __name__ == "__main__":
    workload, seconds = sys.argv[1], float(sys.argv[2])
    for seed in map(int, sys.argv[3:]):
        try:
            r = run_cell(workload, seed, seconds, False, control=True)
        except Exception as e:      # report the seed and go on
            print(json.dumps({"seed": seed, "error": str(e)[:300]}),
                  flush=True)
            continue
        info = r["info"]
        print(json.dumps({
            "seed": seed, "chunks": info["compared_chunks"],
            "streams": info["compared_streams"],
            "sound_max": max(info["chunk_rel_err"], default=None),
            "control_min": min(info["control_rel_err"], default=None),
            "control_max": max(info["control_rel_err"], default=None),
            "control_correct": r["result"]["correct"],
            "coverage": info["coverage"],
            "window_features": info["window_features"], "e2e": info["e2e"],
            "residency": info["residency"],
            "reference_s": info["reference_s"]}), flush=True)
