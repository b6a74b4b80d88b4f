#!/usr/bin/env python3
"""The knee sweep of a cell, once, on the chip:

    python3 bench/sweep.py <cell> <seconds> <seed> <rate> [key=value ...]

Runs the cell once with its traffic at ``rate`` streams/s (and any other
key of the traffic file, or ``conf.<key>`` of the configuration file, set
as given, the value read as JSON) for a window
of the given seconds, and prints the end-to-end numbers, the front door's
and the pool's counters and the streams still waiting at the close.  One
rate per process: a run that fails leaves its memory held.  The knee is
the highest rate at which ``cpr`` >= 0.9 and the queue does not grow over
the window; the cell's traffic file then fixes its rate at about 0.8 of
it.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench.run import cell_files, run_cell  # noqa: E402

if __name__ == "__main__":
    workload, seconds, seed = sys.argv[1], float(sys.argv[2]), int(sys.argv[3])
    rate = float(sys.argv[4])
    _, _, conf, traffic = cell_files(workload)
    traffic = dict(traffic, rate_per_s=rate)
    # the sweep reads the end-to-end numbers only: no reference replay
    conf = dict(conf, check=dict(conf["check"], reference_passes=0))
    for kv in sys.argv[5:]:
        k, v = kv.split("=", 1)
        if k.startswith("conf."):
            conf[k[5:]] = json.loads(v)
        else:
            traffic[k] = json.loads(v)
    r = run_cell(workload, seed, seconds, False, conf_override=conf,
                 traffic_override=traffic)
    info = r["info"]
    print(json.dumps({"rate": rate, "traffic": traffic, "e2e": info["e2e"],
                      "setup_s": info["setup_s"],
                      "front_door": info["front_door"],
                      "residency": info["residency"],
                      "gated_at_close": info["gated_at_close"],
                      "compiles_in_window": info["compiles_in_window"],
                      "window_fidelities": info["window_fidelities"],
                      "window_features": info["window_features"],
                      "attempted": r["result"]["attempted"],
                      "failed": r["result"]["failed"],
                      "memory_peak_bytes":
                          r["result"]["device"]["memory_peak_bytes"]}),
          flush=True)
