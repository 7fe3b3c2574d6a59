#!/usr/bin/env python3
"""Find the knee of a serving cell: the highest offered rate the engine
sustains without a growing backlog.

  python3 perfbench/sweep.py --workload slice-48t-steady --seed N \\
      --seconds 30 --rates 2.0,2.5,3.0,3.5,4.0

One process: the weights are made once, and each rate runs the cell's
open-loop traffic scaled to that aggregate rate for ``--seconds``.  Per
rate it prints the offered and delivered tokens per second, the 90th
percentile time to first token, and the requests still outstanding at
half time and at the close.  It is run by hand on the chip, once, to set a
cell's rate; the benchmark's runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import numpy as np

    from perfbench import core, engine_cell

    core.setup_compile_cache()
    params = None
    for rate in [float(x) for x in args.rates.split(",")]:
        cell = core.load_cell(args.workload, args.seed, args.seconds, False)
        cell.traffic = dict(cell.traffic, rate_per_s=rate)
        r = engine_cell.Run(cell, time.perf_counter(), params=params)
        params = r.params
        w = r.window()
        half = cell.seconds / 2
        offered = sum(a.max_new for a in r.traffic.arrivals)
        done_half = sum(1 for t in w["step_t"] if t < half)
        out = {
            "rate_per_s": rate, "requests": w["attempted"],
            "offered_tokens_per_s": offered / cell.seconds,
            "tokens_per_s": w["tokens"] / w["window_s"],
            "ttft_p90_ms": float(np.percentile(w["ttft"], 90)) * 1e3,
            "ttft_p50_ms": float(np.percentile(w["ttft"], 50)) * 1e3,
            "tbt_p99_ms": float(np.percentile(w["gaps"], 99)) * 1e3,
            "step_ms_median": float(np.median(w["step_wall"])) * 1e3,
            "steps": len(w["step_wall"]), "steps_first_half": done_half,
            "queued_at_close": sum(len(t.queue)
                                   for t in r.eng.tenants.values()),
            "failed": w["failed"],
        }
        print(json.dumps(out), flush=True)
        r.eng._cache = None


if __name__ == "__main__":
    main()
