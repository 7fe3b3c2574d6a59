#!/usr/bin/env python3
"""Find the knee of a serving cell: the highest offered rate the engine
sustains without a growing backlog, on the median of several draws of the
arrivals.

  python3 perfbench/sweep.py --workload slice-48t-steady --seed N \\
      --seconds 51 --rates 9,10.5,12,13,14,15 --draws 3

One process: the cell is loaded as a run loads it (its configuration file
and model reference), the weights are made once, and each rate runs the
cell's open-loop traffic scaled to that aggregate rate for ``--seconds``,
once for each of ``--draws`` schedules (the traffic file's
``schedule_seed`` and the ones after it; the first is the cell's own).
Per rate and draw it prints the requests due in the window, the offered
and delivered tokens per second, the time to first token, the backlog
(requests due and not finished) averaged over one burst cycle (the
traffic's ``burst_on_s`` + ``burst_off_s``) before half time and before
the close, and whether it grew: by more than one batch of ``n_slots``
requests from half time to the close.  The last line names the knee: the
highest rate below the first at which most draws grew (the median draw):
one draw's test swings with its bursts.  It is run by hand on the chip,
once, to set a cell's rate; the benchmark's runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def mean_backlog(w: dict, end_s: float, span_s: float) -> float:
    """Mean over the batch steps in ``[end_s - span_s, end_s)`` of the
    requests due and not finished, from the window's due and finish
    stamps."""
    import numpy as np

    t = np.asarray([s for s in w["step_t"] if end_s - span_s <= s < end_s])
    if not len(t):
        return 0.0
    due = np.sort(np.fromiter(w["due"].values(), float))
    done = np.sort(np.fromiter(w["finished"].values(), float))
    n = (np.searchsorted(due, t, side="right")
         - np.searchsorted(done, t, side="right"))
    return float(n.mean())


def knee(lines) -> float | None:
    """The highest rate below the first at which more than half of the
    draws' backlogs grew; None if they grew at the lowest."""
    out = None
    for rate in sorted({x["rate_per_s"] for x in lines}):
        grew = [x["backlog_grew"] for x in lines if x["rate_per_s"] == rate]
        if 2 * sum(grew) > len(grew):
            break
        out = rate
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--draws", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import numpy as np

    from perfbench import core, engine_cell

    core.setup_compile_cache()
    params, lines = None, []
    for rate in [float(x) for x in args.rates.split(",")]:
        for d in range(args.draws):
            cell = core.load_cell(args.workload, args.seed, args.seconds,
                                  False)
            tr = cell.traffic
            cell.traffic = dict(tr, rate_per_s=rate,
                                schedule_seed=tr["schedule_seed"] + d)
            span = tr["burst_on_s"] + tr["burst_off_s"]
            r = engine_cell.Run(cell, time.perf_counter(), params=params)
            params = r.params
            w = r.window()
            half = mean_backlog(w, w["window_s"] / 2, span)
            close = mean_backlog(w, w["window_s"], span)
            offered = sum(a.max_new for a in r.traffic.arrivals)
            out = {
                "rate_per_s": rate,
                "schedule_seed": cell.traffic["schedule_seed"],
                "requests_due": w["attempted"],
                "offered_tokens_per_s": offered / cell.seconds,
                "tokens_per_s": w["tokens"] / w["window_s"],
                "ttft_p50_ms": float(np.percentile(w["ttft"], 50)) * 1e3,
                "ttft_p90_ms": float(np.percentile(w["ttft"], 90)) * 1e3,
                "tbt_p99_ms": float(np.percentile(w["gaps"], 99)) * 1e3,
                "step_ms_median": float(np.median(w["step_wall"])) * 1e3,
                "steps": len(w["step_wall"]),
                "backlog_half": half, "backlog_close": close,
                "backlog_grew": close > half + r.ec["n_slots"],
                "failed": w["failed"],
            }
            lines.append(out)
            print(json.dumps(out), flush=True)
            r.driver.free()
    print(json.dumps({"knee_per_s": knee(lines), "draws": args.draws}),
          flush=True)


if __name__ == "__main__":
    main()
