#!/usr/bin/env python3
"""Readings that set a cell's correctness limits: the program's numbers and
the control's, over many seeds, in one process.

  python3 perfbench/control.py --workload NAME --seconds S --seeds 1,2,3

For each seed it runs the cell's window at the cell's own load and prints
one JSON line with every number the run compares (the lower readings) and
the control's reading of the same number (the upper readings):

* serving cells: ``logit_gap``'s control is the model reference's own
  (its ``block_gaps`` with ``control``; for Qwen3, ``reference/decoder.py``
  computes every matrix product in float8 e4m3, the precision next below
  the configuration's bfloat16), read at the same positions of the same
  tokens: the gap of the token the control puts first.  ``tick_state_err``'s
  control is the reference tick in the precision next below the engine's
  tick (float32 below the host loop's float64; bfloat16 below the Pallas
  kernel's float32).  ``admission_mismatches`` is exact;
* fleet cells: each gap's control is the reference node with its state
  rounded to bfloat16 every tick (the precision next below the scan's
  float32).

It is run by hand on the chip when a limit is set; the benchmark's runs
never run it.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from perfbench import core

    core.setup_compile_cache()
    for seed in [int(x) for x in args.seeds.split(",")]:
        cell = core.load_cell(args.workload, seed, args.seconds, False)
        print(json.dumps(readings(cell)), flush=True)


def readings(cell) -> dict:
    """The program's and the control's readings of one seed."""
    t = time.perf_counter()
    if cell.config["system"] == "engine":
        from perfbench import engine_cell

        r = engine_cell.Run(cell, t)
        w = r.window()
        c = r.checks(w, control=True)
        out = {k: v["value"] for k, v in c.items()
               if k not in ("control", "_counts")}
        out["control"] = c["control"]
        out.update(c["_counts"])
    else:
        import ml_dtypes

        from perfbench import fleet_cell

        r = fleet_cell.Run(cell, t)
        w = r.window()
        out = r.checks(w)
        low = r.checks(w, state_dtype=ml_dtypes.bfloat16)
        out["control"] = {k: v for k, v in low.items()
                          if k != "calls_differing"}
    out.update(seed=cell.seed, workload=cell.workload,
               wall_s=time.perf_counter() - t)
    return out


if __name__ == "__main__":
    main()
