#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

  python3 perfbench/run.py --workload NAME --seed N --seconds S --trace 0|1

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  The run makes
its inputs and weights from ``--seed``, warms up every shape it uses (set-up,
reported as ``setup_s``), measures for ``--seconds``, checks what the timed
path produced against a plain reference, and prints one JSON line last:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics, read from a profiler
trace of part of the window), ``device`` and ``checks`` (each number
compared, beside its limit; also the last lines of standard error).

It refuses to run, printing no result, without a TPU or with fewer chips
than the cell asks for.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the profiler's files under .bench_trace/")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    from perfbench import core

    cell = core.load_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    cell.keep_trace = args.keep_trace
    import repro  # noqa: F401  (the system under test must be present)

    core.setup_compile_cache()
    core.require_chips(cell.chips)

    system = cell.config["system"]
    if system == "engine":
        from perfbench import engine_cell as runner
    elif system == "fleet":
        from perfbench import fleet_cell as runner
    else:
        raise core.BenchError(f"unknown system {system!r}")
    core.emit(runner.run(cell, T_START))


if __name__ == "__main__":
    main()
