"""A kept profiler trace of a traced fleet run, cut by host span: the
program's ``fleet.*`` spans (``repro.obs``) beside the benchmark's
``bench.*`` ones, all on the profiler's clock.

  python3 perfbench/run.py --workload fig7-fleet-scan --seed N \\
      --seconds 51 --trace 1 --keep-trace
  python3 -m perfbench.spanreduce .bench_trace/fig7-fleet-scan

prints one JSON object: the traced window's device busy and idle time, the
share of that idle time that falls under a ``fleet.*`` span, the per-call
numbers of the fleet's stages (``per_call``) and the table by span name
(``spans``).  ``tracereduce`` keeps only ``bench.*`` host spans, so its
``reduce`` and the metrics that read it are as they were; no registered
metric reads this module yet.
"""
from __future__ import annotations

import argparse
import json
from bisect import bisect_right
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from perfbench import tracereduce as tr

PROGRAM_PREFIX = "fleet."
PREFIXES = (tr.SPAN_PREFIX, PROGRAM_PREFIX)


def program_spans(path: Path) -> Tuple[List[tuple],
                                       Dict[Tuple[str, float], dict]]:
    """The program's ``fleet.*`` host spans in the ``.xplane.pb`` file, as
    ``tracereduce`` events, and their args by the span's
    ``(name, start_ns)``."""
    from jax.profiler import ProfileData

    events, args = [], {}
    for plane in ProfileData.from_file(str(path)).planes:
        if tr.is_device_plane(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM_PREFIX):
                    start = float(ev.start_ns)
                    events.append((plane.name, line.name, ev.name, start,
                                   float(ev.duration_ns)))
                    args[(ev.name, start)] = dict(ev.stats)
    return events, args


def _covered(gaps: List[tuple]):
    """``x -> length of gaps before x``, for sorted disjoint gaps."""
    starts = [g0 for g0, _ in gaps]
    before, acc = [], 0.0
    for g0, g1 in gaps:
        before.append(acc)
        acc += g1 - g0

    def f(x: float) -> float:
        i = bisect_right(starts, x) - 1
        if i < 0:
            return 0.0
        g0, g1 = gaps[i]
        return before[i] + min(x, g1) - g0

    return f


def spans(events: List[tuple], window: Tuple[float, float],
          args: Optional[Dict[Tuple[str, float], dict]] = None) -> dict:
    """The traced window ``(start_ns, end_ns)`` by host span name.

    For each ``bench.*`` and ``fleet.*`` span name: ``count``; ``total_s``,
    its time clipped to the window; ``self_s``, the part of it no span that
    starts inside it holds; ``busy_s``, device busy time inside it (the
    union of the operations); ``idle_s``, the device idle time given to it:
    each idle gap is cut at span boundaries and each piece goes to the
    innermost ``fleet.*`` span that holds it, else to the innermost
    ``bench.*`` one; and ``args``, the sums of its numeric args (``args``
    maps a span's ``(name, start_ns)`` to them, as ``program_spans`` reads
    them).  Device times are averaged over the device planes that ran
    anything in the window, as in ``tracereduce.reduce``.
    """
    lo, hi = window
    args = args or {}
    host = [(n, max(s, lo), min(s + d, hi), args.get((n, s), {}))
            for p, _, n, s, d in events
            if not tr.is_device_plane(p) and n.startswith(PREFIXES)
            and s < hi and s + d > lo]
    idle_in = []  # per device plane that ran anything: idle length before x
    for plane in sorted({p for p, *_ in events if tr.is_device_plane(p)}):
        ivs = [(s, s + d) for p, line, _, s, d in events
               if p == plane and line == tr.OPS_LINE and s < hi and s + d > lo]
        if ivs:
            idle_in.append(_covered(tr.union_length(ivs, lo, hi)[1]))
    n_dev = max(len(idle_in), 1)

    out = {}
    for n, s, e, a in host:
        sp = out.setdefault(n, dict(count=0, total_s=0.0, self_s=0.0,
                                    busy_s=0.0, idle_s=0.0, args={}))
        sp["count"] += 1
        sp["total_s"] += (e - s) * 1e-9
        sp["busy_s"] += sum((e - s) - (f(e) - f(s))
                            for f in idle_in) / n_dev * 1e-9
        for k, v in a.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                sp["args"][k] = sp["args"].get(k, 0) + v
    cuts = sorted({lo, hi, *(s for _, s, _, _ in host),
                   *(e for _, _, e, _ in host)})
    for a, b in zip(cuts, cuts[1:]):
        holders = [(s, -e, n) for n, s, e, _ in host if s <= a and b <= e]
        if not holders:
            continue
        out[max(holders)[2]]["self_s"] += (b - a) * 1e-9
        fleet = [h for h in holders if h[2].startswith(PROGRAM_PREFIX)]
        owner = max(fleet or holders)[2]
        out[owner]["idle_s"] += sum(f(b) - f(a)
                                    for f in idle_in) / n_dev * 1e-9
    return out


def per_call(sp: dict) -> Optional[dict]:
    """The fleet's stages per ``fleet.call`` of the window: host time
    building the node traces (``prep_s``: ``fleet.workload`` +
    ``fleet.slot_trace`` + ``fleet.stage``), dispatching the scan
    (``dispatch_ms``) and reading its outputs back (``readback_s``); and
    device busy time inside ``fleet.scan`` per tick (``tick_us``, None with
    no device plane).  None where the program opened no ``fleet.call``."""
    calls = sp.get("fleet.call", {}).get("count")
    if not calls:
        return None

    def total(*names):
        return sum(sp[n]["total_s"] for n in names if n in sp) / calls

    scan = sp.get("fleet.scan", {})
    ticks = scan.get("args", {}).get("ticks")
    busy = scan.get("busy_s", 0.0)
    return {
        "prep_s": total("fleet.workload", "fleet.slot_trace", "fleet.stage"),
        "dispatch_ms": total("fleet.dispatch") * 1e3,
        "readback_s": total("fleet.readback"),
        "tick_us": busy / ticks * 1e6 if ticks and busy else None,
    }


def reduce_kept(trace_dir: Path) -> dict:
    """The traced window of the trace kept under ``trace_dir``."""
    path = tr.find_xplane(trace_dir)
    prog, args = program_spans(path)
    events = tr.events_from_xplane(path) + prog
    window = tr.span_window(events, "bench.traced")
    red = tr.reduce(events, window)
    sp = spans(events, window, args)
    idle = red["window_s"] - red["busy_s"]
    fleet_idle = sum(s["idle_s"] for n, s in sp.items()
                     if n.startswith(PROGRAM_PREFIX))
    return {"window_s": red["window_s"], "busy_s": red["busy_s"],
            "idle_s": idle,
            "fleet_idle_share": (fleet_idle / idle
                                 if red["devices"] and idle > 0 else None),
            "per_call": per_call(sp), "spans": sp}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir", type=Path,
                    help="a trace kept by run.py --trace 1 --keep-trace")
    print(json.dumps(reduce_kept(ap.parse_args(argv).trace_dir), indent=1))


if __name__ == "__main__":
    main()
