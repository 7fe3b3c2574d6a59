"""From a profiler trace to numbers: device busy time as the union of the
operations' intervals, time by operation and by compiled module, and the
device's idle gaps, each named by the benchmark span the host was in.

The reduction works on plain events, ``(plane, line, name, start_ns,
dur_ns)``; ``events_from_xplane`` reads them from the ``.xplane.pb`` file
that ``jax.profiler`` writes.  Device planes are those named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per operation
and their ``XLA Modules`` line one per compiled program run.  Host spans
are the ``bench.*`` annotations on the host plane, on the same clock.
"""
from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


def short_op(name: str) -> str:
    """An operation's HLO name without the rest of its text:
    ``%fusion.130 = bf16[16,32,64] fusion(...)`` -> ``fusion.130``."""
    m = re.match(r"%?(\S+) = ", name)
    return m.group(1) if m else name


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:")


def events_from_xplane(path: Path) -> List[tuple]:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        device = is_device_plane(plane.name)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(SPAN_PREFIX):
                    continue
                out.append((plane.name, line.name, ev.name,
                            float(ev.start_ns), float(ev.duration_ns)))
    return out


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union_length(intervals: Iterable[Tuple[float, float]],
                 lo: float, hi: float) -> Tuple[float, List[tuple]]:
    """Length of the union of [start, end) intervals clipped to [lo, hi),
    and the gaps of that window the union leaves."""
    total, gaps = 0.0, []
    cur = lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cur:
            gaps.append((cur, s))
            total += e - s
            cur = e
        elif e > cur:
            total += e - cur
            cur = e
    if cur < hi:
        gaps.append((cur, hi))
    return total, gaps


def _innermost(spans: List[tuple], t: float) -> str:
    """The latest-starting host span that holds time ``t``."""
    best, best_start = "host (no benchmark span)", None
    for name, s, e in spans:
        if s <= t < e and (best_start is None or s > best_start):
            best, best_start = name, s
    return best


def reduce(events: List[tuple], window: Tuple[float, float]) -> dict:
    """Numbers of the traced window ``(start_ns, end_ns)``, averaged over
    the device planes that ran anything in it."""
    lo, hi = window
    planes = sorted({p for p, *_ in events if is_device_plane(p)})
    spans = [(n, s, s + d) for p, _, n, s, d in events
             if not is_device_plane(p)]
    busy, ops, modules, module_runs = [], defaultdict(float), \
        defaultdict(float), defaultdict(int)
    gaps_by_span = defaultdict(float)
    used = 0
    for plane in planes:
        ivs = []
        for p, line, name, s, d in events:
            if p != plane or s + d <= lo or s >= hi:
                continue
            clipped = min(s + d, hi) - max(s, lo)
            if line == OPS_LINE:
                ivs.append((s, s + d))
                ops[name] += clipped
            elif line == MODULES_LINE:
                modules[name] += clipped
                module_runs[name] += 1
        if not ivs:
            continue
        used += 1
        b, gaps = union_length(ivs, lo, hi)
        busy.append(b)
        for g0, g1 in gaps:
            gaps_by_span[_innermost(spans, (g0 + g1) / 2)] += g1 - g0
    n = max(used, 1)
    top = lambda d: sorted(  # noqa: E731
        ([short_op(k), v / n * 1e-9] for k, v in d.items()),
        key=lambda kv: -kv[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n * 1e-9,
        "devices": used,
        "op_s": {k: v / n * 1e-9 for k, v in ops.items()},
        "module_s": {k: v / n * 1e-9 for k, v in modules.items()},
        "module_runs": {k: v / n for k, v in module_runs.items()},
        "breakdown": {"device_ops": top(ops)[:10],
                      "idle_gaps": top(gaps_by_span)[:10]},
    }


def span_window(events: List[tuple], name: str) -> Tuple[float, float]:
    """The interval of the host span ``name`` (the traced window)."""
    for p, _, n, s, d in events:
        if n == name and not is_device_plane(p):
            return s, s + d
    raise ValueError(f"no host span {name!r} in the trace")


def time_matching(table: Dict[str, float], *needles: str) -> float:
    """Sum of the entries whose name holds any of ``needles``."""
    return sum(v for k, v in table.items() if any(x in k for x in needles))
