"""What every cell shares: ``BENCHMARK.json`` and a cell's files, the chip
check, the compile cache, the peaks table, the metric readers and the
result line.

A cell is found by name.  Its configuration file is the one
``BENCHMARK.json`` names, its traffic file is ``traffic/<traffic>.json``,
its correctness limits are ``limits/<workload>.json``, and each metric is
read by ``metrics/<metric name>.py``.  Adding a cell or a metric adds files
and entries; no file here changes.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
# fixed and inside the checkout (the path is part of the cache's key), and
# the benchmark's own: no entry another tool left there can break it
CACHE_DIR = ROOT / ".bench_jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


class BenchError(Exception):
    """The benchmark cannot run this cell as asked."""


@dataclass
class Cell:
    workload: str
    config: dict
    traffic: dict
    limits: dict
    chips: int
    seed: int
    seconds: float
    trace: bool
    end_to_end: List[dict]
    per_layer: List[dict]
    # smaller sizes for the CPU rehearsal tests; empty in every real run
    overrides: dict = field(default_factory=dict)
    keep_trace: bool = False  # leave the profiler's files for reading by hand


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_metrics(entries: List[dict], workload: str) -> List[dict]:
    """The metrics of ``entries`` that this cell reports."""
    return [m for m in entries
            if "workloads" not in m or workload in m["workloads"]]


def load_cell(workload: str, seed: int, seconds: float, trace: bool,
              root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    return make_cell(by_name[workload], bench, seed, seconds, trace, root)


def make_cell(w: dict, bench: dict, seed: int, seconds: float, trace: bool,
              root: Path = ROOT) -> Cell:
    """The cell of workload entry ``w`` (an entry of ``BENCHMARK.json``'s
    ``workloads``, or one prepared for it)."""
    workload = w["name"]
    configs = {c["name"]: c for c in bench["configs"]}
    return Cell(
        workload=workload,
        config=load_json(root / configs[w["config"]]["file"]),
        traffic=load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(BENCH_DIR / "limits" / f"{workload}.json"),
        chips=int(w["chips"]),
        seed=int(seed),
        seconds=float(seconds),
        trace=bool(trace),
        end_to_end=cell_metrics(bench["end_to_end"], workload),
        per_layer=cell_metrics(bench["per_layer"], workload),
    )


# -- the machine -------------------------------------------------------------


def setup_compile_cache() -> str:
    """JAX's persistent compilation cache, in the checkout, for every
    program however short its compile, so that only a cell's first run in
    a checkout compiles.  Any cache directory set in the environment is
    replaced: two checkouts never share one."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return str(CACHE_DIR)


def require_chips(n: int) -> None:
    """Exit, printing no result, unless JAX sees at least ``n`` TPU chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"perfbench: needs a TPU, JAX found platform "
                         f"{devices[0].platform!r}")
    if len(devices) < n:
        raise SystemExit(f"perfbench: the cell needs {n} chips, JAX found "
                         f"{len(devices)}")


def device_info() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, where the backend says."""
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a chip not in the table is an error."""
    table = load_json(BENCH_DIR / "peaks.json")
    if device_kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"peaks.json")
    return table["devices"][device_kind]


def seed_words(seed: int, n: int = 2) -> List[int]:
    """``n`` 31-bit words from a seed of any size (seeds may be larger than
    32 bits)."""
    import numpy as np

    words = np.random.SeedSequence(int(seed)).generate_state(n, np.uint32)
    return [int(w) >> 1 for w in words]


class WindowWatch:
    """What else ran in the process during the measured window: programs
    traced or compiled (none should be), and garbage-collection pauses.
    For the run's log line on standard error; no metric reads it."""

    TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.traces = self.compiles = self.collections = 0
        self.compile_s = self.gc_s = self.gc_max_s = 0.0
        self._gc_t0 = 0.0

    def _on_event(self, event: str, secs: float, **_):
        if event == self.TRACE_EVENT:
            self.traces += 1
        elif event == self.COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += secs

    def _on_gc(self, phase: str, info: dict):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
            return
        d = time.perf_counter() - self._gc_t0
        self.collections += 1
        self.gc_s += d
        self.gc_max_s = max(self.gc_max_s, d)

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc):
        import jax

        gc.callbacks.remove(self._on_gc)
        jax.monitoring.unregister_event_duration_listener(self._on_event)
        return False

    def line(self) -> str:
        return (f"in window: traced {self.traces} compiled {self.compiles} "
                f"({self.compile_s:.3f}s) gc {self.collections} "
                f"({self.gc_s:.4f}s, longest {self.gc_max_s:.4f}s)")


# -- metrics -----------------------------------------------------------------


def reader(name: str) -> Callable[[dict], Optional[float]]:
    """``metrics/<name>.py``'s ``read``: record -> value, or None where the
    run gave it nothing to read."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    if not path.exists():
        raise BenchError(f"no reader {path.name} for metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(record: dict, entries: List[dict]) -> Dict[str, dict]:
    out = {}
    for m in entries:
        value = reader(m["name"])(record)
        if value is None:
            continue
        value = float(value)
        if not math.isfinite(value):
            raise BenchError(f"metric {m['name']} read {value}")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# -- correctness and the result line ---------------------------------------


def check(value: float, limit: float) -> dict:
    return {"value": float(value), "limit": float(limit)}


def all_pass(checks: Dict[str, dict]) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: Dict[str, dict],
                breakdown: Optional[dict] = None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks  # last: the numbers compared, beside their limits
    return json.dumps(out)


def emit(result: dict) -> None:
    """Each number compared beside its limit as the last lines on standard
    error, then the result as the last line of standard output."""
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {name} = {c['value']!r} limit {c['limit']!r} "
              f"{verdict}", file=sys.stderr, flush=True)
    print(result_line(**result), flush=True)
