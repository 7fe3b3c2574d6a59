"""Fleet cells: ``fleet.simulate_fleet(backend="jax")`` called back to back.

``--seed`` is the fleet's demand seed.  Set-up places the fleet and makes
one call, which compiles the scan for the shape of this seed's traces (the
busiest handler slot sets it, so a new seed compiles anew).  The window
then calls again as soon as the last call returns, until ``--seconds``
have passed, always with that seed, so nothing compiles in the window; the
rate is the simulated node-seconds of all the calls over their wall
time.

After the window the run checks what the calls produced: every call must
return what the first returned (``calls_differing``), and the fleet's
summed request latency and its switch overhead must agree with the plain
float64 reference of one node (``reference/fleet.py``), run for every
distinct node workload.
"""
from __future__ import annotations

import contextlib
import shutil
import sys
import time
from typing import List

import numpy as np

from perfbench import core, tracereduce
from perfbench.reference import fleet as ref_fleet

CLOCK = time.perf_counter


def _summary(fr) -> tuple:
    lat = fr.latencies
    return (fr.n_completed, float(fr.overhead_frac), lat.tobytes())


class Run:
    def __init__(self, cell: core.Cell, t_start: float):
        from repro.fleet import make_policy, place

        self.cell = cell
        self.fc = dict(cell.config["fleet"], **cell.overrides.get("fleet", {}))
        fc = self.fc
        self.demand_seed = cell.seed
        self.asg = place(fc["placement"], fc["n_fns"], fc["n_nodes"],
                         policy=make_policy(fc["policy"]),
                         n_cores=fc["n_cores"], exec_s=fc["exec_s"],
                         seed=self.demand_seed)
        self.kw = dict(duration_s=fc["duration_s"], n_cores=fc["n_cores"],
                       seed=self.demand_seed, exec_s=fc["exec_s"],
                       backend="jax", threads_per_fn=fc["threads_per_fn"])
        self.first = self.call()  # compiles this seed's scan
        self.setup_s = CLOCK() - t_start

    def call(self):
        from repro.fleet import simulate_fleet

        return simulate_fleet(self.fc["policy"], self.asg, **self.kw)

    def window(self) -> dict:
        cell = self.cell
        build: List[float] = []
        spans = (lambda n: __import__("jax").profiler.TraceAnnotation(
            "bench." + n)) if cell.trace else (
            lambda n: contextlib.nullcontext())
        if cell.trace:
            self._wrap_for_trace(spans, build)
        first = _summary(self.first)
        differing, calls = 0, 0
        traced_call = 1
        t0 = CLOCK()
        while True:
            traced = cell.trace and calls == traced_call
            if traced:
                self._start_trace()
                build.clear()
            with spans("traced" if traced else "call"):
                fr = self.call()
            if traced:
                self._stop_trace()
                self.traced_build_s = sum(build)
            calls += 1
            if _summary(fr) != first:
                differing += 1
            if CLOCK() - t0 >= cell.seconds:
                break
        window_s = CLOCK() - t0
        if cell.trace and calls <= traced_call:
            # one call filled the window: trace one more, after it
            self._start_trace()
            build.clear()
            with spans("traced"):
                self.call()
            self._stop_trace()
            self.traced_build_s = sum(build)
        return dict(window_s=window_s, calls=calls, differing=differing)

    def _start_trace(self):
        import jax

        self.trace_dir = core.TRACE_DIR / self.cell.workload
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(self.trace_dir))

    def _stop_trace(self):
        import jax

        jax.profiler.stop_trace()

    def _wrap_for_trace(self, spans, build: List[float]):
        """Time the host-side trace building of each call, in the traced
        run only."""
        from repro.core import simkernel_jax
        from repro.fleet import simulate

        def timed(mod, name):
            inner = getattr(mod, name)

            def wrapper(*a, **kw):
                t = CLOCK()
                with spans(name):
                    out = inner(*a, **kw)
                build.append(CLOCK() - t)
                return out

            setattr(mod, name, wrapper)

        timed(simulate, "make_workload")
        timed(simulate, "_pad_trace")
        timed(simkernel_jax, "build_slot_trace")

    def checks(self, w: dict, state_dtype=np.float64) -> dict:
        """The numbers compared: the summed latency of every completed
        request (so a lost completion counts too) and the switch overhead,
        each as the gap to the reference relative to the reference, and the
        window's calls that returned something else than the first."""
        fc, fr = self.fc, self.first
        n_ticks = int(fc["duration_s"] / ref_fleet.TICK)
        by_count = {}
        lat, ovh = 0.0, 0.0
        for k in fr.counts:
            k = int(k)
            if k == 0:
                continue
            if k not in by_count:
                at, de, slot_fn = ref_fleet.node_trace(
                    k, fc["duration_s"], fc["n_cores"], self.demand_seed,
                    fc["exec_s"], fc["threads_per_fn"])
                by_count[k] = ref_fleet.simulate_node(
                    at, de, slot_fn, n_fns=k, n_cores=fc["n_cores"],
                    n_ticks=n_ticks, burst_us=fc["burst_us"],
                    depth=fc["cgroup_depth"],
                    window_ticks=fc["credit_window_ticks"],
                    state_dtype=state_dtype)
            lat += float(by_count[k]["latencies"].sum())
            ovh += by_count[k]["overhead_s"]
        ovh_ref = ovh / (len(fr.counts) * fc["n_cores"] * fc["duration_s"])
        def gap(got, want):
            return abs(got - want) / max(abs(want), 1e-12)

        return {
            "latency_gap": gap(float(fr.latencies.sum()), lat),
            "overhead_gap": gap(fr.overhead_frac, ovh_ref),
            "calls_differing": w["differing"],
        }

    def record(self, w: dict, reduction) -> dict:
        fc = self.fc
        return {
            "kind": "fleet", "setup_s": self.setup_s,
            "window_s": w["window_s"], "calls": w["calls"],
            "node_s": w["calls"] * fc["n_nodes"] * fc["duration_s"],
            "build_s": getattr(self, "traced_build_s", None),
            "trace": reduction, "chips": self.cell.chips,
        }


def run(cell: core.Cell, t_start: float) -> dict:
    r = Run(cell, t_start)
    with core.WindowWatch() as watch:
        w = r.window()
    device = core.device_info()
    device["memory_peak_bytes"] = core.memory_peak_bytes()
    reduction = None
    if cell.trace:
        path = tracereduce.find_xplane(r.trace_dir)
        events = tracereduce.events_from_xplane(path)
        reduction = tracereduce.reduce(events, tracereduce.span_window(
            events, "bench.traced"))
        if not cell.keep_trace:
            shutil.rmtree(r.trace_dir, ignore_errors=True)
        device["busy_s"] = reduction["busy_s"]
        device["window_s"] = reduction["window_s"]
    print(f"perfbench: {cell.workload} window {w['window_s']:.3f}s calls "
          f"{w['calls']} completed {r.first.n_completed}; {watch.line()}",
          file=sys.stderr)
    readings = r.checks(w)
    checks = {k: core.check(v, cell.limits[k]) for k, v in readings.items()}
    rec = r.record(w, reduction)
    entries = cell.per_layer if cell.trace else cell.end_to_end
    return dict(correct=core.all_pass(checks), attempted=w["calls"],
                failed=w["differing"], metrics=core.read_metrics(rec, entries),
                device=device, checks=checks,
                breakdown=reduction["breakdown"] if reduction else None)
