"""The one traffic generator.  Every file under ``traffic/`` is data for it.

Serving traffic (``kind`` ``open_loop`` or ``closed_loop``) is the request
mix of ``launch/serve.py::build_workload`` (``serve.py:48-64``, repeated in
``benchmarks/serving_lags.py::run_engine``), copied here so that a later
change to the program cannot move the yardstick: each tenant has a weight
size drawn uniformly from ``weight_mb``, log-spaced mean rates, Markov-
modulated Poisson arrivals (``burst_on_s`` on, ``burst_off_s`` off, ten
times the mean rate while on: ``core/traces.py::_mmpp_arrivals``), prompts
and outputs drawn uniformly from ``prompt_tokens`` and ``new_tokens``
(half-open ranges).  Here the aggregate rate is requests per second of the
host's wall clock, not of the engine's simulated clock.

Arrival times and sizes come from the file's ``schedule_seed``, so every
``--seed`` gets the same set of sizes and arrivals; the run's seed deals
them to the tenants in another order (a permutation of tenant labels).
Tenant labels break ties in the admission order, so the order matters,
but the work offered does not change with the seed.

Fleet traffic (``kind`` ``back_to_back``) is a call pattern: the fleet
simulation is called again as soon as the last call returns.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import numpy as np


@dataclass
class Arrival:
    rid: int
    tenant: int
    prompt_len: int
    max_new: int
    due_s: float  # seconds after the window opens


@dataclass
class ServingTraffic:
    weight_mb: List[float]  # per tenant label
    arrivals: List[Arrival]  # open loop: the schedule, sorted by due time
    # closed loop: per tenant label, the sizes of its successive requests
    sizes: Dict[int, List[tuple]]
    outstanding: int  # closed loop: requests each tenant keeps in flight


def mmpp_arrivals(rate, duration, rng, burst_on=1.5, burst_off=10.0):
    """Markov-modulated Poisson: ON (bursty) / OFF periods, mean ``rate``
    (a copy of ``core/traces.py::_mmpp_arrivals``)."""
    if rate <= 0:
        return np.empty(0)
    frac_on = burst_on / (burst_on + burst_off)
    on_rate = rate / frac_on
    out = []
    t = 0.0
    on = rng.uniform() < frac_on
    while t < duration:
        seg = rng.exponential(burst_on if on else burst_off)
        seg = min(seg, duration - t)
        if on and on_rate > 0:
            n = rng.poisson(on_rate * seg)
            out.append(t + np.sort(rng.uniform(0, seg, n)))
        t += seg
        on = not on
    return np.concatenate(out) if out else np.empty(0)


def tenant_rates(spec: dict) -> np.ndarray:
    lo, hi = spec["rate_log10_span"]
    rates = np.logspace(lo, hi, spec["tenants"])
    return rates * (spec["rate_per_s"] / rates.sum())


def serving(spec: dict, seed: int, seconds: float) -> ServingTraffic:
    n = int(spec["tenants"])
    rng = np.random.default_rng(spec["schedule_seed"])
    weight = [float(rng.uniform(*spec["weight_mb"])) for _ in range(n)]
    label = np.random.default_rng(int(seed)).permutation(n)
    p_lo, p_hi = spec["prompt_tokens"]
    o_lo, o_hi = spec["new_tokens"]
    weight_mb = [0.0] * n
    for t in range(n):
        weight_mb[label[t]] = weight[t]
    if spec["kind"] == "open_loop":
        rates = tenant_rates(spec)
        rows = []
        for t in range(n):
            for a in mmpp_arrivals(rates[t], seconds, rng,
                                   spec["burst_on_s"], spec["burst_off_s"]):
                rows.append((float(a), int(label[t]),
                             int(rng.integers(p_lo, p_hi)),
                             int(rng.integers(o_lo, o_hi))))
        rows.sort(key=lambda r: (r[0], r[1]))
        arrivals = [Arrival(i, t, p, o, a)
                    for i, (a, t, p, o) in enumerate(rows)]
        return ServingTraffic(weight_mb, arrivals, {}, 0)
    if spec["kind"] == "closed_loop":
        depth = int(spec["requests_per_tenant"])
        sizes = {}
        for t in range(n):
            sizes[int(label[t])] = [
                (int(rng.integers(p_lo, p_hi)), int(rng.integers(o_lo, o_hi)))
                for _ in range(depth)]
        return ServingTraffic(weight_mb, [], sizes,
                              int(spec["outstanding_per_tenant"]))
    raise ValueError(f"unknown serving traffic kind {spec['kind']!r}")

