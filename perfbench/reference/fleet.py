"""Plain reference of the fleet scan: one node of the Fig 7 fleet, tick by
tick, in float64 numpy.  It imports nothing of the program.

Demand is the ``azure2021`` band model (after the Azure Functions
characterisation, Shahrad et al., ATC 2020), copied from
``core/traces.py``: ten log-spaced demand bands, each function's rate drawn
within its band, Markov-modulated Poisson arrivals (1.5 s on, 10 s off),
every request ``exec_s`` long.  Each function's requests are dealt round
robin to ``threads_per_fn`` slots and run first come first served within
a slot.

Each 4 ms tick: slots whose next request has arrived become runnable; LAGS
gives the node's cores to the runnable slots of the lowest-Load-Credit
functions (ties to the lowest slot); a slot keeps its core for its slice
unless a lighter function waits; each running slot does ``tick * burst /
(burst + switch cost)`` of work, where the switch cost is the cgroup
re-insert model (``core/switch_cost.py``) and LAGS pays nothing for a
sole sibling served in credit order, a same-group switch for one in
order, and CFS's mixed cost otherwise, times ``1 + 0.85 p`` for wakeup
preemptions; the rest of the tick is switch overhead.  Each function's
PELT load (half-life 8 ticks) and Load Credit (EMA over ``window_ticks``)
follow its share of a core.

``state_dtype`` rounds the carried state after every tick; the control
runs it in bfloat16.
"""
from __future__ import annotations

import numpy as np

TICK = 0.004
N_BANDS = 10
PEAK_DENSITY = 9
BAND_EXEC_S = 0.100
BASE_US, PUT_US, SET_US, CROSS_US = 0.5, 1.55, 0.35, 1.0
CREDIT_EPS = 1e-12
BIG = np.iinfo(np.int32).max // 2


def band_rates(n_cores: int) -> np.ndarray:
    raw = np.logspace(0.0, 2.6, N_BANDS)
    capacity_rps = 0.60 * n_cores / BAND_EXEC_S
    per_band = PEAK_DENSITY * n_cores / N_BANDS
    return raw * (capacity_rps / (per_band * raw.sum()))


def fn_rates(n_fns: int, n_cores: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    bands = band_rates(n_cores)
    return np.asarray([bands[i % N_BANDS] * rng.uniform(0.6, 1.4)
                       for i in range(n_fns)])


def mmpp(rate, duration, rng, burst_on=1.5, burst_off=10.0):
    if rate <= 0:
        return np.empty(0)
    frac_on = burst_on / (burst_on + burst_off)
    on_rate = rate / frac_on
    out, t = [], 0.0
    on = rng.uniform() < frac_on
    while t < duration:
        seg = min(rng.exponential(burst_on if on else burst_off),
                  duration - t)
        if on and on_rate > 0:
            n = rng.poisson(on_rate * seg)
            out.append(t + np.sort(rng.uniform(0, seg, n)))
        t += seg
        on = not on
    return np.concatenate(out) if out else np.empty(0)


def node_trace(n_fns: int, duration_s: float, n_cores: int, seed: int,
               exec_s: float, tpf: int):
    """(arrival tick (S, R), demand (S, R), slot's function (S,))."""
    rng = np.random.default_rng(seed)
    rates = fn_rates(n_fns, n_cores, seed)
    slots = [[] for _ in range(n_fns * tpf)]
    for f in range(n_fns):
        for j, t in enumerate(mmpp(rates[f], duration_s, rng)):
            slots[f * tpf + j % tpf].append(int(t / TICK))
    R = max(1, max(len(q) for q in slots))
    at = np.full((len(slots), R), BIG, np.int64)
    de = np.zeros((len(slots), R))
    for s, q in enumerate(slots):
        at[s, :len(q)] = q
        de[s, :len(q)] = exec_s
    return at, de, np.repeat(np.arange(n_fns), tpf)


def _switch_us(same: bool, sib, grp, depth: float):
    leaf = PUT_US * np.log2(1.0 + np.maximum(sib, 1.0))
    upper = PUT_US * np.log2(1.0 + max(grp, 1.0)) * max(depth - 1.0, 1.0)
    return BASE_US + leaf + SET_US * depth + (0.0 if same
                                              else upper + CROSS_US)


def simulate_node(at, de, slot_fn, *, n_fns: int, n_cores: int,
                  n_ticks: int, burst_us: float, depth: float,
                  window_ticks: int, slice_ticks: int = 1,
                  halflife: int = 8, state_dtype=np.float64) -> dict:
    S, R = at.shape
    C = n_cores
    ar = np.arange(S)
    q = (lambda x: x) if state_dtype is np.float64 else (
        lambda x: np.asarray(x).astype(state_dtype).astype(np.float64))
    ptr = np.zeros(S, np.int64)
    rem = np.zeros(S)
    load = np.zeros(n_fns)
    credit = np.zeros(n_fns)
    busy = ovh = 0.0
    done = np.full((S, R), -1, np.int64)
    slice_left = np.zeros(S, np.int64)
    prev = np.zeros(S, bool)
    y = 0.5 ** (1.0 / halflife)
    alpha = 2.0 / (window_ticks + 1.0)
    burst = burst_us * 1e-6
    for tick in range(n_ticks):
        cur = np.minimum(ptr, R - 1)
        start = (rem <= 0.0) & (at[ar, cur] <= tick) & (ptr < R)
        rem = np.where(start, de[ar, cur], rem)
        runnable = rem > 0.0
        sib = np.bincount(slot_fn, weights=runnable, minlength=n_fns)
        fn_run = sib > 0
        slot_credit = credit[slot_fn]
        continuing = prev & (slice_left > 0) & runnable
        waiting = runnable & ~continuing
        wait_min = slot_credit[waiting].min() if waiting.any() else np.inf
        sticky = continuing & ~(slot_credit > wait_min + CREDIT_EPS)
        key = np.where(runnable, slot_credit, np.inf) + ar * 1e-12
        key = np.where(sticky, key - 1e18, key)
        order = np.argsort(key, kind="stable")[:C]
        picked = np.isfinite(key[order])
        run = order[picked]
        picked_slot = np.zeros(S, bool)
        picked_slot[run] = True
        slice_left = np.where(picked_slot, np.where(
            continuing, slice_left - 1, slice_ticks - 1), 0)
        n_grp = int(fn_run.sum())
        n_run = int(runnable.sum())
        run_fn = slot_fn[run]
        sibs = sib[run_fn]
        p_pre = min(1.0, max(n_run - len(run), 0) / (2.0 * C))
        c_same = _switch_us(True, sibs, n_grp, depth)
        c_cross = _switch_us(False, sibs, n_grp, depth)
        p_same = np.clip((sibs - 1.0) / max(n_run - 1.0, 1.0), 0.0, 1.0)
        cost_cfs = p_same * c_same + (1.0 - p_same) * c_cross
        wait_cmin = credit[fn_run].min() if fn_run.any() else np.inf
        in_order = credit[run_fn] <= wait_cmin + CREDIT_EPS
        cost = np.where(in_order & (sibs <= 1.0), 0.0,
                        np.where(in_order, c_same, cost_cfs))
        cost_v = cost * 1e-6 * (1.0 + 0.85 * p_pre)
        eff = TICK * burst / (burst + cost_v)
        ovh = ovh + float(np.sum(TICK - eff))
        busy = busy + float(np.sum(np.minimum(eff, rem[run])))
        new_rem = rem.copy()
        new_rem[run] -= eff
        completed = (rem > 0.0) & (new_rem <= 0.0)
        done[ar[completed], np.minimum(ptr, R - 1)[completed]] = tick
        ptr = ptr + completed
        frac = np.bincount(run_fn, weights=eff / TICK, minlength=n_fns)
        load = q(y * load + (1.0 - y) * frac)
        credit = q((1.0 - alpha) * credit + alpha * load)
        rem = q(new_rem)
        busy, ovh = float(q(busy)), float(q(ovh))
        prev = picked_slot
    ok = (done >= 0) & (at < BIG)
    return {"latencies": ((done[ok] + 1) - at[ok]) * TICK,
            "n_completed": int(ok.sum()), "overhead_s": ovh, "busy_s": busy}
