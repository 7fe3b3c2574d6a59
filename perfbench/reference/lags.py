"""Plain reference of one serving-engine step's scheduling: LAGS admission
and the Load-Credit tick, as the configuration states them.

It imports nothing of the program.  It is handed what the engine held
before a step (each tenant's credit, load and queue, the running requests)
and says what the engine must hold after it:

* admission: finished requests leave; if a waiting tenant's credit is
  below ``hysteresis`` times the heaviest running tenant's (ties broken by
  tenant id) and the batch is full, that running tenant's first request
  yields its slot and goes back to the head of its queue; free slots are
  filled lowest credit first (then lowest tenant id), each chosen tenant's
  queue drained in order before the next (run to completion);
* the tick: each tenant's PELT load ``y*load + (1-y)*frac`` with half-life
  ``halflife`` steps, then its credit ``(1-a)*credit + a*load`` with
  ``a = 2/(window+1)``, where ``frac`` is the tenant's share of the step's
  service.  ``tick(..., dtype)`` computes it in a given precision, which is
  how the control of the tick is made.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

CREDIT_EPS = 1e-12


def admit(snap: dict, n_slots: int, hysteresis: float) -> List[int]:
    """Request ids running after the step, in the engine's batch order.

    ``snap``: ``running`` [(rid, tenant, done)], ``tenants`` {tid: (credit,
    [queued rids])}.
    """
    running = [(rid, t) for rid, t, done in snap["running"] if not done]
    queues: Dict[int, list] = {t: list(q) for t, (_, q) in
                               snap["tenants"].items()}
    credit = {t: c for t, (c, _) in snap["tenants"].items()}
    waiting = [t for t in queues if queues[t]]
    run_t = {t for _, t in running}
    if waiting and run_t:
        light = min(waiting, key=lambda t: (credit[t], t))
        heavy = max(run_t, key=lambda t: (credit[t], -t))
        fire = credit[light] < hysteresis * credit[heavy] - CREDIT_EPS
        if fire and len(running) >= n_slots:
            i = next(i for i, (_, t) in enumerate(running) if t == heavy)
            queues[heavy].insert(0, running.pop(i)[0])
    free = n_slots - len(running)
    admitted: List[int] = []
    for t in sorted((t for t in queues if queues[t]),
                    key=lambda t: (credit[t], t)):
        while queues[t] and len(admitted) < free:
            admitted.append(queues[t].pop(0))
        if len(admitted) >= free:
            break
    return [rid for rid, _ in running] + admitted


def tick(load, credit, frac, *, window: int, halflife: int,
         dtype=np.float64):
    """One Load-Credit tick in ``dtype``; returns float64 arrays."""
    y = dtype(0.5 ** (1.0 / halflife))
    a = dtype(2.0 / (window + 1.0))
    one = dtype(1.0)
    load = np.asarray(load, dtype)
    credit = np.asarray(credit, dtype)
    frac = np.asarray(frac, dtype)
    new_load = (y * load + (one - y) * frac).astype(dtype)
    new_credit = ((one - a) * credit + a * new_load).astype(dtype)
    return new_load.astype(np.float64), new_credit.astype(np.float64)


def state_error(got_load, got_credit, want_load, want_credit) -> float:
    """Worst tenant's error in load or credit, relative to the largest
    value of that quantity."""
    err = 0.0
    for got, want in ((got_load, want_load), (got_credit, want_credit)):
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        scale = max(float(np.max(np.abs(want))), 1e-30)
        err = max(err, float(np.max(np.abs(got - want))) / scale)
    return err
