"""JAX tick simulator: the paper's scheduler as a composable JAX module.

A functional ``lax.scan`` port of ``simkernel``.  Fully jit-able,
``vmap``-able over nodes, and pjit-shardable over the production mesh —
the cluster consolidation study runs hundreds of simulated nodes
data-parallel on a pod (see ``repro.core.cluster`` and
``benchmarks/fig7_cluster.py``).

Policy logic lives entirely in ``repro.sched.jax_backend``: the policy
code in :class:`SimParams` is a static jit argument resolved to pure
``jnp`` key / stickiness / voluntary-cost functions at trace time, so
**every** policy kind — CFS, EEVDF, SCHED_RR, CFS-LAGS, CFS-LAGS-static
and the tuned-slice variants — runs through this one scan body with no
policy branching here.

Modelling simplifications vs the numpy engine (validated against it in
``tests/test_simkernel_jax.py``): requests are pre-assigned round-robin to
a fixed per-function slot pool (FIFO within a slot), core assignment is a
per-tick top-C selection with slice stickiness (sticky-core switch
accounting is statistical, as in the numpy engine's burst model).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import load_credit as lc
from repro.core.switch_cost import BASE_US, CROSS_US, PUT_US, SET_US
from repro.sched import jax_backend as jb

TICK = lc.TICK_SEC

# historical two-policy codes, re-exported for existing callers; the full
# set (EEVDF, RR, LAGS_STATIC, *_TUNED) lives in repro.sched.jax_backend
CFS, LAGS = jb.CFS, jb.LAGS


class SlotTrace(NamedTuple):
    """Per-slot request queues, preassigned (see module docstring)."""

    arrival_tick: jnp.ndarray  # (T, R) int32, padded with BIG
    demand: jnp.ndarray  # (T, R) float32 seconds
    slot_fn: jnp.ndarray  # (T,) int32


class SimParams(NamedTuple):
    n_cores: int
    n_fns: int
    n_ticks: int
    policy: int = CFS  # repro.sched.jax_backend code (static)
    burst_us: float = 120.0
    depth: float = 2.0
    window_ticks: int = 1000
    rt_fns: Tuple[int, ...] = ()  # lags-static: fn ids under SCHED_RR


def _switch_cost_us(same, sib, grp, depth):
    leaf = PUT_US * jnp.log2(1.0 + jnp.maximum(sib, 1.0))
    upper = PUT_US * jnp.log2(1.0 + jnp.maximum(grp, 1.0)) * jnp.maximum(
        depth - 1.0, 1.0
    )
    return BASE_US + leaf + SET_US * depth + jnp.where(same, 0.0, upper + CROSS_US)


def build_slot_trace(workload, n_fns: int, threads_per_fn: int) -> SlotTrace:
    """Pack a ``simkernel.Workload``-style arrival list into fixed slots."""
    BIG = np.iinfo(np.int32).max // 2
    per_slot: list = [[] for _ in range(n_fns * threads_per_fn)]
    for f in range(n_fns):
        arr = workload.arrivals[f]
        dem = workload.service_s[f]
        for j, (t, d) in enumerate(zip(arr, dem)):
            slot = f * threads_per_fn + (j % threads_per_fn)
            per_slot[slot].append((int(t / TICK), float(d)))
    R = max(1, max(len(q) for q in per_slot))
    T = len(per_slot)
    at = np.full((T, R), BIG, np.int32)
    de = np.zeros((T, R), np.float32)
    for s, q in enumerate(per_slot):
        for j, (t, d) in enumerate(q):
            at[s, j] = t
            de[s, j] = d
    slot_fn = np.repeat(np.arange(n_fns, dtype=np.int32), threads_per_fn)
    return SlotTrace(jnp.asarray(at), jnp.asarray(de), jnp.asarray(slot_fn))


def at_pointer(sel: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Each slot's entry of ``x`` (T, R) at the one-hot pointer ``sel``
    (T, R) as a masked sum over R: exact, since one term is kept.  A slot
    whose pointer is past its last request (no ``sel`` entry) reads 0."""
    return jnp.sum(jnp.where(sel, x, 0), axis=1)


@partial(jax.jit, static_argnums=(1,))
def simulate(trace: SlotTrace, p: SimParams):
    """Returns dict of per-request completion ticks + node-level counters.

    The tick body holds no gather and no scatter (``tests/
    test_simkernel_jax.py`` guards the lowered program): every indexed
    read or write is a dense masked select and reduce over a fixed axis —
    the request axis at each slot's pointer, the group axis through the
    slot-to-function one-hot, the slot axis through the cores' picks.
    """
    T, R = trace.arrival_tick.shape
    C = p.n_cores
    spec = jb.spec_of(p.policy)
    slice_ticks = spec.slice_ticks
    is_rt_fn = jnp.asarray(np.isin(np.arange(p.n_fns), p.rt_fns))
    member = jb.group_member(trace.slot_fn, p.n_fns)  # (T, G)
    req = jnp.arange(R)
    slots = jnp.arange(T)

    def tick_body(state, tick):
        (ptr, rem, vrt_fn, load, credit, busy, ovh, done_tick,
         last_pick, slice_left, prev_picked) = state

        # activate: slot idle (rem<=0, i.e. between requests) whose next
        # request has arrived
        sel = req == ptr[:, None]  # (T, R): each slot's current request
        next_arr = at_pointer(sel, trace.arrival_tick)
        can_start = (rem <= 0.0) & (next_arr <= tick) & (ptr < R)
        new_dem = at_pointer(sel, trace.demand)
        rem = jnp.where(can_start, new_dem, rem)
        runnable = rem > 0.0

        # group stats (shared mechanism, not policy)
        sib_count = jb.to_groups(member, runnable.astype(jnp.float32))
        fn_runnable = sib_count > 0

        # policy key via the protocol backend; deterministic tie-break by
        # slot id is this backend's secondary
        view = jb.PolicyView(
            ent_group=trace.slot_fn,
            group_vrt=vrt_fn,
            group_credit=credit,
            last_pick_tick=last_pick,
            runnable=runnable,
            group_runnable=fn_runnable,
            is_rt_group=is_rt_fn,
            tick_sec=TICK,
            slice_ticks=slice_ticks,
        )
        key = jb.primary_key(p.policy, view)
        key = jnp.where(runnable, key, jnp.inf)
        key = key + jnp.arange(T) * 1e-12

        # slice stickiness: a slot that holds an unexpired slice keeps its
        # core unless the policy's preemption rule voids it
        continuing = prev_picked & (slice_left > 0) & runnable
        sticky = jb.sticky_mask(p.policy, view, continuing)
        key = jnp.where(sticky, key - 1e18, key)

        # pick C best runnable; each slot is picked by at most one core
        neg, idx = jax.lax.top_k(-key, C)
        picked = jnp.isfinite(-neg)  # (C,)
        picks = (idx[:, None] == slots) & picked[:, None]  # (C, T)
        picked_slot = jnp.any(picks, axis=0)

        # slice bookkeeping
        slice_left = jnp.where(
            picked_slot,
            jnp.where(continuing, slice_left - 1, slice_ticks - 1),
            0,
        )
        last_pick = jnp.where(picked_slot, tick.astype(last_pick.dtype),
                              last_pick)

        n_grp = jnp.sum(fn_runnable)
        n_run = jnp.sum(runnable)

        # per-slot switch cost, used where the slot is picked
        sibs = jb.to_entities(member, sib_count)
        n_wait = jnp.maximum(n_run - jnp.sum(picked), 0.0)
        p_pre = jnp.minimum(1.0, n_wait / (2.0 * C))

        c_same = _switch_cost_us(True, sibs, n_grp, p.depth)
        c_cross = _switch_cost_us(False, sibs, n_grp, p.depth)
        p_same_cfs = jnp.clip((sibs - 1.0) / jnp.maximum(n_run - 1.0, 1.0), 0, 1)
        cost_cfs = p_same_cfs * c_same + (1 - p_same_cfs) * c_cross

        run_credit = jb.to_entities(member, credit)
        masked_cred = jnp.where(fn_runnable, credit, jnp.inf)
        wait_cmin = jnp.min(masked_cred)
        cost_us, spb = jb.voluntary_switch(
            p.policy, c_same=c_same, c_cross=c_cross, cost_cfs=cost_cfs,
            run_credit=run_credit, wait_cmin=wait_cmin, sibs=sibs,
            p_preempt=p_pre,
        )
        cost_v = cost_us * 1e-6 * spb

        eff = jnp.where(picked_slot, TICK * (cfg_burst := p.burst_us * 1e-6)
                        / (cfg_burst + cost_v), 0.0)
        ovh = ovh + jnp.sum(jnp.where(picked_slot, TICK - eff, 0.0))
        busy = busy + jnp.sum(jnp.minimum(eff, rem * picked_slot))

        # progress
        new_rem = rem - eff
        completed = (rem > 0.0) & (new_rem <= 0.0)
        # record completion tick for the slot's current request (a slot
        # that completes is runnable, so its pointer is below R)
        done_tick = jnp.where(sel & completed[:, None], tick, done_tick)
        ptr = ptr + completed.astype(jnp.int32)

        # load credit
        frac = jb.to_groups(member, eff / TICK)
        (load, credit), _ = lc.jax_tick((load, credit), frac, p.window_ticks)

        # fn vruntime advances by group core-time
        vrt_fn = vrt_fn + jb.to_groups(member, eff)

        return (ptr, new_rem, vrt_fn, load, credit, busy, ovh, done_tick,
                last_pick, slice_left, picked_slot), None

    init = (
        jnp.zeros(T, jnp.int32),
        jnp.zeros(T),
        jnp.zeros(p.n_fns),
        jnp.zeros(p.n_fns),
        jnp.zeros(p.n_fns),
        jnp.zeros(()),
        jnp.zeros(()),
        jnp.full((T, R), -1, jnp.int32),
        jnp.zeros(T),  # last_pick tick
        jnp.zeros(T, jnp.int32),  # slice_left
        jnp.zeros(T, bool),  # prev_picked
    )
    state, _ = jax.lax.scan(tick_body, init, jnp.arange(p.n_ticks))
    (ptr, rem, vrt_fn, load, credit, busy, ovh, done,
     _last_pick, _slice_left, _prev_picked) = state
    return {
        "done_tick": done,
        "busy_s": busy,
        "overhead_s": ovh,
        "credit": credit,
    }


def latencies_from(trace: SlotTrace, done_tick) -> np.ndarray:
    """Completed-request latencies in seconds."""
    at = np.asarray(trace.arrival_tick)
    dt = np.asarray(done_tick)
    ok = (dt >= 0) & (at < np.iinfo(np.int32).max // 2)
    return ((dt[ok] + 1) - at[ok]) * TICK
