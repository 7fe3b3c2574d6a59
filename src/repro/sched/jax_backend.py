"""JAX backend of the scheduling-policy protocol (jit/vmap/pjit-safe).

Pure ``jnp`` mirrors of ``numpy_backend.primary_key`` plus the per-policy
voluntary switch-cost model, consumed by ``core.simkernel_jax`` so that
**all** policy kinds — CFS, EEVDF, SCHED_RR, CFS-LAGS, CFS-LAGS-static
(and the tuned-slice variants) — run under ``lax.scan`` and shard across
the cluster mesh.  Policy codes are static jit arguments, so dispatch is
plain Python at trace time: the scan body contains no policy branches.

Secondary tie-break in this backend: the slot id (added as ``idx * eps``
by the simulator); the numpy backend uses thread-vruntime rank instead.
Primary keys are identical across backends — that is the contract the
differential tests pin (``tests/test_sched_backends.py``).

Group values reach entities through a dense one-hot membership mask
(:func:`group_member`, :func:`to_entities`, :func:`to_groups`), never a
gather or scatter: under ``vmap`` inside the simulator's scan a TPU runs
an indexed read with per-node indices nearly one element at a time, while
a masked select and reduce over the group axis is elementwise work.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Tuple

import jax.numpy as jnp

from repro.sched.protocol import (
    CREDIT_EPS,
    EEVDF_INELIGIBLE,
    RT_BASE,
    PolicySpec,
    spec as get_spec,
)

# Static policy codes (jit static args).  CFS/LAGS keep their historical
# values from the two-policy simulator.
CFS, LAGS, EEVDF, RR, LAGS_STATIC, CFS_TUNED, EEVDF_TUNED = range(7)

CODE_OF = {
    "cfs": CFS, "lags": LAGS, "eevdf": EEVDF, "rr": RR,
    "lags-static": LAGS_STATIC, "cfs-tuned": CFS_TUNED,
    "eevdf-tuned": EEVDF_TUNED,
}
NAME_OF = {v: k for k, v in CODE_OF.items()}


def spec_of(code: int, **overrides) -> PolicySpec:
    return get_spec(NAME_OF[code], **overrides)


class PolicyView(NamedTuple):
    """Per-tick scheduling state handed to the key functions.

    Entity-level arrays are (T,) over request slots; group-level arrays
    are (G,) over function/tenant cgroups, expanded to entities through
    ``ent_group`` (:func:`_of_group`).
    """

    ent_group: jnp.ndarray  # (T,) int32
    group_vrt: jnp.ndarray  # (G,)
    group_credit: jnp.ndarray  # (G,)
    last_pick_tick: jnp.ndarray  # (T,)
    runnable: jnp.ndarray  # (T,) bool
    group_runnable: jnp.ndarray  # (G,) bool
    is_rt_group: jnp.ndarray  # (G,) bool
    tick_sec: float  # python scalar (static)
    slice_ticks: int  # python scalar (static)


def group_member(ent_group: jnp.ndarray, n_groups: int) -> jnp.ndarray:
    """(T, G) bool: entity ``t`` belongs to group ``g``."""
    return ent_group[:, None] == jnp.arange(n_groups, dtype=ent_group.dtype)


def to_entities(member: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """``x[ent_group]`` as a masked reduce over groups; exact, since one
    term per entity is kept and the rest are zero (or False)."""
    if x.dtype == jnp.bool_:
        return jnp.any(member & x, axis=1)
    return jnp.sum(jnp.where(member, x, 0), axis=1)


def to_groups(member: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Per-group sum of entity values (a segment sum) as a masked reduce
    over entities."""
    return jnp.sum(jnp.where(member, v[:, None], 0), axis=0)


def _of_group(v: PolicyView, x: jnp.ndarray) -> jnp.ndarray:
    # ent_group is loop-invariant in the simulator: XLA hoists the compare
    return to_entities(group_member(v.ent_group, x.shape[0]), x)


def primary_key(code: int, v: PolicyView) -> jnp.ndarray:
    """(T,) primary key, lower runs first — jnp mirror of numpy_backend."""
    if code == LAGS:
        return _of_group(v, v.group_credit)
    if code == RR:
        return v.last_pick_tick.astype(jnp.float32)
    if code == LAGS_STATIC:
        is_rt = _of_group(v, v.is_rt_group)
        return jnp.where(is_rt, RT_BASE + v.last_pick_tick,
                         _of_group(v, v.group_vrt))
    if code in (EEVDF, EEVDF_TUNED):
        vrt = _of_group(v, v.group_vrt)
        n_run = jnp.maximum(jnp.sum(v.group_runnable), 1)
        vmean = jnp.sum(jnp.where(v.group_runnable, v.group_vrt, 0.0)) / n_run
        deadline = vrt + v.slice_ticks * v.tick_sec
        inel = (vrt > vmean + CREDIT_EPS).astype(vrt.dtype)
        return inel * EEVDF_INELIGIBLE + deadline
    # CFS / CFS_TUNED
    return _of_group(v, v.group_vrt)


def sticky_mask(code: int, v: PolicyView, continuing: jnp.ndarray
                ) -> jnp.ndarray:
    """Which slice-holding slots keep their core this tick.

    ``continuing`` = picked last tick, slice not expired, still runnable.
    Credit preemption (LAGS) and RT wakeups (LAGS-static) break slices:
    a strictly lighter waiting group / a waiting RT task voids stickiness
    so the top-k pick can reclaim the core — the same rules the numpy
    backend applies in ``Policy.preempt_cores``.
    """
    if code == LAGS:
        waiting = v.runnable & ~continuing
        credit = _of_group(v, v.group_credit)
        wait_cmin = jnp.min(jnp.where(waiting, credit, jnp.inf))
        lighter_waits = credit > wait_cmin + CREDIT_EPS
        return continuing & ~lighter_waits
    if code == LAGS_STATIC:
        is_rt = _of_group(v, v.is_rt_group)
        rt_waiting = jnp.any(v.runnable & ~continuing & is_rt)
        return continuing & (is_rt | ~rt_waiting)
    # CFS/EEVDF slices are one tick by default; tuned variants and RR hold
    # the full quantum (wakeup preemption is folded into the burst model).
    return continuing


def voluntary_switch(code: int, *, c_same, c_cross, cost_cfs, run_credit,
                     wait_cmin, sibs, p_preempt) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-policy voluntary handoff cost + switches-per-burst multiplier.

    jnp mirror of ``numpy_backend.Policy.voluntary_switch``: under
    run-to-completion (LAGS kinds) cores serving in credit order hand off
    within the group, a sole runnable sibling is re-picked switch-free,
    and credit-based wakeup preemption fires less often than CFS's.
    """
    if code in (LAGS, LAGS_STATIC):
        in_order = run_credit <= wait_cmin + CREDIT_EPS
        solo = sibs <= 1.0
        cost = jnp.where(in_order & solo, 0.0,
                         jnp.where(in_order, c_same, cost_cfs))
        return cost, 1.0 + 0.85 * p_preempt
    return cost_cfs, 1.0 + p_preempt


def key_fn(code: int) -> Callable[[PolicyView], jnp.ndarray]:
    if code not in NAME_OF:
        raise ValueError(f"unknown policy code {code!r}")
    return lambda v: primary_key(code, v)
