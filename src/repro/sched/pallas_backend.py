"""Pallas backend: fused Load-Credit tick + k-lowest-credit selection.

Wraps the TPU kernel ``repro.kernels.lags_select`` (PELT + Load Credit EMA
update followed by top-k-lowest selection — ``pick_next_task_fair``
vectorised) as the scheduling-policy protocol's third backend.  The
serving engine routes its per-step credit tick through this path once the
tenant count crosses ``EngineConfig.pallas_threshold``: one kernel launch
replaces the O(T) Python EMA loop, and the returned pick order is exactly
the LAGS admission order the engine applies next step.

Off-TPU the kernel runs in Pallas interpret mode (bit-compatible, slow) —
``interpret_default`` picks the mode from the active JAX backend, and is the
repository's one such choice: tests and CPU smoke runs exercise the
identical kernel code path, and on a TPU the kernel runs compiled.

``numpy_reference`` is the float64 oracle for the cross-backend
differential tests.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.load_credit import (
    DEFAULT_EMA_WINDOW,
    PELT_HALFLIFE_TICKS,
    ema_update,
    pelt_update,
)
from repro.kernels.lags_select import lags_select


def interpret_default() -> bool:
    """Run Pallas kernels in interpret mode unless the backend is a TPU."""
    return jax.default_backend() != "tpu"


@functools.partial(
    jax.jit, static_argnames=("k", "window", "halflife", "interpret"))
def tick(load_avg, credit, running_frac, runnable, *, k: int, window: int,
         halflife: int, interpret: bool):
    """The jitted tick ``tick_and_pick`` runs: (T,) float32 state and a
    (T,) bool runnable mask in, ``lags_select``'s outputs out."""
    return lags_select(load_avg, credit, running_frac, runnable, k,
                       window=window, halflife=halflife, interpret=interpret)


def tick_and_pick(load_avg, credit, running_frac, runnable, k: int, *,
                  window: int = DEFAULT_EMA_WINDOW,
                  halflife: int = PELT_HALFLIFE_TICKS,
                  interpret: bool | None = None):
    """One scheduler tick over T groups on the Pallas kernel.

    Returns ``(new_load (T,), new_credit (T,), picked_idx (k,) int32)``
    with -1 padding when fewer than k groups are runnable.  Picked order
    is ascending updated credit, ties broken by group index — identical
    to the numpy backend's LAGS admission order.
    """
    if interpret is None:
        interpret = interpret_default()
    nl, nc, idx = tick(
        jnp.asarray(load_avg, jnp.float32),
        jnp.asarray(credit, jnp.float32),
        jnp.asarray(running_frac, jnp.float32),
        jnp.asarray(runnable, bool),
        k=k, window=window, halflife=halflife, interpret=interpret,
    )
    return np.asarray(nl), np.asarray(nc), np.asarray(idx)


def numpy_reference(load_avg, credit, running_frac, runnable, k: int, *,
                    window: int = DEFAULT_EMA_WINDOW,
                    halflife: int = PELT_HALFLIFE_TICKS
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """float64 oracle: same tick + selection via the numpy protocol path."""
    y = 0.5 ** (1.0 / halflife)
    new_load = pelt_update(np.asarray(load_avg, np.float64),
                           np.asarray(running_frac, np.float64), y)
    new_credit = ema_update(np.asarray(credit, np.float64), new_load, window)
    runnable = np.asarray(runnable, bool)
    order = [i for i in np.lexsort((np.arange(len(new_credit)), new_credit))
             if runnable[i]][:k]
    picked = np.full(k, -1, np.int32)
    picked[: len(order)] = order
    return new_load, new_credit, picked
