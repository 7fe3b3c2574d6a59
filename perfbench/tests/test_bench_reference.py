"""The plain references against the program at small sizes: the decoder's
served tokens sit at the reference's best logit, LAGS admission and the
tick agree step by step, and the fleet node agrees with the scan."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import engine_cell, fleet_cell, weights
from perfbench.reference import decoder, fleet as ref_fleet, lags
from perfbench.tests.helpers import small_cell


def _greedy_stream(mcfg, params, steps, batch=4):
    from repro.serving.engine import decode_and_pick
    from repro.models import model as model_lib

    step = decode_and_pick(mcfg)
    cache = model_lib.init_cache(mcfg, batch, steps)
    tok = jnp.zeros((batch, 1), jnp.int32)
    ins, outs = [], []
    for p in range(steps):
        ins.append(np.asarray(tok)[:, 0])
        tok, _, cache = step(params, tok, cache, jnp.asarray(p))
        outs.append(np.asarray(tok)[:, 0])
    return np.stack(ins, 1), np.stack(outs, 1)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4), ("bfloat16", 0.2)])
def test_served_tokens_sit_at_the_reference_best(dtype, tol):
    cell = small_cell("slice-48t-steady")
    cell.overrides["model"].update(torch_dtype=dtype)
    model = engine_cell.Model(cell)
    assert model.ref is decoder
    mcfg = model.mcfg
    params = weights.make(model.weight_specs, 77)
    ins, outs = _greedy_stream(mcfg, params, 16)
    gap, _ = decoder.block_gaps(params, jnp.asarray(ins), jnp.asarray(outs),
                                sz=model.sz, chunk=16)
    assert float(jnp.max(gap)) <= tol
    # a token altered where it is produced lies far below the best
    bad = (outs + 1) % mcfg.vocab_size
    gap_bad, _ = decoder.block_gaps(params, jnp.asarray(ins),
                                    jnp.asarray(bad), sz=model.sz, chunk=16)
    assert float(jnp.max(gap_bad)) > 10 * max(float(jnp.max(gap)), 1e-3)


def test_weights_match_the_program_layout():
    cell = small_cell("slice-48t-steady")
    model = engine_cell.Model(cell)
    weights.check_layout(model.weight_specs, model.mcfg)
    p = weights.make(model.weight_specs, 5)
    q = weights.make(model.weight_specs, 5)
    assert all(bool(jnp.array_equal(a, b)) for a, b in
               zip(jax.tree_util.tree_leaves(p), jax.tree_util.tree_leaves(q)))
    assert p["embed"].dtype == jnp.bfloat16


def test_admission_and_tick_agree_every_step():
    r = engine_cell.Run(small_cell("slice-48t-steady", seconds=1.0),
                        time.perf_counter())
    engine_cell.SNAPSHOTS_PER_RUN, keep = 10 ** 6, \
        engine_cell.SNAPSHOTS_PER_RUN
    try:
        w = r.window()
    finally:
        engine_cell.SNAPSHOTS_PER_RUN = keep
    assert len(w["snaps"]) == len(w["step_wall"]) > 50
    mism, err = engine_cell.scheduling_readings(w["snaps"], r.ec)
    assert mism == 0 and err < 1e-12
    # the admission the reference gives differs once the credit order is
    # changed: the comparison sees the order
    before, after = next((b, a) for b, a in w["snaps"]
                         if len(a["running"]) > 1 and b["running"]
                         and not all(d for *_, d in b["running"]))
    flipped = dict(before, tenants={
        t: (-c, q) for t, (c, q) in before["tenants"].items()})
    assert (lags.admit(flipped, r.ec["n_slots"], 0.5) != after["running"]
            or len({t for _, t, _ in before["running"]}) == 1)


def test_tick_control_is_coarser():
    rng = np.random.default_rng(0)
    load, credit, frac = rng.random(64), rng.random(64), rng.random(64)
    a = lags.tick(load, credit, frac, window=256, halflife=8)
    b = lags.tick(load, credit, frac, window=256, halflife=8,
                  dtype=np.float32)
    err = lags.state_error(*b, *a)
    assert 1e-9 < err < 1e-5


def test_fleet_reference_agrees_with_the_scan():
    r = fleet_cell.Run(small_cell("fig7-fleet-scan"), time.perf_counter())
    got = r.checks({"differing": 0})
    assert got["latency_gap"] <= 0.01
    assert got["overhead_gap"] <= 0.05
    at, de, fn = ref_fleet.node_trace(20, 6.0, 12, 1, 0.14, 8)
    assert at.shape[0] == 160 and (de[at < ref_fleet.BIG] == 0.14).all()
