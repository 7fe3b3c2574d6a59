"""Each fault a cell can have, planted under the timed path with the chip
check skipped, turns ``correct`` false; so does the control.

Serving cells: a token altered where it is produced; half of the batch
left undecoded; a decode step that returns its cache unchanged; admission
out of LAGS order; a credit tick that returns its state unchanged.  Fleet
cells: an answer altered where it is produced; a scan whose credit state
never changes.  No cell spans chips, so no exchange between chips can be
left out."""
import jax
import pytest

from perfbench.tests.helpers import run_cell, small_cell


def _wrap_decode(monkeypatch, fault):
    """Plant ``fault`` in the engine's jitted device step."""
    from repro.serving import engine

    real = engine.decode_and_pick

    def faulty(model_cfg):
        step = real(model_cfg)

        def run(params, tokens, cache, cache_len):
            nxt, finite, new_cache = step(params, tokens, cache, cache_len)
            return fault(tokens, nxt, finite, cache, new_cache)

        return run

    monkeypatch.setattr(engine, "decode_and_pick", faulty)


SERVING_FAULTS = {
    "token_altered": lambda tok, nxt, fin, c, nc: ((nxt + 1) % 256, fin, nc),
    "half_batch_undecoded": lambda tok, nxt, fin, c, nc: (
        nxt.at[8:].set(tok[8:]), fin, nc),
    "state_unchanged": lambda tok, nxt, fin, c, nc: (nxt, fin, c),
}


@pytest.mark.parametrize("fault", sorted(SERVING_FAULTS))
def test_serving_decode_fault_is_not_correct(fault, monkeypatch):
    _wrap_decode(monkeypatch, SERVING_FAULTS[fault])
    out = run_cell(small_cell("slice-48t-steady", seconds=1.0))
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]


def test_admission_out_of_order_is_not_correct(monkeypatch):
    from repro.serving import engine

    real = engine.pick_admissions

    def reversed_pick(policy, tenants, free, running):
        return list(reversed(real(policy, tenants, free, running)))

    monkeypatch.setattr(engine, "pick_admissions", reversed_pick)
    out = run_cell(small_cell("slice-48t-backlog", seconds=1.0))
    assert out["correct"] is False
    assert out["checks"]["admission_mismatches"]["value"] > 0


@pytest.mark.parametrize("workload", ["slice-48t-steady",
                                      "slice-1024t-steady"])
def test_tick_returning_its_state_is_not_correct(workload, monkeypatch):
    from repro.sched import pallas_backend
    from repro.scheduler.tenant import Tenant

    monkeypatch.setattr(Tenant, "tick", lambda self, s, st, w=256: None)
    monkeypatch.setattr(pallas_backend, "tick_and_pick",
                        lambda load, cred, frac, run, k, **kw: (
                            load, cred, None))
    out = run_cell(small_cell(workload, seconds=1.0))
    assert out["correct"] is False
    assert out["checks"]["tick_state_err"]["value"] > \
        out["checks"]["tick_state_err"]["limit"]


@pytest.fixture
def fresh_jit():
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_fleet_answer_altered_is_not_correct(monkeypatch, fresh_jit):
    from repro.core import simkernel_jax

    real = simkernel_jax.latencies_from
    monkeypatch.setattr(simkernel_jax, "latencies_from",
                        lambda trace, done: real(trace, done) + 0.04)
    out = run_cell(small_cell("fig7-fleet-scan", seconds=0.5))
    assert out["correct"] is False
    assert out["checks"]["latency_gap"]["value"] > \
        out["checks"]["latency_gap"]["limit"]


def test_fleet_credit_state_unchanged_is_not_correct(monkeypatch, fresh_jit):
    from repro.core import load_credit

    monkeypatch.setattr(load_credit, "jax_tick",
                        lambda state, frac, *a, **k: (state, state[1]))
    out = run_cell(small_cell("fig7-fleet-scan", seconds=0.5))
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("workload", ["slice-48t-steady", "fig7-fleet-scan",
                                      "slice-1024t-steady"])
def test_control_is_not_correct(workload):
    """The reference in the next lower precision, in the program's place,
    fails at least one of the cell's limits; the decoder's float8 control
    also lies well above the served bfloat16 tokens at this size."""
    from perfbench import control

    cell = small_cell(workload, seconds=1.0)
    got = control.readings(cell)
    low = got["control"]
    assert any(v > cell.limits[k] for k, v in low.items()), got
    if "logit_gap" in low:
        assert low["logit_gap"] > 5 * max(got["logit_gap"], 0.01)
