"""Cross-backend differential gate for the unified policy core.

The contract (sched/protocol.py): the numpy, JAX and Pallas backends must
agree on scheduling decisions — identical picked / preempted sets — on
randomized small cases.  State is generated on a coarse 1/16 grid with a
power-of-two group count so every primary key (and the EEVDF runnable
mean) is exact in both float32 and float64: any disagreement is a formula
divergence, not rounding.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.scheduler.tenant import Request, Tenant
from repro.sched import jax_backend as jb
from repro.sched import numpy_backend as nb
from repro.sched import pallas_backend as pb
from repro.sched import protocol
from repro.sched.serving import admission_policy

POLICIES = ("cfs", "eevdf", "rr", "lags", "lags-static")
N_SEEDS = 5  # x 5 policies = 25 randomized cases (acceptance floor: 20)


def _random_case(rng, policy):
    G = 4  # power of two: the EEVDF runnable mean stays grid-exact
    T = int(rng.integers(6, 13))
    ent_group = rng.integers(0, G, T)
    grid = lambda n: rng.choice(np.arange(128), size=n, replace=False) / 16.0
    group_vrt = grid(G)
    group_credit = grid(G)
    last_pick = rng.permutation(T).astype(np.float64)
    runnable = rng.random(T) < 0.8
    if not runnable.any():
        runnable[int(rng.integers(0, T))] = True
    group_runnable = np.zeros(G, bool)
    group_runnable[np.unique(ent_group[runnable])] = True
    is_rt = np.zeros(G, bool)
    if policy == "lags-static":
        is_rt[int(rng.integers(0, G))] = True
    k = int(rng.integers(1, 5))
    return dict(ent_group=ent_group, group_vrt=group_vrt,
                group_credit=group_credit, last_pick=last_pick,
                runnable=runnable, group_runnable=group_runnable,
                is_rt=is_rt, k=k)


@pytest.mark.parametrize("policy", POLICIES)
def test_numpy_jax_primary_keys_pick_identical_sets(policy):
    """numpy and JAX primary keys admit the same entity sets."""
    spec = protocol.spec(policy)
    for seed in range(N_SEEDS):
        rng = np.random.default_rng(1000 * seed + hash(policy) % 1000)
        c = _random_case(rng, policy)

        nview = nb.EntityView(
            ent_group=c["ent_group"], group_vrt=c["group_vrt"],
            group_credit=c["group_credit"], last_pick_tick=c["last_pick"],
            runnable=c["runnable"], group_runnable=c["group_runnable"],
            is_rt_group=c["is_rt"], tick_sec=0.004,
            slice_ticks=spec.slice_ticks,
        )
        key_np = nb.primary_key(spec, nview)

        jview = jb.PolicyView(
            ent_group=jnp.asarray(c["ent_group"], jnp.int32),
            group_vrt=jnp.asarray(c["group_vrt"], jnp.float32),
            group_credit=jnp.asarray(c["group_credit"], jnp.float32),
            last_pick_tick=jnp.asarray(c["last_pick"], jnp.float32),
            runnable=jnp.asarray(c["runnable"]),
            group_runnable=jnp.asarray(c["group_runnable"]),
            is_rt_group=jnp.asarray(c["is_rt"]),
            tick_sec=0.004, slice_ticks=spec.slice_ticks,
        )
        key_jx = np.asarray(
            jb.primary_key(jb.CODE_OF[policy], jview), np.float64
        )

        np.testing.assert_allclose(key_jx, key_np, rtol=1e-6, atol=1e-6)
        picks_np = nb.pick_k(key_np, c["runnable"], c["k"])
        picks_jx = nb.pick_k(key_jx, c["runnable"], c["k"])
        assert picks_np.tolist() == picks_jx.tolist(), (
            f"{policy} seed {seed}: numpy picked {picks_np}, "
            f"jax picked {picks_jx}"
        )


def test_preemption_rule_agrees_across_backends():
    """protocol.credit_preempt, the JAX sticky-slice break and the serving
    LAGS admission policy fire on exactly the same credit states."""
    rng = np.random.default_rng(42)
    fired = set()
    for _ in range(25):
        G = int(rng.integers(2, 7))
        credit = rng.choice(np.arange(64), size=G, replace=False) / 16.0
        run_g = int(rng.integers(0, G))
        waiting = [g for g in range(G) if g != run_g]
        expect = protocol.credit_preempt(
            float(credit[waiting].min()), float(credit[run_g]), 1.0
        )
        fired.add(expect)

        # JAX backend: the running slot's slice is broken iff a strictly
        # lighter group waits — same rule, phrased as stickiness
        continuing = np.zeros(G, bool)
        continuing[run_g] = True
        view = jb.PolicyView(
            ent_group=jnp.arange(G, dtype=jnp.int32),
            group_vrt=jnp.zeros(G, jnp.float32),
            group_credit=jnp.asarray(credit, jnp.float32),
            last_pick_tick=jnp.zeros(G, jnp.float32),
            runnable=jnp.ones(G, bool),
            group_runnable=jnp.ones(G, bool),
            is_rt_group=jnp.zeros(G, bool),
            tick_sec=0.004, slice_ticks=25,
        )
        sticky = np.asarray(
            jb.sticky_mask(jb.LAGS, view, jnp.asarray(continuing))
        )
        assert bool(~sticky[run_g]) == expect

        # serving backend on the identical credit state
        tenants = {g: Tenant(g) for g in range(G)}
        for g in range(G):
            tenants[g].credit = float(credit[g])
        for g in waiting:
            tenants[g].queue.append(Request(g, g, 8, 4, 0.0))
        fire, victim = admission_policy("lags").preempt(
            tenants, {run_g}, 1.0
        )
        assert fire == expect
        if fire:
            assert victim == run_g
    assert fired == {True, False}  # both outcomes exercised


def test_preemption_boundary_equal_credits_never_fires():
    for h in (1.0, 0.5):
        tenants = {0: Tenant(0), 1: Tenant(1)}
        tenants[0].credit = 2.0
        tenants[1].credit = 2.0 * h  # wait == h * run exactly
        tenants[1].queue.append(Request(0, 1, 8, 4, 0.0))
        assert admission_policy("lags").preempt(tenants, {0}, h) == (False, -1)


# -- Pallas backend ---------------------------------------------------------


def test_pallas_tick_matches_numpy_reference():
    """The fused kernel agrees with the float64 oracle: identical pick
    order, allclose credit state, on 20 randomized cases."""
    rng = np.random.default_rng(7)
    for case in range(20):
        T = int(rng.integers(4, 33))
        # credits distinct on a 1/16 grid; one EMA step (window 256) moves
        # them < half the spacing, so f32 vs f64 cannot reorder the picks
        credit = rng.choice(np.arange(64), size=T, replace=False) / 16.0
        load = rng.integers(0, 17, T) / 16.0
        frac = rng.integers(0, 17, T) / 16.0
        runnable = rng.random(T) < 0.7
        k = int(rng.integers(1, 9))

        nl, nc, idx = pb.tick_and_pick(
            load, credit, frac, runnable, k, window=256
        )
        rl, rc, ridx = pb.numpy_reference(
            load, credit, frac, runnable, k, window=256
        )
        np.testing.assert_allclose(nl, rl, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(nc, rc, rtol=1e-5, atol=1e-6)
        assert idx.tolist() == ridx.tolist(), f"case {case}"


def test_engine_pallas_tick_matches_python_tick():
    """Engine state after one _pallas_tick == one python Tenant.tick loop."""
    from repro.serving.engine import Engine, EngineConfig

    rng = np.random.default_rng(3)
    n = 12
    loads = rng.random(n)
    creds = rng.random(n)
    served = {i: float(rng.random() * 0.01) for i in range(0, n, 2)}
    step_s = 0.012

    ta = {i: Tenant(i) for i in range(n)}
    tb = {i: Tenant(i) for i in range(n)}
    for i in range(n):
        ta[i].load_avg = tb[i].load_avg = float(loads[i])
        ta[i].credit = tb[i].credit = float(creds[i])
    ta[1].queue.append(Request(0, 1, 8, 4, 0.0))

    eng = Engine(
        EngineConfig(policy="lags", pallas_threshold=1, credit_window=256),
        ta,
    )
    eng._pallas_tick(served, step_s)
    for i in range(n):
        tb[i].tick(served.get(i, 0.0), step_s, 256)

    np.testing.assert_allclose(
        [ta[i].load_avg for i in range(n)],
        [tb[i].load_avg for i in range(n)], rtol=1e-5, atol=1e-6,
    )
    np.testing.assert_allclose(
        [ta[i].credit for i in range(n)],
        [tb[i].credit for i in range(n)], rtol=1e-5, atol=1e-6,
    )
    assert [ta[i].served_s for i in range(n)] == \
        [tb[i].served_s for i in range(n)]


def test_engine_pallas_path_completes_like_python_path():
    from repro.serving.engine import Engine, EngineConfig

    def run(threshold):
        tenants = {i: Tenant(i, weight_mb=32.0) for i in range(6)}
        eng = Engine(
            EngineConfig(policy="lags", pallas_threshold=threshold), tenants
        )
        reqs = [Request(i, i % 6, 64, 6, arrival=0.0) for i in range(12)]
        return eng.run(8.0, reqs)

    st_py = run(0)  # kernel path disabled
    st_pl = run(1)  # kernel path forced
    assert len(st_py.completed) == len(st_pl.completed) == 12
