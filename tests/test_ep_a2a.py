"""Sort-based EP dispatch vs the dense capacity-dispatch semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _dense_oracle(x, ids, gate, w_gate, w_up, w_down, capacity):
    """Straightforward per-(token,k) loop with per-expert capacity."""
    T, K = ids.shape
    E = w_gate.shape[0]
    used = np.zeros(E, int)
    y = np.zeros_like(np.asarray(x))
    total_cap = capacity  # single peer: shared buffer across experts
    placed = 0
    for t in range(T):
        for k in range(K):
            e = int(ids[t, k])
            if placed >= total_cap:
                continue
            placed += 1
            xe = np.asarray(x[t])
            g = xe @ np.asarray(w_gate[e])
            u = xe @ np.asarray(w_up[e])
            h = (g / (1 + np.exp(-g))) * u
            y[t] += float(gate[t, k]) * (h @ np.asarray(w_down[e]))
    return y


def test_local_matches_oracle():
    from repro.distributed.ep_a2a import moe_ep_a2a_local

    rng = np.random.default_rng(0)
    T, K, E, M, F = 16, 2, 4, 8, 16
    x = jnp.asarray(rng.standard_normal((T, M)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, E, (T, K)))
    gate = jnp.asarray(rng.uniform(0.1, 1.0, (T, K)), jnp.float32)
    w_gate = jnp.asarray(rng.standard_normal((E, M, F)) * 0.1, jnp.float32)
    w_up = jnp.asarray(rng.standard_normal((E, M, F)) * 0.1, jnp.float32)
    w_down = jnp.asarray(rng.standard_normal((E, F, M)) * 0.1, jnp.float32)

    cap = T * K  # no drops
    y = moe_ep_a2a_local(x, ids, gate, w_gate, w_up, w_down,
                         capacity_factor=float(cap) / (T * K))
    want = _dense_oracle(x, ids, gate, w_gate, w_up, w_down, cap)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-4)


def test_capacity_drops_are_bounded():
    from repro.distributed.ep_a2a import moe_ep_a2a_local

    rng = np.random.default_rng(1)
    T, K, E, M, F = 32, 2, 4, 8, 16
    x = jnp.asarray(rng.standard_normal((T, M)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, E, (T, K)))
    gate = jnp.ones((T, K), jnp.float32)
    w = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.1, jnp.float32)
    y_full = moe_ep_a2a_local(x, ids, gate, w(E, M, F), w(E, M, F),
                              w(E, F, M), capacity_factor=1.0)
    assert np.isfinite(np.asarray(y_full)).all()


def test_shard_map_single_device():
    """all_to_all path under shard_map on a 1-device 'model' axis equals the
    local path (exercises the collective wiring)."""
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from repro.distributed.ep_a2a import moe_ep_a2a_local

    rng = np.random.default_rng(2)
    T, K, E, M, F = 8, 2, 4, 8, 8
    x = jnp.asarray(rng.standard_normal((T, M)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, E, (T, K)))
    gate = jnp.asarray(rng.uniform(0.1, 1.0, (T, K)), jnp.float32)
    w = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.1, jnp.float32)
    wg, wu, wd = w(E, M, F), w(E, M, F), w(E, F, M)

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("model",))
    fn = jax.shard_map(
        lambda *a: moe_ep_a2a_local(*a, axis_name="model", capacity_factor=2.0),
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P()),
        out_specs=P(),
        check_vma=False,
    )
    y_sm = fn(x, ids, gate, wg, wu, wd)
    y_local = moe_ep_a2a_local(x, ids, gate, wg, wu, wd, capacity_factor=2.0)
    np.testing.assert_allclose(np.asarray(y_sm), np.asarray(y_local),
                               rtol=1e-5, atol=1e-5)
