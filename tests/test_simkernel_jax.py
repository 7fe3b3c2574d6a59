"""JAX tick simulator: agreement with the numpy engine + vmap over nodes,
and the dense (gather- and scatter-free) forms of its tick body."""
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import simkernel_jax as sj
from repro.core.policies import make_policy
from repro.core.simkernel import SimConfig, simulate
from repro.core.traces import make_workload
from repro.sched import jax_backend as jb

# completion ticks of one Fig 7 node (80 functions, 8 threads, 12 cores,
# LAGS, 20 s) from the scan as it was with gathers and scatters
PINNED_DONE_TICK = Path(__file__).parent / "data" / "simkernel_jax_done_tick.npz"


def _setup(n_fns=40, dur=15.0, seed=3, threads=8):
    wl = make_workload("azure2021", n_fns, duration_s=dur, seed=seed,
                       threads_per_fn=threads)
    trace = sj.build_slot_trace(wl, n_fns, threads)
    return wl, trace


def test_matches_numpy_engine():
    wl, trace = _setup()
    for name, code in (("cfs", sj.CFS), ("lags", sj.LAGS)):
        p = sj.SimParams(n_cores=12, n_fns=40, n_ticks=int(15.0 / sj.TICK),
                         policy=code)
        out = sj.simulate(trace, p)
        lat = sj.latencies_from(trace, out["done_tick"])
        wl2 = make_workload("azure2021", 40, duration_s=15.0, seed=3,
                            threads_per_fn=8)
        r = simulate(wl2, make_policy(name), SimConfig())
        # same completion count, comparable medians and overhead
        assert abs(len(lat) - r.n_completed) <= max(3, 0.05 * r.n_completed)
        assert abs(np.median(lat) - r.pct(50)) < 0.25 * max(r.pct(50), 0.05)
        ovh_jax = float(out["overhead_s"]) / (12 * 15.0)
        assert abs(ovh_jax - r.overhead_frac) < 0.05


def test_vmap_over_nodes():
    """Cluster-scale: many simulated nodes in one jit via vmap."""
    _, trace = _setup(n_fns=10, dur=5.0, threads=4)
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.stack([x, x]), trace
    )
    p = sj.SimParams(n_cores=4, n_fns=10, n_ticks=int(5.0 / sj.TICK))
    out = jax.vmap(lambda t: sj.simulate(t, p))(stacked)
    assert out["done_tick"].shape[0] == 2
    # identical traces -> identical results
    np.testing.assert_array_equal(
        np.asarray(out["done_tick"][0]), np.asarray(out["done_tick"][1])
    )


def test_jit_cache_and_grad_free():
    _, trace = _setup(n_fns=6, dur=2.0, threads=2)
    p = sj.SimParams(n_cores=2, n_fns=6, n_ticks=int(2.0 / sj.TICK))
    out1 = sj.simulate(trace, p)
    out2 = sj.simulate(trace, p)
    np.testing.assert_array_equal(np.asarray(out1["done_tick"]),
                                  np.asarray(out2["done_tick"]))


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_at_pointer_matches_take_along_axis(dtype):
    rng = np.random.default_rng(7)
    T, R = 96, 11
    x = jnp.asarray(rng.uniform(-1e3, 1e3, (T, R)), dtype)
    ptr = rng.integers(0, R + 1, T)
    ptr[:4] = R  # past the slot's last request
    sel = jnp.arange(R) == jnp.asarray(ptr)[:, None]
    got = np.asarray(sj.at_pointer(sel, x))
    want = np.asarray(jnp.take_along_axis(
        x, jnp.asarray(ptr)[:, None], axis=1, mode="clip"))[:, 0]
    inside = ptr < R
    np.testing.assert_array_equal(got[inside], want[inside])
    np.testing.assert_array_equal(got[~inside], 0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32, jnp.bool_])
def test_to_entities_matches_gather(dtype):
    rng = np.random.default_rng(11)
    G, T = 13, 120
    slot_fn = rng.integers(0, G, T).astype(np.int32)
    slot_fn[-16:] = 0  # padding slots
    if dtype == jnp.bool_:
        x = rng.random(G) < 0.5
    else:
        x = rng.uniform(-50.0, 50.0, G)
        if dtype == jnp.float32:
            x[3] = np.inf
    x = jnp.asarray(x, dtype)
    member = jb.group_member(jnp.asarray(slot_fn), G)
    got = jb.to_entities(member, x)
    assert got.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x)[slot_fn])


@pytest.mark.parametrize("n_pad", [0, 64])
def test_to_groups_matches_segment_sum(n_pad):
    rng = np.random.default_rng(5 + n_pad)
    G, tpf = 80, 8
    slot_fn = np.concatenate([np.repeat(np.arange(G), tpf),
                              np.zeros(n_pad, int)]).astype(np.int32)
    v = rng.uniform(0.0, 0.004, slot_fn.size).astype(np.float32)
    v[rng.random(slot_fn.size) < 0.5] = 0.0
    member = jb.group_member(jnp.asarray(slot_fn), G)
    got = np.asarray(jb.to_groups(member, jnp.asarray(v)))
    want = np.asarray(jax.ops.segment_sum(jnp.asarray(v), jnp.asarray(slot_fn),
                                          num_segments=G))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=0)


@pytest.mark.parametrize("seed", [3141592653, 3735928559])
def test_lags_done_tick_matches_pinned(seed):
    """The dense tick body completes every request on the same tick as the
    gather-based one did, at the Fig 7 node's shape."""
    wl = make_workload("azure2021", 80, duration_s=20.0, n_cores=12,
                       seed=seed, exec_s=0.14, threads_per_fn=8)
    trace = sj.build_slot_trace(wl, 80, 8)
    p = sj.SimParams(n_cores=12, n_fns=80, n_ticks=int(20.0 / sj.TICK),
                     policy=sj.LAGS, burst_us=280.0, depth=5.0)
    got = np.asarray(sj.simulate(trace, p)["done_tick"])
    want = np.load(PINNED_DONE_TICK)[f"seed_{seed}"]
    assert got.shape == want.shape
    moved = int((got != want).sum())
    assert moved == 0, (
        f"{moved} of {int((want >= 0).sum())} completion ticks moved")


def _loop_body(module: str) -> str:
    """StableHLO text of the one ``while`` body in ``module`` and of every
    function it calls."""
    funcs = dict(re.findall(r"^  func\.func \w+ @([\w$.-]+)\((.*?)^  }$",
                            module, re.M | re.S))
    lines = module.splitlines()
    loops = [i for i, line in enumerate(lines) if "stablehlo.while" in line]
    assert len(loops) == 1, f"{len(loops)} while loops"
    indent = lines[loops[0]][:len(lines[loops[0]]) - len(lines[loops[0]].lstrip())]
    start = lines.index(indent + "} do {", loops[0])
    end = lines.index(indent + "}", start)
    texts = ["\n".join(lines[start + 1:end])]
    seen = set()
    todo = re.findall(r"call @([\w$.-]+)", texts[0])
    while todo:
        name = todo.pop()
        if name not in seen:
            seen.add(name)
            texts.append(funcs[name])
            todo += re.findall(r"call @([\w$.-]+)", funcs[name])
    return "\n".join(texts)


@pytest.mark.parametrize("policy", sorted(jb.CODE_OF))
def test_tick_body_has_no_gather_or_scatter(policy):
    """The vmapped scan's tick body indexes nothing per element: no gather
    and no scatter, for every policy code (only ``top_k`` selects)."""
    p = sj.SimParams(n_cores=12, n_fns=80, n_ticks=100,
                     policy=jb.CODE_OF[policy],
                     rt_fns=(0, 3) if policy == "lags-static" else ())
    T, R = 640, 58
    trace = sj.SlotTrace(jax.ShapeDtypeStruct((10, T, R), jnp.int32),
                         jax.ShapeDtypeStruct((10, T, R), jnp.float32),
                         jax.ShapeDtypeStruct((10, T), jnp.int32))
    module = jax.jit(lambda t: jax.vmap(lambda x: sj.simulate(x, p))(t)
                     ).lower(trace).as_text()
    body = _loop_body(module)
    assert "top_k" in body
    for op in ("gather", "scatter"):
        n = body.count(f"stablehlo.{op}")
        assert n == 0, f"{n} {op} ops in the tick body"
