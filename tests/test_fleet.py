"""Fleet layer: placement conservation, differential contracts, merged obs.

The load-bearing guarantees pinned here:

  * every placement strategy conserves the function count (the legacy
    ``simulate_node_share`` floor silently dropped up to ``n_nodes - 1``
    functions — the (800, 14) case is the regression that motivated the
    fleet layer);
  * a placement handing every node identical per-node shares reproduces
    the legacy representative-node numbers *exactly* (numpy and JAX);
  * the vmapped+padded JAX fleet path is bit-identical to per-node
    unpadded scans, and statistically agrees with the numpy tick engine;
  * fleet observability: ``SchedStats.merge`` totals add up, and
    ``repro.obs.report --merge`` renders one view from per-node records.
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.cluster import simulate_node_share
from repro.fleet import (
    PLACEMENTS,
    consolidation_sweep,
    fn_shares,
    make_policy,
    min_nodes_meeting_slo,
    place,
    placement_comparison,
    record_fleet,
    simulate_fleet,
    switch_penalty,
)
from repro.obs.schedstats import SchedStats, from_sim_result


# -- placement: conservation ------------------------------------------------

@pytest.mark.parametrize("name", sorted(PLACEMENTS))
def test_placement_conserves_function_count(name):
    asg = place(name, 23, 4, policy=make_policy("lags"))
    assert int(asg.counts.sum()) == 23
    seen = np.concatenate(asg.node_fns)
    assert len(np.unique(seen)) == 23


@pytest.mark.parametrize("name", sorted(PLACEMENTS))
def test_800_over_14_regression(name):
    """The legacy floor gave 14 * (800 // 14) = 798 functions; placements
    must assign all 800."""
    asg = place(name, 800, 14, policy=make_policy("lags"))
    assert int(asg.counts.sum()) == 800  # not 798
    # and the legacy path indeed drops them (documented approximation)
    assert 14 * max(1, 800 // 14) == 798


@settings(max_examples=15)
@given(
    total=st.integers(min_value=1, max_value=64),
    n_nodes=st.integers(min_value=1, max_value=7),
    name=st.sampled_from(sorted(PLACEMENTS)),
)
def test_placement_conservation_property(total, n_nodes, name):
    asg = place(name, total, n_nodes, policy=make_policy("cfs"))
    assert int(asg.counts.sum()) == total
    assert len(np.unique(np.concatenate(asg.node_fns))) == total


def test_unknown_placement_rejected():
    with pytest.raises(ValueError, match="unknown placement"):
        place("best-fit-ever", 10, 2)


def test_switch_aware_stacks_less_than_pack():
    """pack is the consolidation-friendly extreme; switch-aware trades some
    density away against the policy's voluntary-switch cost."""
    shares = fn_shares(120, seed=7)
    packed = place("pack", 120, 4, shares=shares)
    aware = place("switch-aware", 120, 4, shares=shares,
                  policy=make_policy("cfs"))
    assert aware.counts.max() < packed.counts.max()
    assert aware.share_imbalance() <= packed.share_imbalance() + 1e-9


def test_switch_penalty_monotone_and_policy_aware():
    """Denser cgroup stacking costs more, and CFS pays more than LAGS
    (run-to-completion handoffs are near-free) — the signal switch-aware
    placement keys on."""
    cfs, lags = make_policy("cfs"), make_policy("lags")
    sparse = switch_penalty(cfs, 8, util=0.8)
    dense = switch_penalty(cfs, 96, util=0.8)
    assert 0.0 <= sparse < dense < 1.0
    assert switch_penalty(lags, 96, util=0.8) < dense
    assert switch_penalty(cfs, 0, util=0.8) == 0.0


# -- differential: fleet vs legacy representative node ----------------------

def test_round_robin_fleet_matches_legacy_exactly():
    """Equal-count round-robin nodes regenerate the same band workload the
    legacy single-node path simulated: per-node results are identical."""
    legacy = simulate_node_share("lags", 24, 2, duration_s=8.0)
    asg = place("round-robin", 24, 2)
    fleet = simulate_fleet("lags", asg, duration_s=8.0)
    assert list(asg.counts) == [12, 12]
    for r in fleet.nodes:
        np.testing.assert_array_equal(r.latencies, legacy.latencies)
        assert r.switches == legacy.switches
        assert r.busy_time_s == legacy.busy_time_s
        assert r.switch_time_s == legacy.switch_time_s
    assert fleet.n_completed == 2 * legacy.n_completed


def test_pack_with_uniform_shares_matches_legacy():
    """Uniform shares + headroom=1.0 force pack into an even split, which
    must then reproduce the legacy numbers too (placement only acts through
    the per-node counts under the shared-seed band model)."""
    shares = np.full(24, 1.0 / 64.0)
    asg = place("pack", 24, 2, shares=shares, headroom=1.0)
    assert list(asg.counts) == [12, 12]
    fleet = simulate_fleet("cfs", asg, duration_s=8.0)
    legacy = simulate_node_share("cfs", 24, 2, duration_s=8.0)
    for r in fleet.nodes:
        np.testing.assert_array_equal(r.latencies, legacy.latencies)
        assert r.busy_time_s == legacy.busy_time_s


def test_equal_count_nodes_share_one_simulation():
    """Shared seed + equal counts -> the numpy path simulates once and
    reuses the result object (the banded-placement fast path)."""
    asg = place("round-robin", 36, 3)
    fleet = simulate_fleet("lags", asg, duration_s=5.0)
    assert fleet.nodes[0] is fleet.nodes[1] is fleet.nodes[2]
    distinct = simulate_fleet("lags", asg, duration_s=5.0,
                              distinct_seeds=True)
    assert distinct.nodes[0] is not distinct.nodes[1]
    assert not np.array_equal(distinct.nodes[0].latencies,
                              distinct.nodes[1].latencies)


def test_pack_idle_nodes_are_empty_results():
    """pack may drain tail nodes entirely; they must appear as explicit
    zero-work nodes, not crash the workload synthesiser."""
    shares = np.full(8, 0.05)
    asg = place("pack", 8, 4, shares=shares, headroom=4.0)
    assert 0 in asg.counts
    fleet = simulate_fleet("lags", asg, duration_s=4.0)
    assert fleet.n_nodes == 4
    for r, k in zip(fleet.nodes, asg.counts):
        if k == 0:
            assert r.n_arrived == 0 and r.busy_time_s == 0.0
    assert fleet.n_arrived == sum(
        r.n_arrived for r, k in zip(fleet.nodes, asg.counts) if k > 0
    )


# -- differential: vmapped JAX fleet ---------------------------------------

def test_jax_fleet_matches_per_node_scan_exactly():
    """Padding to the common (T, R) and vmapping must be bit-identical to
    running each node's unpadded scan alone."""
    jax = pytest.importorskip("jax")  # noqa: F841
    from repro.core import simkernel_jax as sj
    from repro.core.traces import make_workload
    from repro.sched.jax_backend import CODE_OF

    asg = place("round-robin", 11, 2)  # counts (6, 5): uneven, forces padding
    fleet = simulate_fleet("lags", asg, duration_s=5.0, backend="jax")
    assert list(asg.counts) == [6, 5]
    for node, k in zip(fleet.nodes, asg.counts):
        wl = make_workload("azure2021", int(k), duration_s=5.0, n_cores=12,
                           seed=7, exec_s=0.2, threads_per_fn=8)
        trace = sj.build_slot_trace(wl, int(k), 8)
        p = sj.SimParams(n_cores=12, n_fns=int(k),
                         n_ticks=int(5.0 / sj.TICK),
                         policy=CODE_OF["lags"], burst_us=280.0, depth=5.0)
        out = sj.simulate(trace, p)
        lat = sj.latencies_from(trace, out["done_tick"])
        np.testing.assert_array_equal(np.sort(node.latencies), np.sort(lat))
        assert abs(node.busy_time_s - float(out["busy_s"])) < 1e-6
        assert abs(node.switch_time_s - float(out["overhead_s"])) < 1e-6


FLEET_SPANS = ("fleet.call", "fleet.workload", "fleet.slot_trace",
               "fleet.stage", "fleet.scan", "fleet.dispatch",
               "fleet.readback")


def test_jax_fleet_spans_count_its_work(profiled):
    """A profiled 2-node JAX fleet: every span of the call is in the
    profiler's trace, nested as documented, and its counts agree with the
    result; the result is bit-identical to an unprofiled call."""
    pytest.importorskip("jax")
    from repro.core import simkernel_jax as sj

    asg = place("round-robin", 11, 2)
    kw = dict(duration_s=5.0, backend="jax")
    plain = simulate_fleet("lags", asg, **kw)
    fleet, spans = profiled(lambda: simulate_fleet("lags", asg, **kw))
    by = {}
    for name, s, d, args in spans:
        by.setdefault(name, []).append((s, s + d, args))
    assert {n: len(by.get(n, ())) for n in FLEET_SPANS} == {
        "fleet.call": 1, "fleet.workload": 2, "fleet.slot_trace": 2,
        "fleet.stage": 1, "fleet.scan": 1, "fleet.dispatch": 1,
        "fleet.readback": 1}
    (call,) = by["fleet.call"]
    assert call[2] == {"nodes": 2, "backend": "jax"}
    for n in FLEET_SPANS[1:]:
        assert all(call[0] <= s and e <= call[1] for s, e, _ in by[n])
    (scan,), (disp,) = by["fleet.scan"], by["fleet.dispatch"]
    assert scan[0] <= disp[0] and disp[1] <= scan[1]
    assert scan[2] == {"nodes": 2, "ticks": int(5.0 / sj.TICK)}
    (stage,) = by["fleet.stage"]
    st = stage[2]
    assert st["requests"] == fleet.n_arrived
    assert sum(a["requests"] for *_, a in by["fleet.workload"]) \
        == fleet.n_arrived
    assert [a["fns"] for *_, a in by["fleet.workload"]] == [6, 5]
    T, R = st["slots"], st["depth"]
    assert T == 6 * 8 and st["padded"] == 2 * T * R
    assert st["h2d_bytes"] == 2 * (T * R * 8 + T * 4)
    built = [a for *_, a in by["fleet.slot_trace"]]
    assert R == max(a["depth"] for a in built)
    assert st["d2h_bytes"] == sum(a["h2d_bytes"] for a in built) == sum(
        a["slots"] * (a["depth"] * 8 + 4) for a in built)
    (rb,) = by["fleet.readback"]
    assert rb[2]["d2h_bytes"] == 2 * (3 * T * R * 4 + T * 4 + 2 * 4)
    for a, b in zip(plain.nodes, fleet.nodes):
        assert a.latencies.tobytes() == b.latencies.tobytes()
        assert a.fn_of.tobytes() == b.fn_of.tobytes()
        assert (a.n_arrived, a.n_completed, a.busy_time_s,
                a.switch_time_s) == (b.n_arrived, b.n_completed,
                                     b.busy_time_s, b.switch_time_s)


def test_numpy_fleet_opens_the_call_span(profiled):
    pytest.importorskip("jax")
    asg = place("round-robin", 6, 2)
    _, spans = profiled(lambda: simulate_fleet("lags", asg, duration_s=2.0))
    names = [n for n, *_ in spans if n.startswith("fleet.")]
    assert names == ["fleet.call"]
    assert [a for n, *_, a in spans if n == "fleet.call"] == [
        {"nodes": 2, "backend": "numpy"}]


def test_jax_fleet_agrees_with_numpy_fleet():
    """Backend-differential (same tolerances as test_simkernel_jax): the
    scan fleet and the tick fleet see the same cluster."""
    pytest.importorskip("jax")
    asg = place("round-robin", 20, 2)
    ref = simulate_fleet("lags", asg, duration_s=10.0, threads_per_fn=8)
    jx = simulate_fleet("lags", asg, duration_s=10.0, backend="jax")
    assert abs(jx.n_completed - ref.n_completed) <= max(
        6, 0.05 * ref.n_completed)
    assert abs(jx.pct(50) - ref.pct(50)) < 0.25 * max(ref.pct(50), 0.05)
    assert abs(jx.overhead_frac - ref.overhead_frac) < 0.05


# -- consolidation search ---------------------------------------------------

def test_consolidation_sweep_reports_imbalance_fields():
    res = consolidation_sweep(
        total_fns=24, node_counts=(3, 2), policies=("lags",),
        duration_s=5.0,
    )
    assert len(res) == 2
    for r in res:
        assert r.placement == "round-robin"
        assert r.p95_spread >= 0.0
        assert r.ovh_max_over_mean >= 1.0 - 1e-9
    n = min_nodes_meeting_slo(res, "lags")
    assert n in (2, 3)


def test_placement_comparison_runs_all_strategies(tmp_path):
    res = placement_comparison(
        24, 2, policy="lags", duration_s=4.0,
        placements=("round-robin", "pack"),
        record_dir=str(tmp_path),
    )
    assert [r.placement for r in res] == ["round-robin", "pack"]
    assert (tmp_path / "round-robin" / "node0" / "run.json").exists()
    assert (tmp_path / "pack" / "node1" / "run.json").exists()


# -- fleet observability ----------------------------------------------------

def test_schedstats_merge_sums_totals_and_entities():
    a, b = SchedStats("node0"), SchedStats("node1")
    for stx, ent in ((a, 1), (b, 2)):
        stx.account_time(10.0)
        stx.account_useful(ent, 4.0)
        stx.account_switch(ent, 0.5, n=5)
        stx.account_completion(ent, 0.2)
        stx.account_completion(1, 0.4)
    m = SchedStats.merged([a, b], name="fleet")
    assert m.time_s == 20.0
    assert m.useful_s == 8.0
    assert m.switch_s == 1.0
    assert m.switches == 10
    assert m.latency.count == 4
    assert m.entities[1].completed == 3  # 2 from a, 1 from b
    assert m.entities[2].completed == 1
    assert m.entities[1].switches == 5
    # merge is additive on histograms, not averaging
    assert m.switch_cost_us.count == a.switch_cost_us.count * 2


def test_fleet_merged_sched_matches_sum_of_nodes():
    asg = place("round-robin", 18, 2)
    fleet = simulate_fleet("lags", asg, duration_s=5.0,
                           distinct_seeds=True)
    merged = fleet.merged_sched()
    assert merged.useful_s == pytest.approx(
        sum(r.busy_time_s for r in fleet.nodes))
    assert merged.switches == sum(r.switches for r in fleet.nodes)
    assert merged.latency.count == fleet.n_completed
    ref = SchedStats.merged([from_sim_result(r) for r in fleet.nodes])
    assert merged.switch_share == pytest.approx(ref.switch_share)


def test_report_merge_renders_fleet_view(tmp_path):
    from repro.obs import report

    asg = place("round-robin", 18, 2)
    fleet = simulate_fleet("lags", asg, duration_s=5.0,
                           distinct_seeds=True)
    paths = record_fleet(fleet, str(tmp_path))
    assert len(paths) == 2
    text = report.main(["--merge", str(tmp_path / "node0"),
                        str(tmp_path / "node1")])
    assert "fleet view: 2 run records merged" in text
    assert "policies: lags" in text
    assert "per-shard:" in text and "merged:" in text
    # merged completion count = fleet total
    assert f"{fleet.n_completed}" in text


def test_report_merge_requires_two_runs(tmp_path):
    from repro.obs import report

    with pytest.raises(SystemExit):
        report.main(["--merge", str(tmp_path)])


def test_imbalance_report_fields():
    asg = place("pack", 40, 3, policy=make_policy("lags"))
    fleet = simulate_fleet("lags", asg, duration_s=5.0)
    imb = fleet.imbalance()
    assert set(imb) == {"p95_min", "p95_max", "p95_spread",
                        "ovh_max_over_mean"}
    assert imb["p95_max"] >= imb["p95_min"]
    assert imb["p95_spread"] == pytest.approx(
        imb["p95_max"] - imb["p95_min"])


# -- live schedstats streaming ----------------------------------------------

def test_engine_run_fires_checkpoints():
    from repro.launch.serve import build_workload
    from repro.serving.engine import Engine, EngineConfig

    tenants, arrivals = build_workload(4, 2.0, seed=0)
    eng = Engine(EngineConfig(policy="lags", n_slots=4), tenants)
    snaps = []
    eng.run(2.0, arrivals, checkpoint_every_s=0.5,
            on_checkpoint=lambda stx: snaps.append(stx.time_s))
    assert len(snaps) >= 3
    assert snaps == sorted(snaps)
    # no checkpointing when the knob is off
    eng2 = Engine(EngineConfig(policy="lags", n_slots=4), tenants)
    missed = []
    eng2.run(1.0, arrivals, on_checkpoint=lambda stx: missed.append(1))
    assert missed == []


def test_serve_streams_checkpoints_and_shard_meta(tmp_path, capsys):
    from repro.launch import serve
    from repro.obs.recorder import load_run

    serve.main([
        "--policy", "lags", "--tenants", "4", "--duration", "2",
        "--obs-dir", str(tmp_path), "--checkpoint-every", "0.5",
        "--shard", "s0",
    ])
    run = load_run(str(tmp_path))
    assert run["meta"]["shard"] == "s0"
    assert run["meta"]["checkpoints"] >= 3
    assert "live" not in run["meta"]  # final record, not a checkpoint
    assert run["sched"] is not None
    assert "checkpoints=" in capsys.readouterr().out
