"""repro.obs: histogram quantiles, trace export, schedstats conservation."""
import json
import math
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import repro.obs as obs
from repro.obs import recorder, report, tracing
from repro.obs.metrics import Histogram, Registry
from repro.obs.schedstats import SchedStats, from_sim_result

SRC = Path(__file__).resolve().parents[1] / "src"


@pytest.fixture(autouse=True)
def _obs_reset():
    """Keep the process-wide switch/registry/tracer clean per test."""
    obs.disable()
    obs.registry().reset()
    obs.uninstall_tracer()
    yield
    obs.disable()
    obs.registry().reset()
    obs.uninstall_tracer()


# -- histograms -----------------------------------------------------------
def test_histogram_quantiles_vs_numpy():
    """Log-bucketed quantiles stay within a bucket (~5 %) of numpy's."""
    rng = np.random.default_rng(0)
    for sample in (
        rng.lognormal(-2.0, 1.0, 20000),
        rng.exponential(0.3, 20000),
        rng.uniform(1e-4, 10.0, 20000),
    ):
        h = Histogram("t")
        h.record_many(sample)
        for q in (25, 50, 90, 95, 99):
            got, want = h.pct(q), float(np.percentile(sample, q))
            assert abs(got - want) / want < 0.06, (q, got, want)
        assert h.count == len(sample)
        assert abs(h.sum - sample.sum()) / sample.sum() < 1e-9
        assert h.min == sample.min() and h.max == sample.max()


def test_histogram_scalar_matches_vectorised():
    rng = np.random.default_rng(1)
    xs = rng.lognormal(0.0, 2.0, 500)
    h1, h2 = Histogram("a"), Histogram("b")
    h1.record_many(xs)
    for x in xs:
        h2.record(float(x))
    assert h1.buckets == h2.buckets
    assert h1.count == h2.count


def test_histogram_roundtrip_and_merge():
    rng = np.random.default_rng(2)
    a, b = Histogram("a"), Histogram("b")
    a.record_many(rng.exponential(1.0, 1000))
    b.record_many(rng.exponential(2.0, 1000))
    back = Histogram.from_dict(json.loads(json.dumps(a.to_dict())))
    assert back.pct(95) == a.pct(95) and back.count == a.count
    both = Histogram("m").merge(a).merge(b)
    assert both.count == 2000
    assert a.pct(50) <= both.pct(50) <= b.pct(50) + 1e-9


def test_histogram_zero_and_negative_bucket():
    h = Histogram("z")
    for x in (0.0, -1.0, 0.5, 2.0):
        h.record(x)
    assert h.zero == 2 and h.count == 4
    assert h.pct(99) <= 2.0


# -- metrics registry / disabled path ------------------------------------
def test_registry_helpers_gate_on_enabled():
    c = obs.counter("x")  # disabled -> shared null
    c.inc(5)
    assert "x" not in obs.registry().snapshot()
    obs.enable()
    obs.counter("x").inc(5)
    obs.histogram("h").record(1.0)
    snap = obs.registry().snapshot()
    assert snap["x"]["value"] == 5 and snap["h"]["count"] == 1


def test_registry_type_conflict():
    r = Registry()
    r.counter("m")
    with pytest.raises(TypeError):
        r.histogram("m")


# -- tracing --------------------------------------------------------------
def test_trace_export_roundtrip(tmp_path):
    tr = tracing.Tracer(capacity=1024)
    with tr.span("outer", cat="test", k=1):
        with tr.span("inner"):
            pass
    tr.emit("marker", "obs", 5.0, 0.0, ph="i")
    tr.emit("runq", "counter", 6.0, 0.0, {"depth": 3}, ph="C")
    path = tmp_path / "trace.json"
    tr.export(str(path))
    obj = json.loads(path.read_text())
    evs = obj["traceEvents"]
    assert len(evs) == 4
    for e in evs:
        assert {"name", "cat", "ph", "ts", "pid", "tid"} <= set(e)
        assert e["ts"] >= 0.0
        if e["ph"] == "X":
            assert e["dur"] >= 0.0
    # export is ts-sorted: monotonic non-decreasing timeline
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)
    # nesting: outer event spans inner completely
    by_name = {e["name"]: e for e in evs}
    o, i = by_name["outer"], by_name["inner"]
    assert o["ts"] <= i["ts"]
    assert o["ts"] + o["dur"] >= i["ts"] + i["dur"]
    assert o["args"] == {"k": 1}


def test_tracer_ring_is_bounded():
    tr = tracing.Tracer(capacity=16)
    for k in range(100):
        tr.emit(f"e{k}", "obs", float(k), 0.0, ph="i")
    assert len(tr) == 16
    assert tr.dropped == 84
    names = [e["name"] for e in tr.events()]
    assert names[-1] == "e99"  # newest kept, oldest dropped


def test_module_span_noop_when_disabled():
    with tracing.span("nothing") as sp:
        pass
    assert sp.tracer is None
    obs.install_tracer(capacity=8)
    with tracing.span("real"):
        pass
    assert len(obs.tracer()) == 1


@pytest.mark.parametrize("ring", [False, True])
def test_span_writes_into_the_profiler_trace(profiled, ring):
    """The span reaches the profiler's host plane with its args as stats,
    with or without a ring tracer; the ring records it as before."""
    pytest.importorskip("jax")
    if ring:
        obs.install_tracer()

    def work():
        with obs.span("x", k=3) as sp:
            sp.set_args(n=5)

    _, spans = profiled(work)
    (ev,) = [s for s in spans if s[0] == "x"]
    assert ev[3] == {"k": 3, "n": 5}
    if ring:
        (rec,) = obs.tracer().events()
        assert rec["name"] == "x" and rec["args"] == {"k": 3, "n": 5}
    else:
        assert obs.tracer() is None


def test_import_obs_does_not_import_jax():
    code = "import sys, repro.obs; print('jax' in sys.modules)"
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True,
                         env=dict(os.environ, PYTHONPATH=str(SRC)))
    assert out.stdout.strip() == "False"


def test_fenced_span_measures_jax_work():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    obs.install_tracer()
    with tracing.fenced_span("matmul") as sp:
        x = jnp.ones((64, 64))
        sp(x @ x)
    assert sp.dur_s > 0.0
    assert [e["name"] for e in obs.tracer().events()] == ["matmul"]


# -- schedstats ------------------------------------------------------------
def test_engine_conservation_invariant():
    """Every accounted engine second is useful, switch, or idle — the
    schedstat identity the paper's measurement model rests on."""
    from repro.scheduler.tenant import Request, Tenant
    from repro.serving.engine import Engine, EngineConfig

    tenants = {i: Tenant(i, weight_mb=64.0) for i in range(6)}
    eng = Engine(EngineConfig(policy="lags", max_resident=3), tenants)
    reqs = [Request(i, i % 6, 64, 12, arrival=0.02 * i) for i in range(30)]
    st = eng.run(20.0, reqs)
    sched = st.sched
    assert len(st.completed) == 30
    assert sched.conservation_error() < 1e-9 * max(sched.time_s, 1.0)
    # per-entity breakdown sums to totals
    assert abs(sum(e.useful_s for e in sched.entities.values())
               - sched.useful_s) < 1e-9
    assert abs(sum(e.switch_s for e in sched.entities.values())
               - sched.switch_s) < 1e-9
    # latency histogram saw every completion
    assert sched.latency.count == 30
    # compat views stay aligned with the schedstats
    assert st.useful_s == sched.useful_s
    assert st.membership_changes == int(sched.switches)


def test_simkernel_schedstats_match_result():
    from repro.core.policies import make_policy
    from repro.core.simkernel import SimConfig, Workload, simulate

    rng = np.random.default_rng(3)
    arr = [np.sort(rng.uniform(0, 10.0, 40)) for _ in range(4)]
    svc = [np.full(40, 0.05) for _ in range(4)]
    wl = Workload(4, arr, svc, threads_per_fn=4, duration_s=10.0)
    obs.enable()
    r = simulate(wl, make_policy("cfs"), SimConfig(n_cores=2))
    s = r.schedstats
    assert s is not None
    assert s.switches == float(r.switches)
    assert abs(s.switch_s - r.switch_time_s) < 1e-12
    assert abs(s.useful_s - r.busy_time_s) < 1e-12
    assert abs(sum(e.useful_s for e in s.entities.values())
               - r.busy_time_s) < 1e-9
    assert s.latency.count == r.n_completed
    assert sum(e.arrived for e in s.entities.values()) == r.n_arrived
    # capacity identity: useful + switch + idle == cores * duration
    assert abs(s.useful_s + s.switch_s + s.idle_s - s.capacity_s) < 1e-9
    # disabled -> no schedstats, result otherwise identical
    obs.disable()
    r2 = simulate(wl, make_policy("cfs"), SimConfig(n_cores=2))
    assert r2.schedstats is None
    assert r2.switches == r.switches
    summary = r2.sched_summary()
    assert summary.switches == float(r2.switches)


def test_des_schedstats():
    from repro.core.des import EventSim
    from repro.core.policies import make_policy

    sim = EventSim(n_fns=2, n_cores=1, policy=make_policy("cfs"))
    sim.submit(0, 0.0, 0.2)
    sim.submit(1, 0.0, 0.2)
    lat = sim.run(until=2.0)
    assert len(lat) == 2
    assert sim.sched.latency.count == 2
    assert abs(sim.sched.useful_s - 0.4) < 1e-9
    assert sim.switches == int(sim.sched.switches)
    assert sim.sched.run_delay.count == 2  # both requests got first-run delay


# -- recorder + report -----------------------------------------------------
def _mini_run(policy: str, switch_s: float) -> SchedStats:
    s = SchedStats(policy)
    s.account_time(10.0)
    s.account_useful(0, 10.0 - switch_s)
    s.account_switch(0, switch_s, n=5)
    for i in range(20):
        s.account_completion(0, 0.1 + 0.01 * i)
    return s


def test_record_load_and_diff(tmp_path):
    pa = recorder.record_run(
        str(tmp_path / "fair"), {"policy": "fair"}, sched=_mini_run("fair", 2.0)
    )
    recorder.record_run(
        str(tmp_path / "lags"), {"policy": "lags"}, sched=_mini_run("lags", 1.0)
    )
    run_a = recorder.load_run(str(tmp_path / "fair"))
    run_b = recorder.load_run(str(tmp_path / "lags"))
    assert run_a["sched"].switch_share == pytest.approx(0.2)
    text = report.diff(run_a, run_b)
    assert "switch_share" in text and "p99_latency" in text
    assert "lower switch-time share: lags" in text
    # CLI entry point over the same dirs
    out = report.main([str(tmp_path / "fair"), str(tmp_path / "lags")])
    assert "fair" in out and "lags" in out
    out = report.main(
        ["--diff", str(tmp_path / "fair"), str(tmp_path / "lags")]
    )
    assert "lower switch-time share: lags" in out


def test_record_run_includes_trace(tmp_path):
    obs.install_tracer()
    with tracing.span("ev"):
        pass
    recorder.record_run(str(tmp_path), {"policy": "x"})
    obj = json.loads((tmp_path / "trace.json").read_text())
    assert [e["name"] for e in obj["traceEvents"]] == ["ev"]
    run = recorder.load_run(str(tmp_path))
    assert run["trace_file"] == "trace.json"


def test_serve_cli_obs_smoke(tmp_path):
    """launch/serve.py end-to-end with telemetry + report diff."""
    from repro.launch import serve

    for pol in ("fair", "lags"):
        serve.main([
            "--policy", pol, "--tenants", "8", "--duration", "2",
            "--obs-dir", str(tmp_path / pol), "--trace",
        ])
        obs.disable()
        obs.uninstall_tracer()
    out = report.main(
        ["--diff", str(tmp_path / "fair"), str(tmp_path / "lags")]
    )
    assert "switch_share" in out and "p99_latency" in out
    trace = json.loads((tmp_path / "lags" / "trace.json").read_text())
    assert trace["traceEvents"], "trace should contain engine.step events"
    ts = [e["ts"] for e in trace["traceEvents"]]
    assert ts == sorted(ts) and all(t >= 0 for t in ts)
