"""The reduction of a kept trace by host span (``spanreduce``): on a
hand-made trace, on a profiled fleet call on the CPU, on the trace a traced
rehearsal of the fleet cell keeps; and on the small trace recorded on a TPU
v5e, where ``tracereduce`` and every registered reader read as they did
before the program had spans."""
import hashlib
import json
import time

import pytest

from perfbench import core, fleet_cell
from perfbench import spanreduce as sr
from perfbench import tracereduce as tr
from perfbench.tests.helpers import small_cell
from perfbench.tests.test_bench_trace import DEV, HOST, recorded

MS = 1e6


def fleet_trace():
    ev = [
        (HOST, "python", "bench.traced", 0.0, 10 * MS),
        (HOST, "python", "fleet.call", 0.5 * MS, 9 * MS),
        (HOST, "python", "fleet.workload", 0.6 * MS, 0.1 * MS),
        (HOST, "python", "fleet.workload", 0.8 * MS, 0.1 * MS),
        (HOST, "python", "fleet.stage", 1 * MS, 1 * MS),
        (HOST, "python", "bench._pad_trace", 1.2 * MS, 0.6 * MS),
        (HOST, "python", "fleet.scan", 2 * MS, 6 * MS),
        (HOST, "python", "fleet.dispatch", 2 * MS, 1 * MS),
        (HOST, "python", "fleet.readback", 8 * MS, 1 * MS),
        (HOST, "python", "other", 0.0, 10 * MS),  # neither bench. nor fleet.
        (DEV, tr.OPS_LINE, "stack", 1.5 * MS, 0.1 * MS),
        (DEV, tr.OPS_LINE, "while.6", 2.5 * MS, 5 * MS),
        (DEV, tr.OPS_LINE, "slice", 8.2 * MS, 0.2 * MS),
    ]
    args = {
        ("fleet.call", 0.5 * MS): {"nodes": 2, "backend": "jax"},
        ("fleet.workload", 0.6 * MS): {"fns": 3, "requests": 4},
        ("fleet.workload", 0.8 * MS): {"fns": 2, "requests": 3},
        ("fleet.stage", 1 * MS): {"requests": 7, "h2d_bytes": 100},
        ("fleet.scan", 2 * MS): {"nodes": 2, "ticks": 100},
    }
    return ev, args


def test_spans_on_a_hand_made_trace():
    ev, args = fleet_trace()
    sp = sr.spans(ev, tr.span_window(ev, "bench.traced"), args)
    assert "other" not in sp
    got = {n: {k: v for k, v in s.items() if k != "args"}
           for n, s in sp.items()}
    ms = 1e-3
    want = {  # count, total, self, device busy inside, idle given to it
        "bench.traced": (1, 10, 1.0, 5.3, 1.0),
        "fleet.call": (1, 9, 0.8, 5.3, 0.8),
        "fleet.workload": (2, 0.2, 0.2, 0.0, 0.2),
        # the gap in [1, 2) goes to fleet.stage, not the wrapper inside it
        "fleet.stage": (1, 1, 0.4, 0.1, 0.9),
        "bench._pad_trace": (1, 0.6, 0.6, 0.1, 0.0),
        "fleet.scan": (1, 6, 5.0, 5.0, 0.5),
        "fleet.dispatch": (1, 1, 1.0, 0.5, 0.5),
        "fleet.readback": (1, 1, 1.0, 0.2, 0.8),
    }
    keys = ("count", "total_s", "self_s", "busy_s", "idle_s")
    assert set(got) == set(want)
    for name, row in want.items():
        for k, v in zip(keys, row):
            assert got[name][k] == pytest.approx(
                v if k == "count" else v * ms, abs=1e-12), (name, k)
    # self time and idle each add up to the window
    assert sum(s["self_s"] for s in sp.values()) == pytest.approx(10 * ms)
    red = tr.reduce(ev, tr.span_window(ev, "bench.traced"))
    idle = red["window_s"] - red["busy_s"]
    assert sum(s["idle_s"] for s in sp.values()) == pytest.approx(idle)
    fleet_idle = sum(s["idle_s"] for n, s in sp.items()
                     if n.startswith("fleet."))
    assert fleet_idle == pytest.approx(3.7 * ms)
    # numeric args are summed, others left out
    assert sp["fleet.call"]["args"] == {"nodes": 2}
    assert sp["fleet.workload"]["args"] == {"fns": 5, "requests": 7}
    assert sp["fleet.scan"]["args"] == {"nodes": 2, "ticks": 100}
    assert sp["bench.traced"]["args"] == {}


def test_spans_clip_to_the_window_and_average_devices():
    ev, args = fleet_trace()
    ev = ev + [(DEV.replace("0", "1"), tr.OPS_LINE, "while.6", 2 * MS,
                6 * MS)]
    sp = sr.spans(ev, (0.0, 5 * MS), args)
    assert sp["fleet.scan"]["total_s"] == pytest.approx(3e-3)
    assert sp["fleet.scan"]["count"] == 1
    assert "fleet.readback" not in sp
    # busy in [2, 5): 2.5 ms on device 0, 3 ms on device 1
    assert sp["fleet.scan"]["busy_s"] == pytest.approx(2.75e-3)


def test_no_device_plane_gives_host_times_only():
    ev, args = fleet_trace()
    ev = [e for e in ev if e[0] == HOST]
    sp = sr.spans(ev, tr.span_window(ev, "bench.traced"), args)
    assert sp["fleet.scan"]["total_s"] == pytest.approx(6e-3)
    assert all(s["busy_s"] == 0 and s["idle_s"] == 0 for s in sp.values())


def test_fleet_readers_on_a_hand_made_trace():
    ev, args = fleet_trace()
    sp = sr.spans(ev, tr.span_window(ev, "bench.traced"), args)
    got = sr.per_call(sp)
    assert got["tick_us"] == pytest.approx(5e-3 / 100 * 1e6)
    assert got["prep_s"] == pytest.approx(1.2e-3)
    assert got["dispatch_ms"] == pytest.approx(1.0)
    assert got["readback_s"] == pytest.approx(1e-3)
    # a program without the spans: nothing to read
    assert sr.per_call({n: s for n, s in sp.items()
                        if n.startswith("bench.")}) is None
    # no device plane: no device time per tick
    host = [e for e in ev if e[0] == HOST]
    got = sr.per_call(sr.spans(host, tr.span_window(host, "bench.traced"),
                               args))
    assert got["tick_us"] is None and got["prep_s"] == pytest.approx(1.2e-3)


def test_spans_of_a_profiled_fleet_call(tmp_path):
    """A real profiler file: the program's spans and their args, on the
    CPU."""
    import jax

    from repro.fleet import place, simulate_fleet

    asg = place("round-robin", 11, 2)
    simulate_fleet("lags", asg, duration_s=3.0, backend="jax")  # compile
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.traced"):
        fr = simulate_fleet("lags", asg, duration_s=3.0, backend="jax")
    jax.profiler.stop_trace()
    path = tr.find_xplane(tmp_path)
    # tracereduce keeps only the benchmark's spans (a traced rehearsal run
    # earlier in this process leaves its bench.* wrappers installed)
    assert all(n.startswith("bench.")
               for p, _, n, _, _ in tr.events_from_xplane(path)
               if not tr.is_device_plane(p))
    prog, args = sr.program_spans(path)
    ev = tr.events_from_xplane(path) + prog
    sp = sr.spans(ev, tr.span_window(ev, "bench.traced"), args)
    assert {"fleet.call", "fleet.workload", "fleet.slot_trace",
            "fleet.stage", "fleet.scan", "fleet.dispatch",
            "fleet.readback"} == {n for n in sp if n.startswith("fleet.")}
    assert "bench.traced" in sp
    assert sp["fleet.stage"]["args"]["requests"] == fr.n_arrived
    assert sp["fleet.scan"]["args"]["ticks"] == int(3.0 / 0.004)
    assert sp["fleet.workload"]["count"] == 2
    assert sp["fleet.call"]["total_s"] <= sp["bench.traced"]["total_s"]
    kept = sr.reduce_kept(tmp_path)
    assert kept["spans"] == sp
    assert kept["per_call"] == sr.per_call(sp)


def test_traced_rehearsal_reads_the_span_metrics(tmp_path, monkeypatch,
                                                 capsys):
    monkeypatch.setattr(core, "TRACE_DIR", tmp_path)
    cell = small_cell("fig7-fleet-scan", seconds=1.0, trace=True)
    cell.keep_trace = True
    out = fleet_cell.run(cell, time.perf_counter())
    m = out["metrics"]
    # the registered metrics read as before, and no span metric is among them
    assert set(m) == {"fleet.build_s"} and m["fleet.build_s"]["value"] > 0
    sr.main([str(tmp_path / "fig7-fleet-scan")])
    kept = json.loads(capsys.readouterr().out)
    got = kept["per_call"]
    for name in ("prep_s", "dispatch_ms", "readback_s"):
        assert got[name] > 0, name
    # no device plane on the CPU: no device time per tick
    assert got["tick_us"] is None and kept["fleet_idle_share"] is None
    # the program's stages sit inside the benchmark's timing of them
    assert got["prep_s"] >= 0.9 * m["fleet.build_s"]["value"]


# the reduction of the recorded trace as it read before the program had
# spans: the earlier fields, and the readers that read only the trace
RECORDED = {
    "window_s": 0.07, "busy_s": 0.066165927, "devices": 1,
    "module_s": {"jit_step(15702542184158559372)": 0.066167296,
                 "jit_convert_element_type(15388027131515875373)": 5.95e-07},
    "module_runs": {"jit_step(15702542184158559372)": 2.0,
                    "jit_convert_element_type(15388027131515875373)": 1.0},
    "idle_gaps": [["bench.decode", 0.003558784],
                  ["bench.engine_step", 0.000275289]],
    "top_op": ["while.5", 0.065607068],
    "n_ops": 53, "op_s_sum": 0.131771836,
}
RECORDED_READS = {
    ("fleet", "scan.device_s"): 0.066165927,
    ("fleet", "device.idle_pct.fleet"): 5.477247142857156,
    ("engine", "device.idle_pct.steady"): 5.477247142857156,
    ("engine", "decode.device_ms.steady"): 33.083648000000004,
}
RECORDED_SHA = (
    "60ec167f91e9d2de3900d4455b25aa76f3a592f91a3911c4cca283ebaea283d6")


def test_recorded_v5e_trace_reads_as_before():
    ev = recorded()
    window = tr.span_window(ev, "bench.traced")
    red = tr.reduce(ev, window)
    for k in ("window_s", "busy_s", "devices", "module_s", "module_runs"):
        assert red[k] == RECORDED[k], k
    assert red["breakdown"]["idle_gaps"] == RECORDED["idle_gaps"]
    assert red["breakdown"]["device_ops"][0] == RECORDED["top_op"]
    assert len(red["op_s"]) == RECORDED["n_ops"]
    assert sum(red["op_s"].values()) == pytest.approx(
        RECORDED["op_s_sum"], rel=1e-15)
    digest = hashlib.sha256(
        json.dumps(red, sort_keys=True).encode()).hexdigest()
    assert digest == RECORDED_SHA
    for (kind, m), v in RECORDED_READS.items():
        assert core.reader(m)({"kind": kind, "trace": red}) == v, m
    sp = sr.spans(ev, window)
    assert sr.per_call(sp) is None
    # the recorded trace has only bench.* spans; all its idle time is theirs
    assert set(sp) == {n for _, _, n, _, _ in ev if n.startswith("bench.")}
    assert sum(s["idle_s"] for s in sp.values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
