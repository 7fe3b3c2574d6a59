"""The trace reduction: busy time as the union of operations, time by
operation and module, idle gaps named by the host span, on a hand-made
trace and on a small trace recorded on a TPU v5e."""
import json
from pathlib import Path

import pytest

from perfbench import tracereduce as tr

DEV = "/device:TPU:0"
HOST = "/host:CPU"
DATA = Path(__file__).parent / "data"


def hand_trace():
    ms = 1e6
    return [
        (HOST, "python", "bench.traced", 0.0, 10 * ms),
        (HOST, "python", "bench.engine_step", 1 * ms, 4 * ms),
        (HOST, "python", "bench.decode", 2 * ms, 3 * ms),
        (HOST, "python", "bench.wait", 6 * ms, 3 * ms),
        (HOST, "python", "other", 0.0, 10 * ms),  # not a benchmark span
        (DEV, tr.MODULES_LINE, "jit_step(1)", 2 * ms, 2.5 * ms),
        (DEV, tr.OPS_LINE, "fusion.1", 2 * ms, 1 * ms),
        (DEV, tr.OPS_LINE, "fusion.2", 2.5 * ms, 1 * ms),  # overlaps
        (DEV, tr.OPS_LINE, "fusion.1", 4 * ms, 0.5 * ms),
        (DEV, tr.OPS_LINE, "copy", 9.5 * ms, 1 * ms),  # clipped at 10
    ]


def test_union_of_intervals():
    busy, gaps = tr.union_length([(1, 3), (2, 4), (6, 7), (8, 12)], 0, 10)
    assert busy == 3 + 1 + 2
    assert gaps == [(0, 1), (4, 6), (7, 8)]


def test_reduce_hand_trace():
    ms = 1e6
    ev = [e for e in hand_trace() if e[2] != "other"]
    red = tr.reduce(ev, tr.span_window(ev, "bench.traced"))
    assert red["window_s"] == pytest.approx(0.010)
    # busy: [2, 3.5) and [4, 4.5) and [9.5, 10)
    assert red["busy_s"] == pytest.approx(0.0025)
    assert red["op_s"]["fusion.1"] == pytest.approx(0.0015)
    assert red["op_s"]["copy"] == pytest.approx(0.0005)
    assert red["module_s"]["jit_step(1)"] == pytest.approx(0.0025)
    assert red["module_runs"]["jit_step(1)"] == 1
    gaps = dict(red["breakdown"]["idle_gaps"])
    # [0, 2): midpoint 1 ms starts bench.engine_step; [3.5, 4) inside
    # bench.decode; [4.5, 9.5) midpoint 7 ms in bench.wait
    assert gaps["bench.engine_step"] == pytest.approx(0.002)
    assert gaps["bench.decode"] == pytest.approx(0.0005)
    assert gaps["bench.wait"] == pytest.approx(0.005)
    assert red["busy_s"] + sum(gaps.values()) == pytest.approx(0.010)
    assert red["breakdown"]["device_ops"][0][0] == "fusion.1"
    del ms


def test_no_device_plane_means_nothing_to_read():
    ev = [e for e in hand_trace() if e[0] == HOST]
    red = tr.reduce(ev, tr.span_window(ev, "bench.traced"))
    assert red["devices"] == 0 and red["busy_s"] == 0.0


def test_time_matching():
    assert tr.time_matching({"jit_step(3)": 1.0, "jit_tick(2)": 2.0,
                             "x": 4.0}, "jit_step", "tick") == 3.0


def recorded():
    d = json.loads((DATA / "trace_v5e_decode.json").read_text())
    return [tuple(e) for e in d["events"]]


def test_reduce_recorded_v5e_trace():
    """0.07 s of a decode-bound serving window on one v5e: the reduction
    against a brute-force count on a 1 us grid."""
    ev = recorded()
    lo, hi = tr.span_window(ev, "bench.traced")
    red = tr.reduce(ev, (lo, hi))
    assert red["devices"] == 1
    grid = bytearray(int((hi - lo) / 1000) + 1)
    for p, line, _, s, d in ev:
        if p == DEV and line == tr.OPS_LINE:
            a = max(int((s - lo) / 1000), 0)
            b = min(int((s + d - lo) / 1000), len(grid))
            grid[a:b] = b"\x01" * max(b - a, 0)
    assert red["busy_s"] == pytest.approx(sum(grid) * 1e-6, rel=0.01)
    # the decode program runs most of the window, one run per engine step
    decode_s = tr.time_matching(red["module_s"], "jit_step")
    assert 0.8 * red["window_s"] < decode_s <= red["window_s"]
    gaps = sum(v for _, v in red["breakdown"]["idle_gaps"])
    assert red["busy_s"] + gaps == pytest.approx(red["window_s"], rel=1e-6)
    names = [n for n, _ in red["breakdown"]["idle_gaps"]]
    assert all(n.startswith("bench.") for n in names)
    top = red["breakdown"]["device_ops"]
    assert [v for _, v in top] == sorted((v for _, v in top), reverse=True)


# each serving per-layer reader on the recorded trace with a fixed window
# of steps, at Qwen3-1.7B's sizes: the values the readers gave when they
# counted the dense decoder themselves (before the model's reference module
# gave the counts)
PINNED = {
    "engine.host_ms.steady": 7.250000000000006,
    "decode.device_ms.steady": 33.083648000000004,
    "decode_roofline": 13.388385671006983,
    "step.mfu_pct.steady": 0.0018274250797252912,
    "device.idle_pct.steady": 5.477247142857156,
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_serving_readers_on_the_recorded_trace(name):
    from perfbench import core, engine_cell

    ev = recorded()
    red = tr.reduce(ev, tr.span_window(ev, "bench.traced"))
    model = engine_cell.Model(core.load_cell("slice-48t-steady", 1, 51.0,
                                             True))
    steps = dict(step_wall_s=[0.070, 0.066, 0.064, 0.069, 0.071],
                 step_pos=[99, 100, -1, 101, 102],
                 step_rows=[16, 12, 16, 9, 16],
                 step_t=[19.93, 20.0, 20.05, 20.07, 28.0])
    rec = dict(steps, kind="engine", setup_s=12.5, window_s=51.0,
               ttft_s=[0.1, 0.2], gaps_s=[0.05], tokens=2,
               decode_wall_s=[0.062, 0.061, 0.0615, 0.0625], n_tenants=48,
               n_slots=16, kernel_tick=False, trace=red, chips=1,
               peaks=core.peaks("TPU v5 lite"),
               **engine_cell.model_counts(model, 16, steps["step_pos"],
                                          steps["step_t"], (20.0, 28.0)))
    assert core.reader(name)(rec) == pytest.approx(PINNED[name], rel=1e-12)
