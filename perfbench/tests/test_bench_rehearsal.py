"""Each cell's harness path for a few seconds on the CPU at a small size:
the last line parses and is correct; a batch step without a device decode
is counted as failed; the cache restart keeps every step decoding."""
import json
import time

import pytest

from perfbench import core, engine_cell
from perfbench.tests.helpers import run_cell, small_cell

WORKLOADS = ["slice-48t-steady", "fig7-fleet-scan", "slice-1024t-steady",
             "slice-48t-backlog"]


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_runs_and_its_line_parses(workload, trace, capsys):
    cell = small_cell(workload, seconds=1.5, trace=trace)
    core.emit(run_cell(cell))
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    want = {m["name"] for m in (cell.per_layer if trace
                                else cell.end_to_end)}
    # a CPU run names no device metric: only host-clock numbers appear
    assert set(out["metrics"]) <= want
    if not trace:
        assert "setup_s" in out["metrics"]
    assert out["device"]["platform"] == "cpu"
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}


def test_a_step_without_a_device_decode_fails_its_requests():
    r = engine_cell.Run(small_cell("slice-48t-steady"), time.perf_counter())
    calls = {"n": 0}

    def every_other(inner):
        def decode():
            calls["n"] += 1
            if calls["n"] % 2:
                inner()
        return decode

    r.driver.wrap_decode(every_other)
    w = r.window()
    assert w["failed"] > 0
    assert len(w["toks"]) < len(w["step_wall"])


def test_the_cache_restart_keeps_every_step_decoding():
    r = engine_cell.Run(small_cell("slice-48t-backlog"), time.perf_counter())
    w = r.window()
    st = r.eng.stats
    assert st.batch_steps > 3 * r.ec["max_len"]
    assert st.device_decodes == st.batch_steps
    assert w["failed"] == 0
    segs = engine_cell.token_segments(
        __import__("numpy").zeros((16, len(w["pos_of"])), "int32"),
        w["pos_of"])
    assert len(segs) > 3
    assert max(w["pos_of"]) == r.ec["max_len"] - 2
