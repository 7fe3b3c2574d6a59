"""Cells at a size a CPU test can hold: a two-layer decoder of the served
family in bfloat16 with an 8-position cache, ten times the offered rate,
and a two-node fleet.

The serving cell is built from its entries in
``data/slice-48t-steady.json`` (ready, not yet registered: PERF.md, Open
questions), with two variants that drive harness paths it does not: 1024
tenants, so that the credit tick runs as the Pallas kernel (whose float32
state error needs its own limit), and a closed loop that keeps every slot
busy, so that the dense cache fills and restarts."""
import time
from pathlib import Path

from perfbench import core

VARIANTS = {
    "slice-1024t-steady": dict(traffic={"tenants": 1024},
                               limits={"tick_state_err": 1e-5}),
    "slice-48t-backlog": dict(traffic={"kind": "closed_loop",
                                       "outstanding_per_tenant": 4,
                                       "requests_per_tenant": 64},
                              limits={}),
}

# heads of the published width, and a width at which the tied head's
# logits spread enough for the float8 control to move the first token
SMALL_MODEL = dict(n_layers=2, d_model=512, n_heads=4, n_kv_heads=2,
                   head_dim=128, d_ff=512, vocab_size=256)


PREPARED = Path(__file__).parent / "data" / "slice-48t-steady.json"


def serving_cell(seed: int, seconds: float, trace: bool) -> core.Cell:
    prep = core.load_json(PREPARED)
    bench = core.load_json(core.ROOT / "BENCHMARK.json")
    bench = dict(bench, configs=bench["configs"] + [prep["config"]],
                 end_to_end=bench["end_to_end"] + prep["end_to_end"],
                 per_layer=bench["per_layer"] + prep["per_layer"])
    return core.make_cell(prep["workload"], bench, seed, seconds, trace)


def small_cell(workload: str, seconds: float = 2.0, seed: int = 2 ** 33 + 5,
               trace: bool = False) -> core.Cell:
    variant = VARIANTS.get(workload)
    if variant or workload == "slice-48t-steady":
        cell = serving_cell(seed, seconds, trace)
    else:
        cell = core.load_cell(workload, seed, seconds, trace)
    if variant:
        cell.workload = workload
        cell.traffic = dict(cell.traffic, **variant["traffic"])
        cell.limits = dict(cell.limits, **variant["limits"])
    if cell.config["system"] == "engine":
        cell.overrides = {"model": dict(SMALL_MODEL),
                          "engine": {"max_len": 8}}
        if cell.traffic["kind"] == "open_loop":
            cell.traffic = dict(cell.traffic,
                                rate_per_s=cell.traffic["rate_per_s"] * 10)
    else:
        cell.overrides = {"fleet": dict(n_fns=40, n_nodes=2,
                                        duration_s=6.0)}
    return cell


def run_cell(cell: core.Cell) -> dict:
    if cell.config["system"] == "engine":
        from perfbench import engine_cell as runner
    else:
        from perfbench import fleet_cell as runner
    return runner.run(cell, time.perf_counter())
