"""Cells at a size a CPU test can hold: a two-layer decoder of the served
family in bfloat16 with an 8-position cache, ten times the offered rate,
and a two-node fleet.

The serving cell is the registered ``slice-48t-steady``, with two variants
that drive harness paths it does not: 1024 tenants, so that the credit
tick runs as the Pallas kernel (whose float32 state error needs its own
limit), and a closed loop that keeps every slot busy, so that the dense
cache fills and restarts."""
import time

from perfbench import core

VARIANTS = {
    "slice-1024t-steady": dict(traffic={"tenants": 1024},
                               limits={"tick_state_err": 1e-5}),
    "slice-48t-backlog": dict(traffic={"kind": "closed_loop",
                                       "outstanding_per_tenant": 4,
                                       "requests_per_tenant": 64},
                              limits={}),
}

# the configuration file's keys: heads of the published width, and a width
# at which the tied head's logits spread enough for the float8 control to
# move the first token
SMALL_MODEL = dict(num_hidden_layers=2, hidden_size=512,
                   num_attention_heads=4, num_key_value_heads=2,
                   head_dim=128, intermediate_size=512, vocab_size=256)


def small_cell(workload: str, seconds: float = 2.0, seed: int = 2 ** 33 + 5,
               trace: bool = False) -> core.Cell:
    variant = VARIANTS.get(workload)
    cell = core.load_cell("slice-48t-steady" if variant else workload, seed,
                          seconds, trace)
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
