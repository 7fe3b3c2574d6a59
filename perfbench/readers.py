"""The arithmetic of the metric readers under ``metrics/``: each reader
file names the quantity it reports and calls one of these on the run's
record.  A function returns None where the record holds nothing to read
(a cell of another kind, an untraced run, a chip with no peaks entry).
A serving record carries the model's own counts (``engine_cell.Model``):
``decode_counts`` per traced decode and ``token_flops`` per batch step."""
from __future__ import annotations

import numpy as np

from perfbench import counts, tracereduce

# the compiled program in the device trace:
# serving.engine.decode_and_pick's jitted step
DECODE_MODULE = "jit_step"


def _engine(rec):
    return rec if rec.get("kind") == "engine" else None


def _traced(rec):
    """The trace's reduction, where it saw a device at work."""
    tr = rec.get("trace")
    return tr if tr and tr["devices"] > 0 else None


def percentile_ms(values, q):
    return float(np.percentile(values, q)) * 1e3 if len(values) else None


def engine_host_ms(rec):
    """Mean host time of a batch step: ``Engine.step``'s wall time less the
    device decode's (its ``decode_wall_s`` entry)."""
    if not _engine(rec) or not _traced(rec):
        return None
    walls = [w for w, p in zip(rec["step_wall_s"], rec["step_pos"]) if p >= 0]
    dec = rec["decode_wall_s"]
    if not walls or len(dec) != len(walls):
        return None
    return (sum(walls) - sum(dec)) / len(walls) * 1e3


def _module(tr, name):
    t = tracereduce.time_matching(tr["module_s"], name)
    n = sum(v for k, v in tr["module_runs"].items() if name in k)
    return t, n


def decode_device_ms(rec):
    tr = _traced(rec)
    if not _engine(rec) or not tr:
        return None
    t, n = _module(tr, DECODE_MODULE)
    return t / n * 1e3 if n > 0 else None


def decode_roofline_pct(rec):
    tr = _traced(rec)
    if not _engine(rec) or not tr or not rec.get("peaks"):
        return None
    t, n = _module(tr, DECODE_MODULE)
    if n <= 0 or not rec["decode_counts"]:
        return None
    need = np.mean([counts.roofline_s(f, b, rec["peaks"])
                    for f, b in rec["decode_counts"]])
    return 100.0 * need / (t / n)


def step_mfu_pct(rec):
    """Model operations of the tokens delivered in the window, over the
    window times the chips' peak."""
    if not _engine(rec) or not rec.get("peaks"):
        return None
    flops = sum(rows * f for rows, f in
                zip(rec["step_rows"], rec["token_flops"]))
    return 100.0 * flops / (rec["window_s"] * rec["chips"]
                            * rec["peaks"]["bf16_flops_per_s"])


def idle_pct(rec, kind):
    tr = _traced(rec)
    if rec.get("kind") != kind or not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def scan_device_s(rec):
    """Device busy time of the traced fleet call: the vmapped scan is
    nearly all of it (a module-line event can be missing from a trace this
    long, so the union of operations is read instead)."""
    tr = _traced(rec)
    if rec.get("kind") != "fleet" or not tr:
        return None
    return tr["busy_s"]
