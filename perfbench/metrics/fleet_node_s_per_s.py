"""Simulated node-seconds of all the window's fleet calls, over their wall
time."""


def read(rec):
    return rec["node_s"] / rec["window_s"] if rec["kind"] == "fleet" else None
