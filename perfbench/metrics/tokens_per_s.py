"""Tokens delivered to requests in the window, over the window's wall time."""


def read(rec):
    return rec["tokens"] / rec["window_s"] if rec["kind"] == "engine" else None
