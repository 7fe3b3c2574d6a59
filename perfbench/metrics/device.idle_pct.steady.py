"""Share of the traced window in which no operation ran on the device."""
from perfbench import readers


def read(rec):
    return readers.idle_pct(rec, "engine")
