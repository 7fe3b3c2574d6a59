"""Share of the traced fleet call in which no operation ran on the device."""
from perfbench import readers


def read(rec):
    return readers.idle_pct(rec, "fleet")
