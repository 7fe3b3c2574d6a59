"""Share of the decode step's roofline time (bytes of weights, valid and
new cache entries; its operations) in its device time."""
from perfbench import readers


def read(rec):
    return readers.decode_roofline_pct(rec)
