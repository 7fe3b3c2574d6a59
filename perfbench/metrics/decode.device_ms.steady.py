"""Device time of the decode step program per decode, in the traced part of
the window."""
from perfbench import readers


def read(rec):
    return readers.decode_device_ms(rec)
