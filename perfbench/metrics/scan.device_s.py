"""Device busy time of one fleet call (the vmapped scan is nearly all of
it), from the traced call."""
from perfbench import readers


def read(rec):
    return readers.scan_device_s(rec)
