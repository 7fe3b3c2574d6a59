"""Model operations of the tokens delivered, over the window times the
chip's bf16 peak."""
from perfbench import readers


def read(rec):
    return readers.step_mfu_pct(rec)
