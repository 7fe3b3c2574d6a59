"""99th percentile of the gaps between consecutive tokens of a request,
over every gap in the window."""
from perfbench import readers


def read(rec):
    return (readers.percentile_ms(rec["gaps_s"], 99)
            if rec["kind"] == "engine" else None)
