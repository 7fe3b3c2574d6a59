"""90th percentile of time to first token, from each request's due time on
the wall clock; a request due in the window with no token by the close
counts its wait until the close."""
from perfbench import readers


def read(rec):
    return (readers.percentile_ms(rec["ttft_s"], 90)
            if rec["kind"] == "engine" and rec["ttft_s"] else None)
