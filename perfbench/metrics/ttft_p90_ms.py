"""90th percentile of time to first token, from each request's due time on
the wall clock to the end of the first batch step that serves it; a request
due in the window with no token by the close counts its wait until the
close.  The engine prefills no prompt on the device (it prices prefill on
its cost model), so this is the wait for a slot and one decode step."""
from perfbench import readers


def read(rec):
    return (readers.percentile_ms(rec["ttft_s"], 90)
            if rec["kind"] == "engine" and rec["ttft_s"] else None)
