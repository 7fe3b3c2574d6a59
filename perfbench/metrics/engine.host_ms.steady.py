"""Mean host time of a batch step (Engine.step less its device decode), in
ms."""
from perfbench import readers


def read(rec):
    return readers.engine_host_ms(rec)
