"""Set-up seconds: process start to the window's first step, compile,
weights and warm-up included."""


def read(rec):
    return rec["setup_s"]
