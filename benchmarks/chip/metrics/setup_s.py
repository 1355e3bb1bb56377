"""Set-up seconds (host clock): process start to the window's start —
imports, seeded weights on the device, engine, compiles or compile-cache
loads, warm-up and the traffic's ramp."""


def read(run):
    return run.setup_s
