"""Set-up: process start to the first timed step or request (TPU start,
weights made on the device, compile or compile cache, warm-up)."""


def read(run):
    return run.setup_s
