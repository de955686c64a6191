"""Set-up: from process start to the first timed fit (imports and TPU
start, data drawn on the device, one warm-up fit)."""


def read(run):
    return run.setup_s
