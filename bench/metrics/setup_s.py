"""Set-up time: process start to ready to serve (imports, weights and
cache on the device, warm-up of every program the traffic uses)."""


def read(run):
    return run.setup_s
