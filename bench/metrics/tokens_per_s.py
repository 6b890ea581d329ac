"""Decode tokens streamed inside the window, divided by the window."""


def read(run):
    return run.tokens_in(run.t0, run.t1) / run.served.seconds
