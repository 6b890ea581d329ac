"""Engine host loop: decode steps per fused decode window over the window
(engine counters ``decode_steps`` / ``windows``)."""


def read(run):
    windows = run.counter_delta("windows")
    return run.counter_delta("decode_steps") / windows if windows else None
