"""Engine host loop: host time per decode window in which the serial loop
leaves the device nothing to run, in ms: the growth over the window of the
self times of admission, swaps, window preparation and token replay
(engine counters ``admit_s``, ``swap_s``, ``prep_s``, ``replay_s``, kept by
``repro.engine.trace``) over the growth of ``windows``.  None for a
program that keeps no such counters."""

HOST_PHASES = ("admit_s", "swap_s", "prep_s", "replay_s")


def read(run):
    if not all(k in run.served.counters_open for k in HOST_PHASES):
        return None
    windows = run.counter_delta("windows")
    if not windows:
        return None
    return sum(run.counter_delta(k) for k in HOST_PHASES) / windows * 1e3
