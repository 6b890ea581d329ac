"""Scheduler: 90th percentile over requests ready inside the window of the
time from ready to admission (the ``on_admit`` hook, which fires after the
admitting prefill pass), less any time the tracer stalled the host."""

from stats import percentile


def read(run):
    return percentile([run.unpaused(r.ready, r.admit)
                       for r in run.window_requests()], 90)
