"""Device: share of the traced time with at least one agent in flight in
which no operation ran on the device."""


def read(run):
    if run.trace is None or not run.trace["inflight_s"]:
        return None
    return run.trace["idle_inflight_s"] / run.trace["inflight_s"]
