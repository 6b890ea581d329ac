"""Jitted decode programs: their device time in the traced window (the
trace's programs whose name holds ``decode_window`` or ``fused_window``)
per decode step the engine counted in that window."""

DECODE_PROGRAMS = ("decode_window", "fused_window")


def decode_seconds(run):
    if run.trace is None:
        return None
    s = sum(t for name, t in run.trace["programs"].items()
            if any(p in name for p in DECODE_PROGRAMS))
    return s or None


def read(run):
    s = decode_seconds(run)
    steps = run.trace_delta("decode_steps") if s is not None else 0
    if s is None or not steps:
        return None
    return s / steps * 1e3
