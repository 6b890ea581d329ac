"""Jitted programs: device time of the prefill programs (the trace's
programs whose name holds ``prefill_write``) in the traced part of the
window, per prompt position the engine prefilled there (its counter
``prefill_tokens``), in microseconds.  None for a program that keeps no
such counter, or a traced part with no prefill."""

PREFILL_PROGRAMS = ("prefill_write",)


def read(run):
    if run.trace is None or "prefill_tokens" not in run.served.counters_open:
        return None
    tokens = run.trace_delta("prefill_tokens")
    s = sum(t for name, t in run.trace["programs"].items()
            if any(p in name for p in PREFILL_PROGRAMS))
    if not tokens or not s:
        return None
    return s / tokens * 1e6
