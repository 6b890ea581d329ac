"""Engine host loop: share of the positions the prefill programs computed
that were padding, over the window: 1 - (growth of the engine counter
``prefill_tokens``, the prompt positions prefilled) / (growth of
``prefill_rows``, batch pad x bucket of every pass).  None for a program
that keeps no such counters, or a window with no prefill."""


def read(run):
    if "prefill_rows" not in run.served.counters_open:
        return None
    rows = run.counter_delta("prefill_rows")
    if not rows:
        return None
    return 1.0 - run.counter_delta("prefill_tokens") / rows
