"""Engine host loop: share of decode slots that produced a token, over the
window: tokens / (decode steps x max_batch), from engine counters."""


def read(run):
    steps = run.counter_delta("decode_steps")
    if not steps:
        return None
    return run.counter_delta("tokens") / (steps * run.engine["max_batch"])
