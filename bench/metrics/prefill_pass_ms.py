"""Engine host loop: mean time of one prefill pass over the window, in
ms: the growth of the engine counter ``prefill_s`` (self time of the
``engine.prefill`` phase: host packing, dispatch and the wait for the
sampled tokens) over that of ``prefill_passes``.  Every running slot
waits that long for its next token.  None for a program that keeps no
such counters."""


def read(run):
    if "prefill_s" not in run.served.counters_open:
        return None
    passes = run.counter_delta("prefill_passes")
    if not passes:
        return None
    return run.counter_delta("prefill_s") / passes * 1e3
