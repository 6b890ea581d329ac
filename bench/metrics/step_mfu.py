"""Model step: model FLOPs of every prompt and decode token processed in
the traced window over the traced window's length times the bf16 peak, in
%.  Prompts count when their request was admitted inside the window."""

import numpy as np



def read(run):
    if run.trace is None:
        return None
    lo, hi = run.served.trace_window
    prompts, ctx = [], []
    for r in run.served.reqs.values():
        p = len(run.request_spec(r)[0])
        if lo <= r.admit < hi:
            prompts.append(p)
        st = np.asarray(r.stamps)
        ctx.append(p + np.flatnonzero((st >= lo) & (st < hi)))
    flops = (run.family.prefill_flops(run.dims, prompts) if prompts else 0.0)
    if ctx:
        flops += run.family.decode_flops(run.dims, np.concatenate(ctx))
    peak = run.trace["window_s"] * run.peak["bf16_flops_per_s"]
    return 100.0 * flops / peak
