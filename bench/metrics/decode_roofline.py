"""Model step: the decode programs' share of their roofline, in %.

The roofline time is the larger of the decode tokens' FLOPs over the bf16
peak and their bytes over the HBM bandwidth (the family's counts: the weights
once per decode step, and per token the keys and values of its live
context and its own new ones); the time is the decode programs' device
time in the traced window.  Tokens are those streamed inside the traced
window; the token fed at position p + j produced the request's j-th
streamed token."""

import numpy as np

from decode_step_ms import decode_seconds
from stats import roofline_seconds


def read(run):
    s = decode_seconds(run)
    if s is None or not run.trace_delta("decode_steps"):
        return None
    steps = run.trace_delta("decode_steps")
    lo, hi = run.served.trace_window
    ctx = []
    for r in run.served.reqs.values():
        st = np.asarray(r.stamps)
        j = np.flatnonzero((st >= lo) & (st < hi))
        ctx.append(len(run.request_spec(r)[0]) + j)
    ctx = np.concatenate(ctx) if ctx else np.zeros(0, np.int64)
    t = roofline_seconds(run.family.decode_flops(run.dims, ctx),
                         run.family.decode_bytes(run.dims, ctx, steps),
                         run.peak)
    return 100.0 * t / s
