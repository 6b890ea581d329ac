"""90th percentile, over the requests ready inside the window, of the time
from ready (the agent's due arrival for its first stage, the previous
stage's completion after that) to the request's first streamed token.
Queueing is included."""

from stats import percentile


def read(run):
    return percentile([r.stamps[0] - r.ready for r in run.window_requests()
                       if r.stamps], 90)
