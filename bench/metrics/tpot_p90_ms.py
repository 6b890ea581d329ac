"""90th percentile over requests ready inside the window of the mean gap
between their streamed tokens, (last - first) / (tokens - 1), in ms.
Taken per request because a decode window delivers its tokens in a burst."""

from stats import percentile


def read(run):
    return percentile(
        [(r.stamps[-1] - r.stamps[0]) / (len(r.stamps) - 1) * 1e3
         for r in run.window_requests() if len(r.stamps) >= 2],
        90,
    )
