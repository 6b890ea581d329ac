"""Mean agent completion time over the agents due inside the window: from
the agent's due arrival to its last request's completion."""

import numpy as np


def read(run):
    jct = [a.done - a.due for a in run.window_agents() if a.done is not None]
    return float(np.mean(jct)) if jct else None
