"""What a metric reader sees of a run, and the arithmetic they share.

Each metric is a module ``bench/metrics/<name>.py`` with ``read(run)``,
which returns the metric's value or ``None`` when the run holds nothing it
can read (the harness then leaves the metric out).  ``run`` is a ``Run``.

Windows.  The window opens at ``t0`` and lasts ``seconds``; a traced run
traces its last part (``served.trace_window``), and the device metrics
count only the work of that part.  A rate counts only work stamped inside
the window.  A latency tail is taken over every request
that became ready inside it (the agent's due time for its first stage, the
previous stage's completion after that), each followed to its end, also
when that falls in the drain.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


def percentile(values, q: float) -> Optional[float]:
    """The q-th percentile, interpolated linearly between order statistics
    (rank ``q/100 * (n - 1)``); None for no values."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return None
    rank = q / 100.0 * (len(v) - 1)
    lo = int(np.floor(rank))
    hi = min(lo + 1, len(v) - 1)
    return float(v[lo] + (v[hi] - v[lo]) * (rank - lo))


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


@dataclasses.dataclass
class Run:
    served: object            # loadgen.Served
    traffic: object           # traffic generator's Traffic
    family: object            # the configuration's family (bench/reference/)
    dims: object              # its ``family.Dims``
    engine: dict              # the configuration's engine sizes
    peak: dict                # device peaks (peaks.json)
    setup_s: float
    memory: dict              # device.memory_stats() after the window
    trace: Optional[dict] = None    # devtrace.summarize(...) or None

    # ---------------------------------------------------------- windows

    @property
    def t0(self) -> float:
        return self.served.t0

    @property
    def t1(self) -> float:
        return self.served.t0 + self.served.seconds

    def window_requests(self) -> list:
        """Requests that became ready inside the window."""
        return [r for r in self.served.reqs.values()
                if self.t0 <= r.ready < self.t1]

    def window_agents(self) -> list:
        return [a for a in self.served.agents if self.t0 <= a.due < self.t1]

    def tokens_in(self, lo: float, hi: float) -> int:
        return sum(int(np.sum((np.asarray(r.stamps) >= lo)
                              & (np.asarray(r.stamps) < hi)))
                   for r in self.served.reqs.values())

    def __post_init__(self):
        # a stage's requests take consecutive rids in the order the traffic
        # lists them, so rank within the stage gives the request's place
        self._prompts = {}
        for a in self.served.agents:
            agent = self.traffic.agents[a.index]
            by_stage: dict[int, list] = {}
            for rid in sorted(a.rids):
                by_stage.setdefault(self.served.reqs[rid].stage, []).append(rid)
            for stage, rids in by_stage.items():
                for j, rid in enumerate(rids):
                    self._prompts[rid] = agent.stages[stage][j]

    def request_spec(self, r):
        """(prompt token ids, decode budget) the request was served."""
        return self._prompts[r.rid]

    # ---------------------------------------------------------- counters

    def counter_delta(self, key: str) -> int:
        """An engine counter's growth over the window."""
        return (self.served.counters_close[key]
                - self.served.counters_open[key])

    def trace_delta(self, key: str) -> int:
        """An engine counter's growth over the traced part of the window."""
        start, end = self.served.counters_trace
        return end[key] - start[key]

    def unpaused(self, lo: float, hi: float) -> float:
        """Seconds of [lo, hi] outside the tracer's own stalls."""
        return (hi - lo) - sum(max(0.0, min(hi, b) - max(lo, a))
                               for a, b in self.served.paused)
