"""Agent traffic: task-parallel agents drawn from the paper's agent classes.

Every mix file in this directory that names ``"generator": "agents"`` is
read by this one module.  A mix gives the agent classes as data (stages,
parallel fan-out, skew-normal prompt and decode lengths), the loop
(``open``: agents arrive on a schedule; ``closed``: N clients each submit
their next agent the moment their last one completes) and the arrival
process of an open loop.

What a seed changes.  The sequence of agents (their classes and token
lengths) is drawn once from the mix's own ``pool_seed`` and, in an open
loop, the arrival schedule from its ``schedule_seed``, so every run of a
cell serves the same work at the same times.  The run's ``--seed`` draws
the prompt token ids (and, in ``run.py``, the weights).  The order is fixed
too: on one TPU v5e, runs of granite-3-2b whose seed reordered the same
burst traffic spread their p90 time to first token from 14 to 19 s, where
two runs of one seed agreed within 0.1%: the order decides the work each
burst carries.

The class templates, the skew-normal sampler and the Mooncake-like bursty
arrivals are copies of ``repro.workloads.agents`` and
``repro.workloads.arrivals``: the yardstick lives here, where a change to
the program cannot move it.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class Agent:
    """One agent: its class and, per stage, (prompt token ids, decode budget)
    for each parallel request."""

    cls: str
    stages: list[list[tuple[np.ndarray, int]]]

    @property
    def requests(self) -> list[tuple[np.ndarray, int]]:
        return [r for stage in self.stages for r in stage]


@dataclasses.dataclass
class Traffic:
    """What one run serves.

    ``agents`` is the submission order.  An open loop submits agent i at
    ``arrivals[i]`` seconds after the window opens; a closed loop has
    ``clients`` clients, each taking the next agent of the sequence when its
    previous one completes.  Submissions stop when the window closes."""

    loop: str
    agents: list[Agent]
    arrivals: list[float] | None = None
    clients: int = 0


def skew_normal(rng: np.random.Generator, loc: float, scale: float,
                alpha: float) -> float:
    """Azzalini skew-normal draw (as ``repro.workloads.agents``)."""
    delta = alpha / math.sqrt(1.0 + alpha * alpha)
    z0 = abs(rng.standard_normal())
    z1 = rng.standard_normal()
    return loc + scale * (delta * z0 + math.sqrt(1.0 - delta * delta) * z1)


def sample_lengths(rng: np.random.Generator, cls: dict) -> list[list[tuple]]:
    """One agent's (prompt, decode) token lengths per stage and request,
    drawn as ``repro.workloads.agents.sample_agent`` draws them."""
    complexity = float(np.clip(
        np.exp(rng.normal(0.0, cls["complexity_spread"])), 0.4, 3.0
    ))
    stages, prev_outputs = [], 0.0
    for st in cls["stages"]:
        lo, hi = st["parallel"]
        n = int(rng.integers(lo, hi + 1))
        reqs = []
        for _ in range(n):
            p = st.get("prefill_from_prev_outputs", 0.0) * prev_outputs / n
            p += float(np.clip(skew_normal(rng, *st["prefill"]), 16, 65536))
            p = min(p, 4096.0)
            d = complexity * float(
                np.clip(skew_normal(rng, *st["decode"]), 4, 8192)
            )
            reqs.append((int(p), max(1, int(d))))
        prev_outputs = float(sum(d for _, d in reqs))
        stages.append(reqs)
    return stages


def mooncake_like_arrivals(rng: np.random.Generator, n: int,
                           window_s: float, burstiness: float,
                           per_burst: int) -> np.ndarray:
    """n sorted arrival times in [0, window_s): Poisson arrivals whose rate
    follows Gamma-weighted bursts (as ``repro.workloads.arrivals``).

    Two departures from the copy.  Each burst's centre is drawn inside its
    own equal slice of the window, not anywhere in it, so bursts do not
    merge into one.  A burst that spills over an end of the window wraps
    round to the other end instead of being clipped, so no pile of agents
    lands on the window's last instant, where its work would fall outside
    the measured window."""
    n_bursts = max(1, int(n / per_burst))
    centers = ((np.arange(n_bursts) + rng.uniform(size=n_bursts))
               * window_s / n_bursts)
    weights = rng.gamma(shape=1.0 / burstiness, scale=burstiness,
                        size=n_bursts)
    counts = rng.multinomial(n, weights / weights.sum())
    spread = window_s / n_bursts / 2.0
    times = [
        rng.normal(c, spread, size=k) for c, k in zip(centers, counts) if k
    ]
    return np.sort(np.mod(np.concatenate(times), window_s))


def pool(mix: dict, n: int) -> list[tuple[str, list[list[tuple]]]]:
    """The mix's fixed sequence of n agents: (class, lengths per stage)."""
    rng = np.random.default_rng(mix["pool_seed"])
    names = sorted(mix["classes"])
    out = []
    for _ in range(n):
        name = names[int(rng.integers(len(names)))]
        out.append((name, sample_lengths(rng, mix["classes"][name])))
    return out


def pool_size(mix: dict, seconds: float) -> int:
    if mix["loop"] == "open":
        return max(1, int(round(mix["rate_agents_per_s"] * seconds)))
    return int(mix["pool_agents"])


def build(mix: dict, seed: int, seconds: float, vocab: int) -> Traffic:
    """The traffic of one run of ``seconds`` seconds with seed ``seed``."""
    n = pool_size(mix, seconds)
    agents = pool(mix, n)
    rng = np.random.default_rng(seed)
    served = [
        Agent(name, [
            [(rng.integers(0, vocab, size=p, dtype=np.int32), d)
             for p, d in stage]
            for stage in lengths
        ])
        for name, lengths in agents
    ]
    if mix["loop"] == "open":
        arr = mix["arrivals"]
        sched = mooncake_like_arrivals(
            np.random.default_rng(arr["schedule_seed"]), n, seconds,
            arr["burstiness"], arr["agents_per_burst"],
        )
        return Traffic("open", served, arrivals=[float(t) for t in sched])
    if mix["loop"] == "closed":
        return Traffic("closed", served, clients=int(mix["clients"]))
    raise ValueError(f"unknown loop {mix['loop']!r}")
