"""Discrete-event cluster simulator + metrics (paper-scale experiments).

``ClusterSim`` is the event-indexed production core;
``reference.ReferenceClusterSim`` is the retained pre-rewrite oracle the
equivalence tests pin it against.
"""

from repro.sim.cluster import ClusterSim, SimAgent, SimResult
from repro.sim.metrics import (
    FairnessStats,
    JctStats,
    fair_ratios,
    fairness_stats,
    jct_stats,
)
from repro.sim.reference import ReferenceClusterSim

__all__ = [
    "ClusterSim",
    "ReferenceClusterSim",
    "SimAgent",
    "SimResult",
    "FairnessStats",
    "JctStats",
    "fair_ratios",
    "fairness_stats",
    "jct_stats",
]
