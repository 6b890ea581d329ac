"""Seeded engine workloads and the engine oracle's snapshot, shared by the
tests that hold ``ServeEngine`` to the frozen ``ReferenceServeEngine``."""

from __future__ import annotations

import numpy as np

#: the engine counters the frozen reference must reproduce exactly
ORACLE_KEYS = ("tokens", "prefills", "swaps", "decode_steps")


def synth_agents(seed: int, n: int, aid0: int = 0) -> list:
    """Seeded mixed task-parallel agents (1-2 stages x 1-2 inferences)."""
    from repro.core import InferenceSpec, agent_cost
    from repro.engine import EngineAgent

    rng = np.random.default_rng(seed)
    agents = []
    for i in range(n):
        stages, specs = [], []
        for _ in range(1 + int(rng.integers(0, 2))):
            stage = []
            for _ in range(1 + int(rng.integers(0, 2))):
                p = int(rng.integers(8, 24))
                d = int(rng.integers(32, 70))
                stage.append((rng.integers(0, 256, size=p), d))
                specs.append(InferenceSpec(p, d))
            stages.append(stage)
        agents.append(
            EngineAgent(aid0 + i, int(rng.integers(0, 5 * n)), stages,
                        agent_cost(specs))
        )
    return agents


def _snapshot(eng) -> dict:
    return {
        "completions": dict(eng.completions),
        "now": eng.now,
        **{k: eng.metrics[k] for k in ORACLE_KEYS},
    }
