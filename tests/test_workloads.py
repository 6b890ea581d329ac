"""Workload statistics: the paper's 72/26/2 size mix, per-class demand
stability and the density-window arrival process."""

import numpy as np


def test_size_mix_matches_paper():
    """72/26/2 small/medium/large sampling probabilities (paper §5.1)."""
    from repro.workloads import SIZE_BUCKETS, sample_mixed_suite

    rng = np.random.default_rng(0)
    suite = sample_mixed_suite(rng, 2000)
    by_size = {"small": 0, "medium": 0, "large": 0}
    for a in suite:
        for size, names in SIZE_BUCKETS.items():
            if a.name in names:
                by_size[size] += 1
    n = len(suite)
    assert abs(by_size["small"] / n - 0.72) < 0.04
    assert abs(by_size["medium"] / n - 0.26) < 0.04
    assert abs(by_size["large"] / n - 0.02) < 0.015


def test_agent_demand_stability():
    """App. A: within-class demand spread is narrow relative to the
    across-class spread (what makes per-class prediction work)."""
    from repro.workloads import AGENT_CLASSES, sample_agent

    rng = np.random.default_rng(1)
    class_means = {}
    within_cv = []
    for cls in AGENT_CLASSES:
        costs = np.array([sample_agent(rng, cls).true_cost
                          for _ in range(40)])
        class_means[cls] = costs.mean()
        within_cv.append(costs.std() / costs.mean())
    means = np.array(list(class_means.values()))
    across_spread = means.max() / means.min()
    assert across_spread > 50          # classes span orders of magnitude
    assert np.mean(within_cv) < 1.0    # within-class is comparatively tight


def test_arrivals_sorted_within_window():
    from repro.workloads import DENSITY_WINDOWS_S, arrivals_for_density

    rng = np.random.default_rng(2)
    for density in (1, 2, 3):
        t = arrivals_for_density(rng, 300, density)
        assert len(t) == 300
        assert (np.diff(t) >= 0).all()
        assert t.min() >= 0 and t.max() <= DENSITY_WINDOWS_S[density]
