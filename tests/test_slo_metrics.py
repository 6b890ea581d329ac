"""SLO-tier latency accounting + fleet metrics regressions (PR 7).

Covers the three accounting fixes that ride the fused-prefill PR:

  * ``repro.sim.metrics``' new TTFT/TBT percentile and SLO-attainment
    aggregates, and :class:`repro.api.service.MetricsRecorder`'s
    per-request tracking (keyed ``(replica, rid)`` — rids are only
    unique per child backend in a fleet);
  * ``ReplicatedBackend.drain`` fleet-level prefix-cache metrics:
    ``hit_fractions`` dict-merged and ``prefill_tokens_saved`` summed
    across children (they used to be dropped — only per-replica copies
    survived);
  * TTFT semantics under prefix hits: BOTH backends must timestamp the
    first token from the SHORTENED prefill, so a cached prefix buys
    exactly its own length of first-token latency — pinned by comparing
    cold/warm TTFT deltas against the cached amount on the sim
    (analytic, exact) and the engine (chunk-granular, exact for
    block- and chunk-aligned prompts), unfused and fused;
  * the interference claim fused prefill exists for: on a contended
    SLO-tiered fleet it cuts TTFT p99 at no more than 5% of mean JCT.
"""

import numpy as np
import pytest

from repro.api import (
    AgentArrived,
    AgentCompleted,
    AgentService,
    AgentSpec,
    EngineBackend,
    SimBackend,
    TokenGenerated,
    specs_from_closed_loop,
)
from repro.api.service import MetricsRecorder
from repro.core import InferenceSpec
from repro.sim.metrics import (
    SloTier,
    latency_stats,
    slo_attainment,
)
from repro.workloads import SLO_CLASSES, SLO_TIERS, slo_tier_of

# ------------------------------------------------------- metric aggregates


def test_latency_stats_percentiles():
    ttfts = {i: float(i) for i in range(1, 101)}     # 1..100
    tbts = {i: 0.5 for i in range(10)}
    lat = latency_stats(ttfts, tbts)
    assert lat.n_ttft == 100 and lat.n_tbt == 10
    assert lat.ttft_mean == pytest.approx(50.5)
    assert lat.ttft_p50 == pytest.approx(np.percentile(range(1, 101), 50))
    assert lat.ttft_p99 == pytest.approx(np.percentile(range(1, 101), 99))
    assert lat.tbt_p99 == pytest.approx(0.5)
    assert "ttft" in lat.row() and "tbt" in lat.row()
    empty = latency_stats({}, {})
    assert empty.n_ttft == 0 and empty.ttft_p99 == 0.0


def test_slo_attainment_tiers():
    fast = SloTier("fast", ttft=1.0, tbt=0.1)
    slow = SloTier("slow", ttft=10.0, tbt=1.0)
    tiers = {0: fast, 1: fast, 2: slow, 3: fast}
    ttfts = {0: 0.5, 1: 2.0, 2: 8.0}     # 3 misses its deadline by absence
    tbts = {0: 0.05, 1: 0.05}            # 2 has no TBT sample: vacuous pass
    slo = slo_attainment(ttfts, tbts, tiers)
    # 0 attains both; 1 misses TTFT; 2 attains (TBT vacuous); 3 has no
    # first token at all -> counted as a miss
    assert slo.n == 4
    assert slo.attainment == pytest.approx(2 / 4)
    assert slo.ttft_attainment == pytest.approx(2 / 4)
    assert slo.per_tier["fast"] == pytest.approx(1 / 3)
    assert slo.per_tier["slow"] == pytest.approx(1.0)
    assert slo_attainment({}, {}, {}).attainment == 1.0


def test_workload_slo_tiers_cover_classes():
    assert set(SLO_TIERS) == set(SLO_CLASSES)
    for cls in SLO_CLASSES:
        tier = slo_tier_of(cls)
        assert tier.ttft > 0 and tier.tbt > 0
    # interactive agents get the tight targets
    assert SLO_TIERS["interactive"].ttft < SLO_TIERS["batch"].ttft


def test_recorder_ttft_tbt_per_request_keying():
    """TTFT is arrival -> first token of ANY request; TBT pools within-
    request gaps.  Two fleet replicas reuse rid 0 for different agents —
    the (replica, rid) key must keep their spans apart."""
    rec = MetricsRecorder()
    rec.record(AgentArrived(0, 10.0, replica=0))
    rec.record(AgentArrived(1, 10.0, replica=1))
    # agent 0 / replica 0, rid 0: tokens at 12, 13, 14
    for t in (12.0, 13.0, 14.0):
        rec.record(TokenGenerated(0, t, rid=0, token=7, replica=0))
    # agent 1 / replica 1, SAME rid 0: tokens at 20, 26
    for t in (20.0, 26.0):
        rec.record(TokenGenerated(1, t, rid=0, token=7, replica=1))
    rec.record(AgentCompleted(0, 14.0, jct=4.0, replica=0))
    rec.record(AgentCompleted(1, 26.0, jct=16.0, replica=1))
    assert rec.ttfts() == {0: pytest.approx(2.0), 1: pytest.approx(10.0)}
    # merged keying would pool one 8-token span; correct keying gives
    # agent 0 a 2s/2-gap span and agent 1 a 6s/1-gap span
    assert rec.tbts() == {0: pytest.approx(1.0), 1: pytest.approx(6.0)}
    lat = rec.latency_stats()
    assert lat.n_ttft == 2 and lat.n_tbt == 2
    tiers = {0: SloTier("t", ttft=5.0, tbt=2.0),
             1: SloTier("t", ttft=5.0, tbt=2.0)}
    assert rec.slo_stats(tiers).attainment == pytest.approx(0.5)


# ------------------------------------------ fleet-level cache metrics fix


def test_replicated_drain_fleet_cache_metrics():
    """Regression: the fleet drain used to drop hit_fractions /
    prefill_tokens_saved on the floor (only ``per_replica`` copies
    survived).  They must now be the dict-merge / sum of the children's,
    with BOTH replicas contributing."""
    svc = AgentService.sim(
        "justitia", replicas=2, router="round_robin",
        total_kv=16384.0, prefix_cache=True,
    )
    rng = np.random.default_rng(3)
    specs = specs_from_closed_loop(rng, 8, 20.0, classes=("chat",))
    svc.submit_many(specs)
    res = svc.drain()
    hf = res.metrics["hit_fractions"]
    saved = res.metrics["prefill_tokens_saved"]
    merged, child_saved = {}, 0.0
    for child in res.metrics["per_replica"]:
        merged.update(child.get("child_hit_fractions") or {})
        child_saved += child.get("child_prefill_tokens_saved", 0) or 0
    assert hf == merged and len(hf) > 0
    assert saved == pytest.approx(child_saved) and saved > 0
    assignment = svc.backend.assignment
    replicas_with_hits = {assignment[aid] for aid in hf}
    assert replicas_with_hits == {0, 1}, (
        "fleet metrics must merge across ALL children, not just the last"
    )


# ------------------------------------- TTFT semantics under prefix hits


BLOCK = 16
CHUNK = 8
PROMPT = 64          # 4 full blocks, 8 chunks
HIT = 32             # shared head: 2 full blocks, 4 chunks
DECODE = 6
PREFILL_RATE = 4000.0


def _shared_prefix_specs(rng):
    """Two one-request agents whose prompts share a block- and
    chunk-aligned 32-token head, far enough apart that neither queues."""
    head = rng.integers(0, 256, size=HIT)
    prompts = [
        np.concatenate([head, rng.integers(0, 256, size=PROMPT - HIT)])
        for _ in range(2)
    ]
    return [
        AgentSpec(
            stages=[[InferenceSpec(PROMPT, DECODE)]],
            arrival=float(200 * i),
            prompts=[[p]],
            prefix_group="fam",
            shared_prefix=float(HIT),
        )
        for i, p in enumerate(prompts)
    ]


def _ttfts(backend):
    svc = AgentService(backend)
    rng = np.random.default_rng(17)
    svc.submit_many(_shared_prefix_specs(rng))
    res = svc.drain()
    assert len(res.finish) == 2
    t = svc.recorder.ttfts()
    return t[0], t[1]


def test_sim_ttft_shortened_by_analytic_hit():
    """Sim cores: the warm agent's first token arrives exactly
    ``hit / prefill_rate`` seconds earlier than the cold agent's."""
    cold_off, warm_off = _ttfts(
        SimBackend("justitia", total_kv=8192.0, token_events=True,
                   prefill_rate=PREFILL_RATE)
    )
    assert warm_off == pytest.approx(cold_off)     # cache off: identical
    cold_on, warm_on = _ttfts(
        SimBackend("justitia", total_kv=8192.0, token_events=True,
                   prefill_rate=PREFILL_RATE, prefix_cache=True)
    )
    assert cold_on == pytest.approx(cold_off)      # cold path unchanged
    shortening = (cold_on - warm_on) * PREFILL_RATE
    assert shortening == pytest.approx(HIT), (
        f"sim first token must come off the SHORTENED prefill: "
        f"TTFT delta covers {shortening:.1f} tokens, expected {HIT}"
    )


@pytest.mark.parametrize("fused", [False, True])
def test_engine_ttft_shortened_by_cached_blocks(tiny_model, fused):
    """Engine (both admission paths): a block-aligned cached head buys
    exactly ``hit / prefill_chunk`` iterations of first-token latency —
    the same "first token timestamped from the shortened prefill" rule
    the sim pins, at the engine's chunk granularity."""
    model, params = tiny_model

    def backend(prefix_cache):
        return EngineBackend(
            model, params, "justitia",
            pool_tokens=2048, block_size=BLOCK, max_batch=4,
            cache_len=128, prefill_chunk=CHUNK, token_scale=1,
            time_scale=1.0, prefix_cache=prefix_cache,
            fused_prefill=fused,
        )

    cold_off, warm_off = _ttfts(backend(prefix_cache=False))
    assert warm_off == pytest.approx(cold_off)     # cache off: identical
    cold_on, warm_on = _ttfts(backend(prefix_cache=True))
    assert cold_on == pytest.approx(cold_off)      # cold path unchanged
    shortening = (cold_on - warm_on) * CHUNK       # iterations -> tokens
    assert shortening == pytest.approx(HIT), (
        f"engine (fused={fused}) first token must come off the shortened "
        f"prefill: TTFT delta covers {shortening:.1f} tokens, "
        f"expected {HIT}"
    )


@pytest.fixture(scope="module")
def tiny_model():
    import jax

    from repro.configs import get_config
    from repro.models import Model

    cfg = get_config("granite-3-2b").reduced(vocab=256)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


# ------------------------------------------------- the interference claim


def _slo_fleet_run(model, params, fused: bool):
    """An SLO-tiered closed-loop fleet (interactive chat agents beside
    long-prompt batch agents) on a narrow pool, so admission queues stay
    non-empty and an unfused prefill pass stalls running decoders."""
    svc = AgentService.engine(
        model, params, "justitia", pool_tokens=384, max_batch=4,
        cache_len=512, prefill_chunk=16, token_scale=8,
        time_scale=30.0 / 8, seed=0, fused_prefill=fused,
        record_events=False,
    )
    svc.submit_many(specs_from_closed_loop(
        np.random.default_rng(7), 24, 30.0, classes=SLO_CLASSES))
    res = svc.drain()
    eng = svc.backend.engine
    eng.alloc.check_invariants()
    assert (eng.metrics.get("fused_slices", 0) > 0) == fused
    return svc.recorder.latency_stats().ttft_p99, np.mean(
        list(res.jct.values()))


def test_fused_cuts_ttft_p99_within_jct_bound(tiny_model):
    """Riding prompts through the decode windows must buy first-token
    latency without selling end-to-end time: TTFT p99 falls, and mean JCT
    stays within 1.05x of the unfused engine's."""
    model, params = tiny_model
    ttft_off, jct_off = _slo_fleet_run(model, params, fused=False)
    ttft_on, jct_on = _slo_fleet_run(model, params, fused=True)
    assert ttft_on < ttft_off
    assert jct_on <= 1.05 * jct_off
