"""Tests for the unified serving API: scheduler registry, online arrivals,
engine-vs-simulator equivalence through AgentService (single-backend and
``ReplicatedBackend`` fleets), the router registry, and the engine's
static-key queue fast path / stall diagnostics."""

import jax
import numpy as np
import pytest

import repro.core as core
import repro.core.schedulers as schedulers_mod
from repro.api import (
    AgentHooks,
    AgentService,
    AgentSpec,
    EngineBackend,
    ReplicatedBackend,
    SimBackend,
    resolve_router,
    router_names,
)
from repro.configs import get_config
from repro.core import (
    AgentScheduler,
    InferenceSpec,
    SchedulerPolicy,
    make_scheduler,
    register_scheduler,
    scheduler_names,
    unregister_scheduler,
)
from repro.engine import EngineAgent, EngineStalledError, ServeEngine
from repro.models import Model

VOCAB = 256


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("granite-3-2b").reduced(vocab=VOCAB)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


# -------------------------------------------------------------- registry


def test_registry_registration_lookup_and_all_schedulers():
    @register_scheduler("test-rr", "rr-alias")
    class _RoundRobin(AgentScheduler):
        pass

    try:
        s = make_scheduler("test-rr", 10.0)
        assert isinstance(s, _RoundRobin)
        assert s.name == "test-rr"
        assert isinstance(s, SchedulerPolicy)
        # aliases resolve but are not listed
        assert isinstance(make_scheduler("rr-alias", 10.0), _RoundRobin)
        assert "rr-alias" not in scheduler_names()
        # ALL_SCHEDULERS is auto-derived from the registry, live
        assert "test-rr" in scheduler_names()
        assert "test-rr" in core.ALL_SCHEDULERS
        assert "test-rr" in schedulers_mod.ALL_SCHEDULERS
    finally:
        unregister_scheduler("test-rr")
    assert "test-rr" not in core.ALL_SCHEDULERS
    with pytest.raises(ValueError):
        make_scheduler("test-rr", 10.0)


def test_registry_unknown_name_and_duplicates():
    with pytest.raises(ValueError, match="unknown scheduler"):
        make_scheduler("nope", 1.0)
    with pytest.raises(ValueError, match="already registered"):

        @register_scheduler("justitia")
        class _Shadow(AgentScheduler):
            pass


def test_all_schedulers_canonical_order():
    assert core.ALL_SCHEDULERS == [
        "vllm-fcfs", "vllm-sjf", "parrot", "vtc", "srjf", "justitia",
        "locality_fair",
    ]


def test_builtin_schedulers_satisfy_policy_protocol():
    for name in core.ALL_SCHEDULERS:
        assert isinstance(make_scheduler(name, 100.0), SchedulerPolicy)


# ------------------------------------------- engine/sim order equivalence

# Sequential-contention workload: the pool fits exactly one inference at a
# time on both backends (p=33 > remaining free while anything runs), so the
# completion order is exactly the scheduler's key order at each completion
# — observable identically through the engine and the simulator.
_EQUIV = [  # (arrival_s, decode)
    (0.0, 16),
    (2.0, 8),
    (4.0, 12),
    (6.0, 4),
]


def _equiv_specs():
    return [
        AgentSpec(stages=[[InferenceSpec(33, d)]], arrival=t)
        for t, d in _EQUIV
    ]


def _completion_order(jct_finish: dict) -> list:
    return [aid for aid, _ in sorted(jct_finish.items(), key=lambda kv: kv[1])]


@pytest.mark.parametrize("sched_name", ["justitia", "vtc"])
def test_online_arrivals_same_completion_order_engine_vs_sim(
    tiny_model, sched_name
):
    model, params = tiny_model
    sim_svc = AgentService(
        SimBackend(
            sched_name, total_kv=64.0, decode_rate=1.0, prefill_rate=33.0
        )
    )
    sim_svc.submit_many(_equiv_specs())
    sim_res = sim_svc.drain()

    eng_svc = AgentService(
        EngineBackend(
            model, params, sched_name,
            pool_tokens=64, block_size=16, max_batch=4, cache_len=64,
            token_scale=1, time_scale=1.0,
        )
    )
    # online: agents enter the engine's pending heap with future arrival
    # iterations and are released mid-run, not submitted upfront
    eng_svc.submit_many(_equiv_specs())
    assert eng_svc.backend.engine.pending, "future arrivals should be pending"
    eng_res = eng_svc.drain()

    assert set(sim_res.finish) == set(eng_res.finish) == {0, 1, 2, 3}
    assert _completion_order(sim_res.finish) == _completion_order(
        eng_res.finish
    ), f"order diverged under {sched_name}"
    # no swap divergence: this workload must be swap-free on both backends
    assert sim_res.swaps == 0 and eng_res.swaps == 0


def test_engine_mid_run_submission_matches_upfront_schedule(tiny_model):
    """Submitting during run(until=...) behaves like a scheduled arrival."""
    model, params = tiny_model

    def serve(online: bool):
        svc = AgentService(
            EngineBackend(
                model, params, "justitia",
                pool_tokens=256, max_batch=2, cache_len=128,
            )
        )
        svc.submit(AgentSpec(stages=[[InferenceSpec(32, 24)]], arrival=0.0))
        if online:
            svc.run(until=10.0)  # clock is now past 10 iterations
            svc.submit(
                AgentSpec(stages=[[InferenceSpec(16, 8)]], arrival=10.0)
            )
        else:
            svc.submit(
                AgentSpec(stages=[[InferenceSpec(16, 8)]], arrival=10.0)
            )
        return svc.drain()

    upfront = serve(online=False)
    online = serve(online=True)
    assert upfront.finish == online.finish


# -------------------------------------------------- facade + event stream


def test_service_streams_events_and_hooks(tiny_model):
    model, params = tiny_model
    svc = AgentService.engine(
        model, params, "justitia",
        pool_tokens=256, max_batch=2, cache_len=128,
    )
    seen = []
    h = svc.submit(
        AgentSpec(stages=[[InferenceSpec(16, 6)], [InferenceSpec(16, 4)]]),
        hooks=AgentHooks(
            on_stage_complete=lambda ev: seen.append(("stage", ev.stage)),
            on_complete=lambda ev: seen.append(("done", ev.agent_id)),
        ),
    )
    res = svc.drain()
    assert h.done and h.finish == res.finish[0]
    assert h.tokens and len(h.tokens) == 10  # per-token streaming
    assert [e for e in seen if e[0] == "stage"] == [("stage", 0), ("stage", 1)]
    assert seen[-1] == ("done", 0)
    assert h.stage_finish[0] < h.stage_finish[1]
    assert res.event_counts["TokenGenerated"] == 10


def test_sim_backend_same_workload_one_flag(tiny_model):
    """The acceptance scenario in miniature: identical AgentSpec list through
    both backends via AgentService."""
    model, params = tiny_model
    specs = [
        AgentSpec(stages=[[InferenceSpec(64, 32)] * 2], arrival=0.0),
        AgentSpec(stages=[[InferenceSpec(32, 8)]], arrival=3.0),
    ]
    results = {}
    for backend in ("sim", "engine"):
        if backend == "sim":
            svc = AgentService.sim("justitia", total_kv=2048.0)
        else:
            svc = AgentService.engine(
                model, params, "justitia", pool_tokens=2048,
                max_batch=4, cache_len=128,
            )
        svc.submit_many([
            AgentSpec(stages=s.stages, arrival=s.arrival) for s in specs
        ])
        results[backend] = svc.drain()
    for backend, res in results.items():
        assert set(res.finish) == {0, 1}, backend
        assert res.stats.n == 2
        assert res.backend == backend


# ------------------------------------------------- replicated fleets


def test_router_registry():
    from repro.api import Router, register_router

    assert router_names() == [
        "round_robin", "least_loaded", "memory_cost_aware",
    ]
    assert resolve_router("rr") is resolve_router("round_robin")
    assert resolve_router("mca") is resolve_router("memory_cost_aware")
    with pytest.raises(ValueError, match="unknown router"):
        resolve_router("nope")
    # neither a canonical name nor an alias may shadow an existing one
    with pytest.raises(ValueError, match="already registered"):

        @register_router("custom", "least_loaded")
        class _Hijack(Router):
            pass

    # the rejected registration must not leave partial state behind
    with pytest.raises(ValueError, match="unknown router"):
        resolve_router("custom")
    with pytest.raises(ValueError, match="already registered"):

        @register_router("round_robin")
        class _Shadow(Router):
            pass


def _fleet_equiv_specs(rng) -> list[AgentSpec]:
    """8 agents, sequential contention per replica (p=33 saturates a pool
    of 64 while anything runs), staggered online arrivals, randomized but
    seed-fixed decode budgets."""
    decodes = rng.integers(4, 17, size=8)
    return [
        AgentSpec(stages=[[InferenceSpec(33, int(d))]], arrival=float(t))
        for t, d in enumerate(decodes)
    ]


def _per_replica_orders(res, assignment) -> dict[int, list[int]]:
    orders: dict[int, list[int]] = {}
    for aid, t in sorted(res.finish.items(), key=lambda kv: (kv[1], kv[0])):
        orders.setdefault(assignment[aid], []).append(aid)
    return orders


@pytest.mark.parametrize("router", ["round_robin", "memory_cost_aware"])
def test_replicated_engine_vs_sim_same_assignment_and_order(
    tiny_model, fixed_seed, router
):
    """Same routing seed => same per-replica assignment AND the same
    per-replica completion order on the replicated sim and engine fleets
    (deterministic across pytest runs via the fixed_seed fixture)."""
    model, params = tiny_model
    specs = _fleet_equiv_specs(np.random.default_rng(fixed_seed))

    sim_svc = AgentService.sim(
        "justitia", replicas=2, router=router, seed=fixed_seed,
        total_kv=64.0, decode_rate=1.0, prefill_rate=33.0,
    )
    sim_svc.submit_many(specs)
    sim_res = sim_svc.drain()

    eng_svc = AgentService.engine(
        model, params, "justitia", replicas=2, router=router,
        seed=fixed_seed,
        pool_tokens=64, block_size=16, max_batch=4, cache_len=64,
        token_scale=1, time_scale=1.0,
    )
    eng_svc.submit_many(specs)
    eng_res = eng_svc.drain()

    assert isinstance(sim_svc.backend, ReplicatedBackend)
    assert set(sim_res.finish) == set(eng_res.finish) == set(range(8))
    # identical routing decisions on both backends
    assert sim_svc.backend.assignment == eng_svc.backend.assignment
    assignment = sim_svc.backend.assignment
    assert set(assignment.values()) == {0, 1}
    # identical per-replica completion order
    assert _per_replica_orders(sim_res, assignment) == _per_replica_orders(
        eng_res, assignment
    ), f"per-replica order diverged under router={router}"
    # handles learned their replica from the event stream on both services
    for svc in (sim_svc, eng_svc):
        for aid, handle in svc.handles.items():
            assert handle.replica == assignment[aid]
    # fleet metrics surfaced on both
    for res in (sim_res, eng_res):
        assert res.metrics["replicas"] == 2
        assert res.metrics["router"] == router
        assert res.metrics["virtual_lag"] >= 0.0
        assert set(res.per_replica) == {0, 1}


def test_replicated_submit_drain_rounds_interleave(fixed_seed):
    """Backend contract: submissions may happen at any point, including
    after a drain.  The fleet re-anchors its children at the fleet makespan
    between rounds, so a short replica's clock never trails the reconciled
    horizon (regression: second-round submit used to raise ValueError)."""
    svc = AgentService.sim(
        "justitia", replicas=2, router="round_robin", seed=fixed_seed,
        total_kv=256.0, decode_rate=1.0,
    )
    # round 1: replica 0 finishes late, replica 1 early
    svc.submit(AgentSpec(stages=[[InferenceSpec(16, 40)]], arrival=0.0))
    svc.submit(AgentSpec(stages=[[InferenceSpec(16, 2)]], arrival=0.0))
    r1 = svc.drain()
    assert set(r1.finish) == {0, 1}
    horizon = max(r1.finish.values())
    # round 2: next agents land on both replicas at or after the horizon
    svc.submit(AgentSpec(stages=[[InferenceSpec(16, 4)]], arrival=0.0))
    svc.submit(AgentSpec(stages=[[InferenceSpec(16, 4)]], arrival=0.0))
    r2 = svc.drain()
    # the service's finish view is cumulative across drain rounds
    assert set(r2.finish) == {0, 1, 2, 3}
    assert r2.finish[2] >= horizon and r2.finish[3] >= horizon
    assert svc.backend.assignment == {0: 0, 1: 1, 2: 0, 3: 1}


def test_mixed_fleet_submit_after_drain(tiny_model):
    """Heterogeneous fleet (sim + engine children) survives interleaved
    submit/drain rounds: the engine child's run() must advance AT LEAST to
    the fleet makespan when re-anchoring, even when the sim child drains at
    a fractional time (regression: round-to-nearest left the engine clock
    trailing the reconciled horizon and the next submit raised)."""
    model, params = tiny_model
    children = [
        SimBackend("justitia", total_kv=256.0, decode_rate=7.0),
        EngineBackend(
            model, params, "justitia",
            pool_tokens=128, block_size=16, max_batch=2, cache_len=64,
            token_scale=1, time_scale=1.0,
        ),
    ]
    svc = AgentService.replicated(children, router="round_robin")
    # sim agent outlasts the engine one and ends at a fractional time
    svc.submit(AgentSpec(stages=[[InferenceSpec(16, 200)]]))  # sim
    svc.submit(AgentSpec(stages=[[InferenceSpec(16, 4)]]))    # engine
    r1 = svc.drain()
    assert r1.makespan != int(r1.makespan)  # the round really is fractional
    svc.submit(AgentSpec(stages=[[InferenceSpec(16, 3)]]))   # sim
    svc.submit(AgentSpec(stages=[[InferenceSpec(16, 3)]]))   # engine
    r2 = svc.drain()
    assert set(r2.finish) == {0, 1, 2, 3}
    for aid in (2, 3):
        assert r2.finish[aid] >= r1.makespan


def test_replicas3_drains_50_agent_mixed_workload_sim(fixed_seed):
    """Acceptance scenario, sim half: AgentService with replicas=3 drains a
    50-agent mixed workload and fleet-level fairness holds — every agent's
    service gap (real finish vs its replica's GPS reference) stays within
    the reconciled virtual-time bound."""
    from repro.api import specs_from_classes
    from repro.core import (
        GlobalVirtualClock,
        agent_cost,
        gps_finish_times,
        inference_cost,
    )
    from repro.core.gps import GpsAgent

    decode_rate, m = 30.0, 8192.0
    rng = np.random.default_rng(fixed_seed)
    specs = specs_from_classes(rng, 50, 60.0)
    service = AgentService.sim(
        "justitia", replicas=3, router="memory_cost_aware",
        total_kv=m, decode_rate=decode_rate,
        prefill_rate=1e12, swap_penalty=0.0,   # theorem-mode children
        record_events=False,
    )
    handles = service.submit_many(specs)
    res = service.drain()

    assert len(res.finish) == 50
    assert set(res.per_replica) == {0, 1, 2}
    assert sum(s.n for s in res.per_replica.values()) == 50

    assignment = service.backend.assignment
    flat = [s for spec in specs for st_ in spec.stages for s in st_]
    c_max = max(inference_cost(s) for s in flat)
    c_agent_max = max(
        agent_cost([s for st_ in spec.stages for s in st_])
        for spec in specs
    )
    gclock = GlobalVirtualClock([m] * 3)
    for h in handles:
        gclock.register(
            assignment[h.agent_id], h.agent_id,
            h.arrival * decode_rate, h.spec.resolved_costs()[1],
        )
    snap = gclock.reconcile(max(res.finish.values()) * decode_rate)
    bound_iters = gclock.delay_bound(c_max, c_agent_max)
    assert snap.lag >= 0.0

    for replica in range(3):
        mine = [h for h in handles if assignment[h.agent_id] == replica]
        gps = gps_finish_times(
            [
                GpsAgent(h.agent_id, h.arrival * decode_rate,
                         h.spec.resolved_costs()[1])
                for h in mine
            ],
            m,
        )
        for h in mine:
            delay = res.finish[h.agent_id] * decode_rate - gps[h.agent_id]
            assert delay <= bound_iters * 1.05 + 1.0, (
                f"agent {h.agent_id} on replica {replica}: service gap "
                f"{delay:.1f} iters exceeds reconciled bound "
                f"{bound_iters:.1f}"
            )


def test_replicas3_drains_50_agent_mixed_workload_engine(
    tiny_model, fixed_seed
):
    """Acceptance scenario, engine half: the same fleet API drains 50
    mixed task-parallel agents across 3 real engines, with per-replica
    metrics aggregated and the load spread across all replicas."""
    model, params = tiny_model
    rng = np.random.default_rng(fixed_seed)
    specs = []
    for i in range(50):
        n_stages = 1 + int(rng.integers(0, 2))
        stages = [
            [
                InferenceSpec(int(rng.integers(8, 25)),
                              int(rng.integers(4, 11)))
                for _ in range(1 + int(rng.integers(0, 2)))
            ]
            for _ in range(n_stages)
        ]
        specs.append(AgentSpec(stages=stages, arrival=float(i)))
    service = AgentService.engine(
        model, params, "justitia", replicas=3, router="least_loaded",
        seed=fixed_seed,
        pool_tokens=512, block_size=16, max_batch=4, cache_len=64,
        token_scale=1, time_scale=1.0, record_events=False,
    )
    service.submit_many(specs)
    res = service.drain()

    assert len(res.finish) == 50
    assert res.metrics["replicas"] == 3
    assert set(res.per_replica) == {0, 1, 2}
    assert sum(s.n for s in res.per_replica.values()) == 50
    # least_loaded keeps the live-agent spread tight at every decision
    agents_per_replica = [p["agents"] for p in res.metrics["per_replica"]]
    assert max(agents_per_replica) - min(agents_per_replica) <= 5
    assert res.metrics["virtual_lag"] >= 0.0


# ------------------------------------- engine satellites: sorts + stalls


def test_static_scheduler_skips_admission_resort(tiny_model):
    model, params = tiny_model
    rng = np.random.default_rng(0)

    def run(name):
        eng = ServeEngine(
            model, params, make_scheduler(name, 512.0),
            pool_tokens=512, max_batch=2, cache_len=128,
        )
        for aid in range(3):
            stage = [(rng.integers(0, VOCAB, size=24), 12) for _ in range(2)]
            eng.submit_agent(EngineAgent(aid, 0, [stage], 100.0 + aid))
        eng.run_until_idle()
        return eng.metrics

    assert run("justitia")["sorts"] == 0     # static key: lazy sorted insert
    assert run("vtc")["sorts"] > 0           # dynamic key: re-sorts per admit


def test_run_until_idle_stall_carries_diagnostics(tiny_model):
    model, params = tiny_model
    rng = np.random.default_rng(1)
    eng = ServeEngine(
        model, params, make_scheduler("justitia", 512.0),
        pool_tokens=512, max_batch=2, cache_len=256,
    )
    eng.submit_agent(
        EngineAgent(0, 0, [[(rng.integers(0, VOCAB, size=16), 64)]], 10.0)
    )
    with pytest.raises(EngineStalledError) as ei:
        eng.run_until_idle(max_iters=4)
    err = ei.value
    assert isinstance(err, RuntimeError)      # backward compatible
    for fragment in ("waiting=", "swapped=", "running=", "free_blocks=",
                     "live_per_agent="):
        assert fragment in str(err)
    assert err.completions == {}
    assert err.metrics["tokens"] > 0          # partial progress surfaced


@pytest.mark.parametrize("env_dir", [None, "elsewhere/jax-cache"])
def test_compile_cache_placement(env_dir):
    """``enable_compile_cache`` leaves ``$JAX_COMPILATION_CACHE_DIR`` to
    JAX when it is set and otherwise points JAX at ``<repo>/.jax_cache``
    (run in a child so this process never turns the cache on)."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    code = (
        "import jax\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, cwd=root, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    returned, configured = proc.stdout.split()
    want = env_dir or os.path.join(root, ".jax_cache")
    assert returned == configured == want
