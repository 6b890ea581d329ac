"""PR 9 suspension semantics: grammar fuzz, KV retention, failover.

A closed-loop agent whose stage callback reports a ``resume_delay``
SUSPENDS at the stage boundary: it holds no decode slot for the think
time, its KV falls under the backend's ``suspend_retention`` policy
(hold / spill / drop), and memory pressure victimizes suspended agents
before running ones.  Checked here:

  * fuzzed Suspended/Resumed grammar interleavings on the sim backend
    under every retention (conformance rules live in
    ``test_event_conformance.assert_conformant_stream``);
  * retention observables: ``hold`` pins KV (``held_peak`` > 0) and
    escalates under pressure; ``drop`` pins nothing;
  * fuzzed grammar under crash failover on a 2-replica fleet
    (``allow_requeue``): suspensions stay balanced through migration;
  * a suspended agent on a crashed replica resumes EXACTLY ONCE, on the
    survivor, no earlier than its think deadline, with its accrued
    virtual finish time carried (``GlobalVirtualClock.migrate`` keeps
    the recorded F_j — a crash cannot demote a thinking agent);
  * the Equinox question: ``think_time_accrual=False`` removes thinking
    agents from the fleet GPS reference via buffered suspend/resume
    notes; both stances serve the workload to completion;
  * the real engine serves the same grammar (hold and drop);
  * with no think time every retention is inert: the sim serves the same
    run under each, and the engine matches the frozen reference engine;
  * under think time, ``drop`` evicts less than ``hold``, ``hold``
    escalates instead of wedging (sim fleet and real engine), and a
    think-time fleet replays bit-identically.
"""

import jax
import numpy as np
import pytest
from _engine_agents import _snapshot, synth_agents
from hypothesis import given, settings
from hypothesis import strategies as st
from test_event_conformance import assert_conformant_stream

from repro.api import (
    AgentRequeued,
    AgentResumed,
    AgentService,
    AgentSpec,
    AgentSuspended,
    EngineBackend,
    FaultPlan,
    SimBackend,
    StageCompleted,
    specs_from_closed_loop,
)
from repro.configs import get_config
from repro.core import InferenceSpec, agent_cost, make_scheduler
from repro.core.virtual_time import GlobalVirtualClock
from repro.engine import ReferenceServeEngine, ServeEngine
from repro.models import Model
from repro.sim import ClusterSim, SimAgent

RETENTIONS = ("hold", "spill", "drop")


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("granite-3-2b").reduced(vocab=256)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


class ScriptedSession:
    """Deterministic closed-loop callback: fixed follow-up stages, each
    preceded by a fixed think delay (0.0 = no suspension)."""

    def __init__(self, stages, delays):
        assert len(stages) == len(delays)
        self.stages = [list(s) for s in stages]
        self.delays = list(delays)
        self.i = 0
        self.last_resume_delay = None

    def __call__(self, outcome):
        if self.i >= len(self.stages):
            return None
        self.last_resume_delay = self.delays[self.i]
        stage = self.stages[self.i]
        self.i += 1
        return stage


def _specs(raw):
    """raw: [(arrival, [stage0, stage1, ...], [delay1, ...])] where each
    stage is [(p, d), ...] and delay k precedes follow-up stage k."""
    specs = []
    for arrival, stages, delays in raw:
        first, rest = stages[0], stages[1:]
        specs.append(AgentSpec(
            stages=[[InferenceSpec(p, d) for p, d in first]],
            arrival=float(arrival),
            next_stage=ScriptedSession(
                [[InferenceSpec(p, d) for p, d in s] for s in rest],
                delays,
            ),
        ))
    return specs


def _demands(raw_agent):
    _, stages, _ = raw_agent
    return [d for stage in stages for _, d in stage]


# agents: staggered arrivals, 1-3 stages of 1-2 inferences, think delays
# in {0} U [0.3, 4.0] before each follow-up stage
think_workload = st.lists(
    st.tuples(
        st.floats(min_value=0.0, max_value=10.0),
        st.lists(
            st.lists(
                st.tuples(
                    st.integers(min_value=40, max_value=250),
                    st.integers(min_value=5, max_value=50),
                ),
                min_size=1, max_size=2,
            ),
            min_size=1, max_size=3,
        ),
        st.lists(
            st.one_of(
                st.just(0.0),
                st.floats(min_value=0.3, max_value=4.0),
            ),
            min_size=2, max_size=2,
        ),
    ).map(lambda t: (t[0], t[1], t[2][: max(0, len(t[1]) - 1)])),
    min_size=1, max_size=5,
)


# ------------------------------------------------------- sim grammar fuzz


@given(
    think_workload,
    st.sampled_from([700.0, 4000.0]),          # pressure / roomy
    st.sampled_from(RETENTIONS),
)
@settings(max_examples=20, deadline=None)
def test_sim_suspension_grammar_fuzz(raw, m, retention):
    svc = AgentService(SimBackend(
        "justitia", total_kv=m, token_events=True,
        suspend_retention=retention,
    ))
    handles = svc.submit_many(_specs(raw))
    res = svc.drain()
    assert len(res.finish) == len(raw)
    assert res.metrics["suspensions"] == res.metrics["resumes"]
    expect_susp = sum(
        sum(1 for d in delays if d > 0.0) for _, _, delays in raw
    )
    assert res.metrics["suspensions"] == expect_susp
    for h, raw_agent in zip(handles, raw):
        assert_conformant_stream(h, token_demands=_demands(raw_agent))
        n_susp = sum(isinstance(e, AgentSuspended) for e in h.events)
        assert n_susp == sum(1 for d in raw_agent[2] if d > 0.0)


def test_suspended_holds_no_decode_slot():
    """During think time the agent is in neither running nor swapped: a
    competing agent admitted mid-think sees the full pool (minus held KV
    under ``hold``)."""
    svc = AgentService(SimBackend(
        "justitia", total_kv=500.0, suspend_retention="drop",
    ))
    svc.submit(AgentSpec(
        stages=[[InferenceSpec(200, 20)]], arrival=0.0,
        next_stage=ScriptedSession([[InferenceSpec(200, 20)]], [5.0]),
    ))
    # arrives mid-think; with the thinker's KV dropped, the 400-token
    # prompt fits a 500-token pool without swapping anyone
    svc.submit(AgentSpec(stages=[[InferenceSpec(400, 10)]], arrival=2.0))
    res = svc.drain()
    assert set(res.finish) == {0, 1}
    assert res.swaps == 0
    assert res.metrics["suspensions"] == 1


def test_retention_observables_sim():
    """hold pins KV (held_peak > 0) and escalates under pressure;
    drop pins nothing and never escalates."""
    raw = [
        (0.5 * i,
         [[(180, 30)], [(180, 30)], [(180, 30)]],
         [2.0, 2.0])
        for i in range(6)
    ]
    out = {}
    for retention in RETENTIONS:
        svc = AgentService(SimBackend(
            "justitia", total_kv=700.0, suspend_retention=retention,
        ))
        svc.submit_many(_specs(raw))
        out[retention] = svc.drain()
    for retention, res in out.items():
        assert len(res.finish) == len(raw), retention
        assert res.metrics["suspensions"] == 12, retention
    assert out["hold"].metrics["held_peak"] > 0.0
    assert out["hold"].metrics["suspend_spills"] > 0, (
        "pressure never escalated held KV — the cell is not contended"
    )
    assert out["drop"].metrics["held_peak"] == 0.0
    assert out["drop"].metrics["suspend_spills"] == 0


# --------------------------------------------------- failover interleaving


def _fleet(plan=None, accrual=True, **kw):
    fleet_kw = {}
    if plan is not None:
        fleet_kw.update(fault_plan=plan, watchdog_timeout=1.0,
                        watchdog_retries=1)
    if not accrual:
        fleet_kw["think_time_accrual"] = False
    return AgentService.sim(
        "justitia", replicas=2, router="round_robin",
        total_kv=3000.0, token_events=True, **fleet_kw, **kw,
    )


@given(
    st.floats(min_value=2.0, max_value=6.0),      # crash time
    st.floats(min_value=3.0, max_value=9.0),      # think time
    st.booleans(),                                # think-time accrual
)
@settings(max_examples=10, deadline=None)
def test_failover_suspension_grammar_fuzz(crash_at, think, accrual):
    """Suspended/Resumed/Requeued interleavings through a replica crash
    keep the grammar: balanced suspensions per agent, no event while
    suspended, exactly one resume per suspension even for agents whose
    replica died mid-think."""
    svc = _fleet(FaultPlan().crash(0, crash_at), accrual=accrual)
    specs = [
        AgentSpec(
            stages=[[InferenceSpec(80, 20)]], arrival=0.4 * i,
            next_stage=ScriptedSession([[InferenceSpec(60, 15)]], [think]),
        )
        for i in range(4)
    ]
    handles = svc.submit_many(specs)
    res = svc.drain()
    assert len(res.finish) == 4
    assert res.metrics["suspensions"] == res.metrics["resumes"]
    for h in handles:
        assert_conformant_stream(
            h, expect_replica=True, allow_requeue=True,
        )
        n_susp = sum(isinstance(e, AgentSuspended) for e in h.events)
        n_res = sum(isinstance(e, AgentResumed) for e in h.events)
        assert n_susp == n_res, (
            f"agent {h.agent_id}: {n_susp} suspensions, {n_res} resumes"
        )


@given(
    st.integers(min_value=0, max_value=10_000),
    st.booleans(),                                # think-time accrual
)
@settings(max_examples=8, deadline=None)
def test_concurrent_suspension_bit_identical(seed, accrual):
    """Concurrent advancement reproduces suspension/resume streams
    event-for-event: the same scripted think workload through a crash
    fleet yields identical typed event sequences with fleet_workers=2."""
    rng = np.random.default_rng(seed)
    raw = [
        (float(rng.uniform(0.0, 4.0)),
         [[(int(rng.integers(60, 200)), int(rng.integers(10, 40)))]
          for _ in range(int(rng.integers(1, 4)))],
         None)
        for _ in range(int(rng.integers(2, 6)))
    ]
    raw = [
        (a, stages,
         [float(rng.choice([0.0, 1.5, 3.0])) for _ in stages[1:]])
        for a, stages, _ in raw
    ]
    streams = []
    for workers in (None, 2):
        svc = _fleet(FaultPlan().crash(0, 3.0), accrual=accrual,
                     fleet_workers=workers)
        handles = svc.submit_many(_specs(raw))
        res = svc.drain()
        streams.append((
            [[(type(e).__name__, e.time, e.replica)
              for e in h.events] for h in handles],
            res.jct, res.event_counts, res.metrics["suspensions"],
        ))
    assert streams[0] == streams[1]


def test_suspended_on_dead_replica_resumes_once_on_survivor():
    """The tentpole failover contract, deterministically: agents thinking
    on the crashed replica resume EXACTLY ONCE — the resume lands before
    the requeue, the remaining work runs on the survivor, and none of it
    starts before the think deadline."""
    svc = _fleet(FaultPlan().crash(0, 4.0))
    handles = [
        svc.submit(AgentSpec(
            stages=[[InferenceSpec(60, 15)]], arrival=float(i) * 0.2,
            next_stage=ScriptedSession([[InferenceSpec(50, 10)]], [6.0]),
        ))
        for i in range(4)
    ]
    res = svc.drain()
    assert len(res.finish) == 4
    assert res.metrics["replica_failures"] == 1
    assert res.metrics["agents_requeued"] >= 1
    requeued = 0
    for h in handles:
        assert_conformant_stream(h, expect_replica=True, allow_requeue=True)
        evs = h.events
        susp = [e for e in evs if isinstance(e, AgentSuspended)]
        resm = [e for e in evs if isinstance(e, AgentResumed)]
        reqs = [e for e in evs if isinstance(e, AgentRequeued)]
        assert len(susp) == 1 and len(resm) == 1, (
            f"agent {h.agent_id}: resume not exactly-once "
            f"({len(susp)} suspensions, {len(resm)} resumes)"
        )
        if not reqs:
            continue
        requeued += 1
        # the victim was mid-think when its replica died: resume precedes
        # the requeue in emission order, the requeue lands on the
        # survivor, and nothing runs before the think deadline
        assert evs.index(resm[0]) < evs.index(reqs[0])
        assert reqs[0].replica != reqs[0].from_replica
        until = susp[0].until
        after = evs[evs.index(reqs[0]):]
        assert all(e.time >= until - 1e-9 for e in after), (
            f"agent {h.agent_id}: survivor ran work before the think "
            f"deadline {until}"
        )
        assert all(e.replica == reqs[0].replica for e in after)
        assert any(isinstance(e, StageCompleted) for e in after), (
            f"agent {h.agent_id}: no follow-up stage on the survivor"
        )
    assert requeued >= 1, "crash victimized no thinking agent"


def test_global_clock_carries_fj_through_suspended_failover():
    """F_j is one-shot across a suspended agent's migration: the virtual
    finish recorded before the crash survives ``fail_replica`` +
    ``migrate``, and suspend/resume notes for dead replicas are no-ops."""
    gvt = GlobalVirtualClock([1000.0, 1000.0])
    gvt.register(0, 1, 0.0, 300.0)
    gvt.register(1, 2, 0.0, 300.0)
    gvt.reconcile(1.0)
    f1 = gvt.virtual_finish[1]
    gvt.note_suspend(0, 1, 2.0)           # thinking when the crash hits
    orphans = gvt.fail_replica(0)
    assert orphans == []                  # arrival already reconciled
    gvt.note_suspend(0, 1, 2.5)           # dead replica: must be a no-op
    gvt.note_resume(0, 1, 3.0)
    carried = gvt.migrate(1, 1, 8.0, 150.0)
    assert carried == f1
    gvt.reconcile(10.0)
    assert gvt.virtual_finish[1] == f1    # never overwritten
    assert gvt.replica_of[1] == 1


def test_think_time_accrual_modes():
    """Equinox stance vs paper stance: with accrual disabled the fleet
    routes deactivate/reactivate notes through the global clock; both
    modes complete the same agent set and record the stance."""
    for accrual in (True, False):
        svc = _fleet(accrual=accrual)
        svc.submit_many([
            AgentSpec(
                stages=[[InferenceSpec(80, 20)]], arrival=float(i) * 0.3,
                next_stage=ScriptedSession(
                    [[InferenceSpec(60, 15)]], [3.0]),
            )
            for i in range(4)
        ])
        res = svc.drain()
        assert len(res.finish) == 4
        assert res.metrics["think_time_accrual"] is accrual
        assert res.metrics["suspensions"] == 4
        assert res.metrics["resumes"] == 4


# ------------------------------------------------------------------ engine


@pytest.mark.parametrize("retention", ["hold", "drop"])
def test_engine_suspension_conformance(tiny_model, retention):
    """The real engine serves the suspension grammar: think-time agents
    release their decode slots, resume on schedule, and complete."""
    model, params = tiny_model
    svc = AgentService(EngineBackend(
        model, params, "justitia",
        pool_tokens=256, block_size=16, max_batch=2, cache_len=64,
        token_scale=1, time_scale=1.0, suspend_retention=retention,
    ))
    handles = [
        svc.submit(AgentSpec(
            stages=[[InferenceSpec(20, 8)]], arrival=float(i),
            next_stage=ScriptedSession(
                [[InferenceSpec(16, 6)]], [2.0]),
        ))
        for i in range(3)
    ]
    res = svc.drain()
    assert len(res.finish) == 3
    assert res.metrics["suspensions"] == 3
    assert res.metrics["resumes"] == 3
    for h in handles:
        assert_conformant_stream(h, token_demands=[8, 6])
        assert sum(isinstance(e, AgentSuspended) for e in h.events) == 1
        susp = next(e for e in h.events if isinstance(e, AgentSuspended))
        resm = next(e for e in h.events if isinstance(e, AgentResumed))
        assert resm.time >= susp.until - 1e-9


# ------------------------------------------- retention without think time


def _static_sim_agents():
    """40 seeded one- and two-stage agents with no closed-loop session:
    nothing ever suspends."""
    rng = np.random.default_rng(11)
    out = []
    for i in range(40):
        stages = [
            [InferenceSpec(int(rng.integers(50, 400)),
                           int(rng.integers(10, 120)))]
            for _ in range(int(rng.integers(1, 3)))
        ]
        cost = agent_cost([s for st in stages for s in st])
        out.append(SimAgent(agent_id=i, arrival=float(rng.uniform(0, 20)),
                            stages=stages, predicted_cost=cost,
                            true_cost=cost))
    return out


@pytest.mark.parametrize("sched", ["justitia", "vtc", "vllm-fcfs"])
def test_sim_retention_inert_without_think_time(sched):
    """Suspension is strictly delay-gated: with no resume delay, every
    retention setting serves the workload bit-identically."""
    runs = {}
    for retention in RETENTIONS:
        res = ClusterSim(make_scheduler(sched, 1500.0, service_rate=30.0),
                         1500.0, suspend_retention=retention,
                         ).run(_static_sim_agents())
        assert res.suspensions == 0
        runs[retention] = (res.finish, res.jct, res.swaps, res.events)
    assert runs["spill"] == runs["hold"] and runs["drop"] == runs["hold"]


def test_engine_retention_inert_without_think_time(tiny_model):
    """The engine side of the same rule: with nothing suspending, every
    retention setting reproduces the frozen reference engine."""
    model, params = tiny_model
    for sched in ("justitia", "vtc"):
        kw = dict(pool_tokens=256, max_batch=4, cache_len=96)
        ref = ReferenceServeEngine(model, params,
                                   make_scheduler(sched, 256.0), **kw)
        for a in synth_agents(3, 10):
            ref.submit_agent(a)
        ref.run_until_idle(max_iters=100_000)
        for retention in RETENTIONS:
            eng = ServeEngine(model, params, make_scheduler(sched, 256.0),
                              suspend_retention=retention, **kw)
            for a in synth_agents(3, 10):
                eng.submit_agent(a)
            eng.run_until_idle(max_iters=100_000)
            eng.alloc.check_invariants()
            assert eng.metrics["suspensions"] == 0
            assert _snapshot(eng) == _snapshot(ref), (sched, retention)


# ------------------------------------------- retention under think time


def _think_fleet(retention: str):
    """A contended think-time fleet: 12 ``tooluse`` sessions on two sim
    replicas with the prefix cache on."""
    svc = AgentService.sim(
        "justitia", replicas=2, total_kv=1500.0, record_events=False,
        prefix_cache=True, suspend_retention=retention,
    )
    svc.submit_many(specs_from_closed_loop(
        np.random.default_rng(7), 12, 6.0, classes=("tooluse",)))
    return svc.drain()


def test_drop_evicts_less_than_hold_within_jct_spread():
    """Held think-time KV is the pressure source: ``drop`` must evict
    strictly less than ``hold`` (swap-outs plus hold->spill escalations,
    which pay the same restore), ``hold`` must escalate rather than wedge,
    and the worst JCT may differ across retentions by at most 3x."""
    runs = {r: _think_fleet(r) for r in RETENTIONS}
    for retention, res in runs.items():
        assert len(res.finish) == 12, retention
        assert res.metrics["suspensions"] >= 1, retention
        assert res.metrics["suspensions"] == res.metrics["resumes"]
    evictions = {r: res.swaps + res.metrics["suspend_spills"]
                 for r, res in runs.items()}
    assert evictions["drop"] < evictions["hold"]
    assert runs["hold"].metrics["suspend_spills"] >= 1
    worst = [max(res.jct.values()) for res in runs.values()]
    assert max(worst) <= 3.0 * min(worst)


def test_think_fleet_replays_bit_identical():
    a, b = _think_fleet("hold"), _think_fleet("hold")
    assert a.finish == b.finish and a.jct == b.jct
    for key in ("suspensions", "resumes", "suspend_spills", "held_peak"):
        assert a.metrics[key] == b.metrics[key], key


def test_engine_hold_escalates_pinned_kv_under_tight_pool(tiny_model):
    """On the real engine with a pool too tight for six thinking agents'
    held KV, ``hold`` escalates pinned KV instead of stalling, and both
    ``hold`` and ``drop`` serve every turn."""
    model, params = tiny_model
    spills = {}
    for retention in ("hold", "drop"):
        svc = AgentService.engine(
            model, params, "justitia", pool_tokens=96, max_batch=2,
            cache_len=96, token_scale=1, time_scale=1.0,
            record_events=False, prefix_cache=True,
            suspend_retention=retention,
        )
        for i in range(6):
            svc.submit(AgentSpec(
                stages=[[InferenceSpec(40, 12)]], arrival=0.2 * i,
                next_stage=ScriptedSession([[InferenceSpec(40, 12)]] * 3,
                                           [3.0] * 3),
                predicted_cost=200.0, true_cost=200.0,
            ))
        res = svc.drain()
        assert len(res.finish) == 6, retention
        assert res.metrics["suspensions"] == res.metrics["resumes"] == 18
        spills[retention] = res.metrics["suspend_spills"]
    assert spills["hold"] >= 1
    assert spills["drop"] == 0
