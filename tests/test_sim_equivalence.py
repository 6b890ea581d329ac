"""Equivalence tests for the event-indexed simulator core.

The optimized ``repro.sim.ClusterSim`` must be *behaviour-preserving*
against the retained pre-rewrite core
(``repro.sim.reference.ReferenceClusterSim``): identical completion
orders, JCTs (within 1e-6; in practice the two cores are float-identical
by construction — see the stable decode form in both modules), swap and
event counts — across schedulers, pool sizes, and mixed arrival patterns.
Also covers the shared ``OrderedQueue``, the virtual-work GPS rewrite, the
admission-overshoot guard, incremental ``advance`` vs batch drain, and the
load-aware router fix.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    GpsAgent,
    InferenceSpec,
    OrderedQueue,
    agent_cost,
    gps_finish_times,
    gps_finish_times_fluid,
    make_scheduler,
)
from repro.sim import ClusterSim, SimAgent
from repro.sim.reference import ReferenceClusterSim

DECODE_RATE = 30.0

SCHEDS = ["justitia", "vtc", "srjf", "vllm-fcfs", "vllm-sjf", "parrot"]

# mixed arrival patterns: a burst at t=0, staggered onlines, random gaps
agents_strategy = st.lists(
    st.tuples(
        st.floats(min_value=0.0, max_value=120.0),       # arrival
        st.lists(                                        # stages
            st.lists(
                st.tuples(
                    st.integers(min_value=8, max_value=400),   # prefill
                    st.integers(min_value=8, max_value=300),   # decode
                ),
                min_size=1,
                max_size=3,
            ),
            min_size=1,
            max_size=2,
        ),
    ),
    min_size=1,
    max_size=12,
)


def _sim_agents(raw):
    agents = []
    for i, (arr, stages) in enumerate(raw):
        spec_stages = [
            [InferenceSpec(p, d) for p, d in stage] for stage in stages
        ]
        cost = agent_cost([s for stage in spec_stages for s in stage])
        agents.append(
            SimAgent(
                agent_id=i,
                arrival=float(arr),
                stages=spec_stages,
                predicted_cost=cost,
                true_cost=cost,
            )
        )
    return agents


class _CompletionOrder:
    """Listener capturing the exact agent-completion emission order."""

    def __init__(self):
        self.order = []

    def on_agent_complete(self, agent_id, t):
        self.order.append(agent_id)


@given(
    agents_strategy,
    st.sampled_from([1200.0, 4000.0, 16384.0]),
    st.sampled_from(SCHEDS),
)
@settings(max_examples=30, deadline=None)
def test_event_indexed_core_matches_reference(raw, m, sched):
    """Identical completion order + JCTs (1e-6) + swap/event counts."""
    la, lb = _CompletionOrder(), _CompletionOrder()
    new = ClusterSim(
        make_scheduler(sched, m, service_rate=DECODE_RATE), m, listener=la
    ).run(_sim_agents(raw))
    ref = ReferenceClusterSim(
        make_scheduler(sched, m, service_rate=DECODE_RATE), m, listener=lb
    ).run(_sim_agents(raw))
    assert set(new.finish) == set(ref.finish)
    assert la.order == lb.order, f"completion order diverged under {sched}"
    for k in ref.finish:
        assert abs(new.finish[k] - ref.finish[k]) < 1e-6
        assert abs(new.jct[k] - ref.jct[k]) < 1e-6
    assert new.swaps == ref.swaps
    assert new.events == ref.events


def test_equivalence_on_paper_workload_suite():
    """Seeded paper-suite workload (heavier than the property examples):
    the two cores must agree exactly, scheduler by scheduler."""
    from repro.workloads import arrivals_for_density, sample_mixed_suite

    def build():
        rng = np.random.default_rng(7)
        suite = sample_mixed_suite(rng, 50)
        arr = arrivals_for_density(rng, 50, 3)
        return [
            SimAgent(i, float(t), [list(s) for s in a.stages],
                     a.true_cost, a.true_cost)
            for i, (a, t) in enumerate(zip(suite, arr))
        ]

    for sched, m in [("justitia", 2000.0), ("vtc", 2000.0),
                     ("srjf", 8192.0), ("vllm-fcfs", 8192.0)]:
        new = ClusterSim(
            make_scheduler(sched, m, service_rate=DECODE_RATE), m
        ).run(build())
        ref = ReferenceClusterSim(
            make_scheduler(sched, m, service_rate=DECODE_RATE), m
        ).run(build())
        assert new.finish == pytest.approx(ref.finish, abs=1e-6)
        assert (new.swaps, new.events) == (ref.swaps, ref.events), sched
        # the optimized core does strictly fewer policy invocations
        assert new.key_evals <= ref.key_evals


# ------------------------------------------------- token-stream overlay


class _StreamCollector(_CompletionOrder):
    """Captures the full token/swap/completion emission sequence."""

    def __init__(self):
        super().__init__()
        self.stream = []

    def on_token(self, agent_id, rid, tok, t):
        self.stream.append(("tok", agent_id, rid, tok, t))

    def on_swap_out(self, agent_id, rid, t):
        self.stream.append(("out", agent_id, rid, t))

    def on_swap_in(self, agent_id, rid, t):
        self.stream.append(("in", agent_id, rid, t))


@given(
    agents_strategy,
    st.sampled_from([1200.0, 4000.0, 16384.0]),
    st.sampled_from(SCHEDS),
)
@settings(max_examples=20, deadline=None)
def test_token_streaming_inert_and_identical_across_cores(raw, m, sched):
    """The ``token_events`` overlay must (a) leave completions/JCTs/swap
    and event counts BIT-IDENTICAL to the non-streaming run, and (b) make
    both cores emit the exact same token stream (ids, order, stamps)."""
    base = ClusterSim(
        make_scheduler(sched, m, service_rate=DECODE_RATE), m
    ).run(_sim_agents(raw))
    la, lb = _StreamCollector(), _StreamCollector()
    new = ClusterSim(
        make_scheduler(sched, m, service_rate=DECODE_RATE), m,
        listener=la, token_events=True,
    ).run(_sim_agents(raw))
    ref = ReferenceClusterSim(
        make_scheduler(sched, m, service_rate=DECODE_RATE), m,
        listener=lb, token_events=True,
    ).run(_sim_agents(raw))
    # (a) inert: bit-identical dynamics with streaming on
    assert new.jct == base.jct and new.finish == base.finish
    assert (new.swaps, new.events) == (base.swaps, base.events)
    # (b) lockstep: identical streams from both cores
    assert la.stream == lb.stream, f"token stream diverged under {sched}"
    assert la.order == lb.order
    # token counts per request sum to the decode demands
    per_rid: dict = {}
    for kind, _, rid, *_ in la.stream:
        if kind == "tok":
            per_rid[rid] = per_rid.get(rid, 0) + 1
    demands = sorted(
        d for _, stages in raw for stage in stages for _, d in stage
    )
    assert sorted(per_rid.values()) == demands


def test_token_streaming_invariant_to_advance_cadence():
    """The emitted token stream (ids AND stamps) must not depend on how
    often the driver polls ``advance`` — tokens catch up at event times,
    which horizon polling never adds or removes."""
    raw = [
        (float(i * 1.3), [[(120, 40), (90, 25)], [(60, 15)]])
        for i in range(8)
    ]
    m = 1500.0

    def run(horizons):
        lc = _StreamCollector()
        sim = ClusterSim(
            make_scheduler("justitia", m, service_rate=DECODE_RATE), m,
            listener=lc, token_events=True,
        )
        for a in sorted(
            _sim_agents(raw), key=lambda a: (a.arrival, a.agent_id)
        ):
            sim.submit(a)
        for h in horizons:
            sim.advance(h)
        sim.drain()
        return lc.stream

    batch = run(())
    assert batch == run(tuple(np.arange(0.9, 40.0, 0.9)))
    assert batch == run((3.0, 17.0, 23.0))


def test_closed_loop_stage_append_identical_across_cores():
    """Closed-loop lockstep: both cores emit ``on_stage_complete`` BEFORE
    the stage-exhaustion check, so a listener appending stages drives the
    same multi-turn continuation — with identical JCTs and streams."""

    class _Chainer(_StreamCollector):
        """Appends one extra stage per agent at its first stage boundary."""

        def __init__(self, sim_agents):
            super().__init__()
            self.by_id = {a.agent_id: a for a in sim_agents}
            self.chained: set = set()

        def on_stage_complete(self, agent_id, stage, t):
            self.stream.append(("stage", agent_id, stage, t))
            if agent_id not in self.chained:
                self.chained.add(agent_id)
                self.by_id[agent_id].stages.append(
                    [InferenceSpec(48, 12 + agent_id)]
                )

    m = 2000.0

    def agents():
        return _sim_agents(
            [(float(i), [[(100 + 10 * i, 20 + i)]]) for i in range(6)]
        )

    a_new, a_ref = agents(), agents()
    la, lb = _Chainer(a_new), _Chainer(a_ref)
    new = ClusterSim(
        make_scheduler("justitia", m, service_rate=DECODE_RATE), m,
        listener=la, token_events=True,
    ).run(a_new)
    ref = ReferenceClusterSim(
        make_scheduler("justitia", m, service_rate=DECODE_RATE), m,
        listener=lb, token_events=True,
    ).run(a_ref)
    assert new.finish == ref.finish and new.jct == ref.jct
    assert la.stream == lb.stream
    # every agent really served the appended second stage
    stages = [e for e in la.stream if e[0] == "stage"]
    assert sorted(e[1] for e in stages if e[2] == 1) == list(range(6))


def test_token_events_leave_closed_loop_jcts_identical():
    """Through the service facade, 300 closed-loop sessions (multi-turn
    chat and react loops whose stages are generated mid-run) served with
    token streaming off and on: the overlay must leave every finish time
    and JCT bit-identical."""
    from repro.api import AgentService, specs_from_closed_loop

    runs = []
    for stream in (False, True):
        specs = specs_from_closed_loop(np.random.default_rng(0), 300, 120.0)
        svc = AgentService.sim(
            "justitia", total_kv=16384.0, decode_rate=DECODE_RATE,
            record_events=False, token_events=stream,
        )
        svc.submit_many(specs)
        res = svc.drain()
        assert len(res.finish) == 300
        runs.append(res)
    off, on = runs
    assert on.event_counts.get("TokenGenerated", 0) > 0
    assert on.finish == off.finish and on.jct == off.jct


# ------------------------------------------------------------------- GPS


gps_strategy = st.lists(
    st.tuples(
        st.floats(min_value=0.0, max_value=50.0),     # arrival
        st.floats(min_value=0.5, max_value=500.0),    # cost
    ),
    min_size=1,
    max_size=40,
)


@given(gps_strategy, st.sampled_from([100.0, 1500.0, 8192.0]))
@settings(max_examples=40, deadline=None)
def test_gps_virtual_work_matches_fluid(raw, m):
    agents = [
        GpsAgent(i, float(a), float(c)) for i, (a, c) in enumerate(raw)
    ]
    fast = gps_finish_times(agents, m)
    fluid = gps_finish_times_fluid(agents, m)
    assert set(fast) == set(fluid)
    for k in fluid:
        assert fast[k] == pytest.approx(fluid[k], rel=1e-6, abs=1e-5), (
            f"agent {k}: virtual-work {fast[k]} vs fluid {fluid[k]}"
        )


# ----------------------------------------------------------- OrderedQueue


def test_ordered_queue_static_sorted_by_construction():
    q = OrderedQueue(lambda x: x, dynamic=False)
    for v in [5, 1, 4, 1.5, 9]:
        q.push(v)
    q.refresh()                       # no-op for static queues
    assert list(q) == [1, 1.5, 4, 5, 9]
    assert q.head_key() == 1
    assert [q.popleft() for _ in range(len(q))] == [1, 1.5, 4, 5, 9]
    assert q.sorts == 0
    assert q.key_evals == 5           # exactly once per push


def test_ordered_queue_dynamic_version_gated_resort():
    keys = {"a": 3, "b": 1, "c": 2}
    q = OrderedQueue(lambda x: (keys[x], x), dynamic=True)
    for x in "abc":
        q.push(x)
    q.refresh(version=10)
    assert list(q) == ["b", "c", "a"] and q.sorts == 1
    # same version, no pushes: the keys cannot have moved -> no sort
    q.refresh(version=10)
    assert q.sorts == 1
    # version moved: re-sort with fresh keys
    keys["a"] = 0
    q.refresh(version=11)
    assert list(q) == ["a", "b", "c"] and q.sorts == 2


def test_ordered_queue_grouped_repositions_only_dirty_groups():
    keys = {1: 10.0, 2: 20.0, 3: 30.0}

    def key_fn(item):
        gid, rid = item
        return (keys[gid], rid)

    q = OrderedQueue(key_fn, dynamic=True, group_fn=lambda it: it[0])
    q.push((1, 0))
    q.push((2, 1))
    q.push((3, 2))
    q.refresh()
    evals0 = q.key_evals
    assert [g for g, _ in q] == [1, 2, 3]
    # group 3's key drops below everyone: only its items re-key
    keys[3] = 5.0
    q.mark_dirty(3)
    q.refresh()
    assert [g for g, _ in q] == [3, 1, 2]
    assert q.key_evals == evals0 + 1  # exactly the one moved item
    # clean refresh: nothing dirty, nothing evaluated
    q.refresh()
    assert q.key_evals == evals0 + 1
    assert q.popleft() == (3, 2)
    assert [g for g, _ in q] == [1, 2]


def test_ordered_queue_tail_access_and_remove_static():
    """PR-4 engine running-set surface: worst-key access at the tail and
    O(log n) arbitrary removal via the cached key (static mode)."""
    q = OrderedQueue(lambda x: x, dynamic=False)
    for v in [5, 1, 4, 1.5, 9]:
        q.push(v)
    assert q.peek_right() == 9
    assert q.pop_right() == 9
    assert list(q) == [1, 1.5, 4, 5]
    q.remove(4)
    assert list(q) == [1, 1.5, 5]
    # removal interacts correctly with the dead popleft prefix
    assert q.popleft() == 1
    q.remove(5)
    assert list(q) == [1.5]
    assert q.pop_right() == 1.5
    assert not q
    # empty-queue guards, including after an uncompacted popleft prefix
    # (the tail slot is then a dead tombstone, not an item)
    with pytest.raises(IndexError):
        q.peek_right()
    with pytest.raises(IndexError):
        q.pop_right()
    q2 = OrderedQueue(lambda x: x, dynamic=False)
    q2.push(7)
    assert q2.popleft() == 7
    with pytest.raises(IndexError):
        q2.pop_right()
    with pytest.raises(ValueError):
        OrderedQueue(lambda x: x, dynamic=True).remove("missing")


def test_ordered_queue_tail_and_remove_grouped_and_dynamic():
    keys = {1: 10.0, 2: 20.0, 3: 30.0}

    def key_fn(item):
        gid, rid = item
        return (keys[gid], rid)

    q = OrderedQueue(key_fn, dynamic=True, group_fn=lambda it: it[0])
    a, b, c = (1, 0), (2, 1), (3, 2)
    for it in (a, b, c):
        q.push(it)
    q.refresh()
    assert q.peek_right() == c
    assert q.pop_right() == c            # group bookkeeping must shrink
    q.mark_dirty(3)                      # no-op: group 3 is gone
    q.refresh()
    assert list(q) == [a, b]
    q.remove(a)
    assert list(q) == [b]
    # grouped removal after a pending (unrefreshed) dirty mark still finds
    # the item at its cached-key position
    keys[2] = 5.0
    q.mark_dirty(2)
    q.remove(b)
    assert not q

    # plain dynamic mode: identity-scan removal
    qd = OrderedQueue(lambda x: x, dynamic=True)
    for v in (3, 1, 2):
        qd.push(v)
    qd.refresh()
    assert qd.peek_right() == 3
    qd.remove(2)
    qd.refresh()
    assert list(qd) == [1, 3]
    assert qd.pop_right() == 3


def test_grouped_queue_matches_full_resort_under_simulation():
    """Randomized: grouped invalidation must equal a full re-sort as long
    as only marked groups' keys move (the agent_keyed contract)."""
    rng = np.random.default_rng(3)
    keys = {g: float(rng.integers(0, 50)) for g in range(8)}

    def key_fn(item):
        gid, rid = item
        return (keys[gid], rid)

    grouped = OrderedQueue(key_fn, dynamic=True, group_fn=lambda it: it[0])
    plain = OrderedQueue(key_fn, dynamic=True)
    rid = 0
    for step in range(200):
        op = rng.random()
        if op < 0.4:
            item = (int(rng.integers(0, 8)), rid)
            rid += 1
            grouped.push(item)
            plain.push(item)
        elif op < 0.7 and len(grouped):
            g = int(rng.integers(0, 8))
            keys[g] += float(rng.integers(1, 10))
            grouped.mark_dirty(g)
        else:
            grouped.refresh()
            plain.refresh()          # plain: unconditional (version=None)
            assert list(grouped) == list(plain)
            if len(grouped):
                assert grouped.popleft() == plain.popleft()
    grouped.refresh()
    plain.refresh()
    assert list(grouped) == list(plain)


# ------------------------------------------------- admission + increments


def test_admission_never_overshoots_pool():
    """Satellite regression: an admission pass must not push occupancy
    past M (the fit check precedes ``running`` insertion)."""
    m = 2000.0
    agents = [
        SimAgent(i, i * 0.05, [[InferenceSpec(700, 200)] * 3], 100.0, 100.0)
        for i in range(12)
    ]
    sim = ClusterSim(
        make_scheduler("justitia", m, service_rate=DECODE_RATE), m
    )
    res = sim.run(agents)
    assert len(res.finish) == 12
    assert res.peak_occupancy <= m + 1e-6


def test_oversized_request_admitted_alone_documented_escape():
    """A request larger than the whole pool is admitted only when the pool
    is otherwise idle (the vLLM thrash escape) — and occupancy may then
    exceed M by design."""
    m = 500.0
    sim = ClusterSim(
        make_scheduler("justitia", m, service_rate=DECODE_RATE), m
    )
    res = sim.run(
        [SimAgent(0, 0.0, [[InferenceSpec(900, 50)]], 10.0, 10.0)]
    )
    assert 0 in res.finish
    assert res.peak_occupancy >= 900.0


@pytest.mark.parametrize("sched", ["justitia", "vtc", "srjf"])
def test_incremental_advance_matches_batch_drain(sched):
    """Results must be invariant to the advance() polling cadence — for
    dynamic policies too (regression: service crediting at advance
    horizons re-partitioned the accounting integral and near-tie VTC
    counter comparisons flipped with the polling frequency)."""
    rng = np.random.default_rng(5)
    raw = [
        (float(rng.uniform(0, 40)),
         [[(int(rng.integers(16, 200)), int(rng.integers(8, 120)))
           for _ in range(int(rng.integers(1, 3)))]])
        for _ in range(25)
    ]
    batch = ClusterSim(
        make_scheduler(sched, 2000.0, service_rate=DECODE_RATE), 2000.0
    ).run(_sim_agents(raw))

    for horizons in [
        (5.0, 11.0, 17.0, 42.0, 99.0),
        tuple(np.arange(0.9, 120.0, 0.9)),       # fine-grained polling
        tuple(np.arange(1.3, 120.0, 1.3)),
    ]:
        inc = ClusterSim(
            make_scheduler(sched, 2000.0, service_rate=DECODE_RATE), 2000.0
        )
        for a in sorted(
            _sim_agents(raw), key=lambda a: (a.arrival, a.agent_id)
        ):
            inc.submit(a)
        for horizon in horizons:
            inc.advance(horizon)
        res = inc.drain()
        assert res.jct == batch.jct, (sched, horizons[:3])
        assert res.finish == batch.finish
        assert res.swaps == batch.swaps


def test_oversized_jump_processes_arrivals_on_time():
    """The single-sequence saturation jump must stop at the next arrival
    (not skip it to the oversized sequence's finish), and incremental
    polling must match the one-shot drain in this regime too."""
    def run_sim(horizons):
        m = 500.0
        sim = ClusterSim(
            make_scheduler("vtc", m, service_rate=1.0),
            m, decode_rate=1.0, prefill_rate=100.0,
        )
        sim.submit(
            SimAgent(0, 0.0, [[InferenceSpec(900, 50)]], 100.0, 100.0)
        )
        sim.submit(SimAgent(1, 3.0, [[InferenceSpec(40, 5)]], 5.0, 5.0))
        listener = _CompletionOrder()
        sim.listener = listener
        for h in horizons:
            sim.advance(h)
        return sim.drain(), listener.order

    one_shot, order_a = run_sim(())
    polled, order_b = run_sim((2.0, 6.0, 11.0, 30.0))
    assert one_shot.jct == polled.jct
    assert one_shot.finish == polled.finish
    assert order_a == order_b
    # stall polls must not inflate the events metric or re-partition
    # service credits (regression: each advance() during the saturated
    # stall used to record a phantom event and credit at horizon times)
    fine, order_c = run_sim(tuple(np.arange(0.5, 40.0, 0.5)))
    assert fine.events == one_shot.events
    assert fine.jct == one_shot.jct
    assert order_c == order_a
    # the reference core agrees (same jump-to-arrival semantics)
    m = 500.0
    ref = ReferenceClusterSim(
        make_scheduler("vtc", m, service_rate=1.0),
        m, decode_rate=1.0, prefill_rate=100.0,
    ).run([
        SimAgent(0, 0.0, [[InferenceSpec(900, 50)]], 100.0, 100.0),
        SimAgent(1, 3.0, [[InferenceSpec(40, 5)]], 5.0, 5.0),
    ])
    assert ref.finish == one_shot.finish


def test_advance_horizon_not_overshot_by_saturation_escape():
    """Regression: the single-sequence-saturates-pool jump used to raise
    the clock past the advance() horizon, so a later online submission was
    clamped to the overshot clock and its JCT corrupted."""
    m = 100.0
    sim = ClusterSim(
        make_scheduler("justitia", m, service_rate=1.0),
        m, decode_rate=1.0, prefill_rate=4000.0,
    )
    # p + d > M: triggers the documented oversized escape, finishing ~10s
    sim.submit(SimAgent(0, 0.0, [[InferenceSpec(95, 10)]], 10.0, 10.0))
    sim.advance(6.0)
    assert sim.t == 6.0                     # horizon respected
    arrival = sim.submit(
        SimAgent(1, 6.5, [[InferenceSpec(10, 2)]], 1.0, 1.0)
    )
    assert arrival == 6.5                   # not clamped to an overshoot
    res = sim.drain()
    assert set(res.finish) == {0, 1}
    assert res.jct[1] == res.finish[1] - 6.5


def test_sim_advance_emits_completions_mid_run():
    """``advance`` really processes events: completions are observable
    before ``drain`` (what load-aware fleet routers rely on)."""
    sim = ClusterSim(
        make_scheduler("justitia", 4000.0, service_rate=DECODE_RATE), 4000.0
    )
    listener = _CompletionOrder()
    sim.listener = listener
    sim.submit(SimAgent(0, 0.0, [[InferenceSpec(100, 30)]], 5.0, 5.0))
    sim.submit(SimAgent(1, 0.0, [[InferenceSpec(100, 3000)]], 9.0, 9.0))
    assert sim.live_agents == 2
    sim.advance(10.0)                       # agent 0 finishes in ~1s
    assert listener.order == [0]
    assert sim.live_agents == 1
    res = sim.drain()
    assert listener.order == [0, 1]
    assert set(res.finish) == {0, 1}


def test_least_loaded_router_sees_sim_completions_mid_run():
    """ROADMAP follow-up: on the sim backend ``least_loaded`` used to
    degenerate to round-robin because completions were only reported at
    drain.  With the incremental sim the fleet's live-agent accounting
    drops mid-run, so a freed replica is preferred."""
    from repro.api import AgentService, AgentSpec

    svc = AgentService.sim(
        "justitia", replicas=2, router="least_loaded",
        total_kv=4000.0, decode_rate=DECODE_RATE,
    )
    # replica 0: long-running elephant; replica 1: quick mouse
    svc.submit(AgentSpec(stages=[[InferenceSpec(100, 3000)]], arrival=0.0))
    svc.submit(AgentSpec(stages=[[InferenceSpec(100, 30)]], arrival=0.0))
    svc.run(until=20.0)                     # the mouse finishes (~1s)
    backend = svc.backend
    assert backend.live_agents == [1, 0]    # completion observed mid-run
    assert backend.children[1].in_flight == 0
    # both next agents prefer the freed replica first, then balance
    svc.submit(AgentSpec(stages=[[InferenceSpec(50, 20)]], arrival=20.0))
    svc.submit(AgentSpec(stages=[[InferenceSpec(50, 20)]], arrival=20.0))
    assert backend.assignment[2] == 1
    assert backend.assignment[3] in (0, 1)  # tie after re-balancing
    res = svc.drain()
    assert len(res.finish) == 4
