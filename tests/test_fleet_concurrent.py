"""Concurrent fleet advancement + load-triggered work stealing (PR 10).

The contract under test: ``fleet_workers > 1`` is purely a wall-clock
knob — same plan, same workload, same steal configuration must produce an
event-for-event bit-identical run (orders, timestamps, JCTs, global-clock
sequence assignment) to the sequential lockstep loop, because the only
difference is that each slice's children step on a thread pool and their
buffered events are replayed in child-index order.  Work stealing must
only ever migrate queued, never-admitted, never-suspended agents, and the
``least_loaded`` router must normalize its live-agent counts by replica
capacity.  Streaming mode (no per-agent results kept) must hold the same
identity while compaction keeps the tracked state bounded.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.api import AgentService, FaultPlan
from repro.api.backend import AgentSpec, InferenceSpec, SimBackend
from repro.api.replicated import ReplicatedBackend
from repro.api.workload import specs_from_closed_loop
from repro.core.virtual_time import GlobalVirtualClock


# ------------------------------------------------------------- helpers


class RawTape:
    """Duck-typed fleet listener recording every forwarded callback as an
    exact ``(event, agent_id, args, t, replica)`` tuple — the raw global
    stream whose order and timestamps the bit-identity property compares.
    """

    _EVENTS = (
        "on_arrival", "on_admit", "on_swap_out", "on_swap_in", "on_token",
        "on_prefix_hit", "on_admission_deferred", "on_stage_complete",
        "on_suspend", "on_resume", "on_agent_complete", "on_requeued",
        "on_replica_failed", "on_replica_recovered",
    )

    def __init__(self):
        self.events = []

    def __getattr__(self, name):
        if name in self._EVENTS:
            def record(agent_id, *args, replica=None):
                # last positional is the timestamp by channel convention
                self.events.append((name, agent_id, args, replica))
            return record
        raise AttributeError(name)


def _specs(raw):
    return [
        AgentSpec(
            stages=[[InferenceSpec(p, d) for p, d in stage]
                    for stage in stages],
            arrival=float(arr),
        )
        for arr, stages in raw
    ]


workload_strategy = st.lists(
    st.tuples(
        st.floats(min_value=0.0, max_value=8.0),
        st.lists(
            st.lists(
                st.tuples(
                    st.integers(min_value=40, max_value=300),   # prefill
                    st.integers(min_value=5, max_value=60),     # decode
                ),
                min_size=1, max_size=2,
            ),
            min_size=1, max_size=2,
        ),
    ),
    min_size=2, max_size=8,
)


def _fleet(n=3, *, total_kv=900.0, plan=None, **kw):
    children = [
        SimBackend("justitia", total_kv=total_kv, token_events=True)
        for _ in range(n)
    ]
    return ReplicatedBackend(
        children, router="round_robin", fault_plan=plan, **kw
    )


def _raw_run(raw, *, plan=None, watchdog=None, **kw):
    fleet = _fleet(
        plan=plan, watchdog_timeout=watchdog, **kw
    )
    tape = RawTape()
    fleet.set_listener(tape)
    for aid, spec in enumerate(_specs(raw)):
        fleet.submit(spec, aid)
    fleet.run(4.0)
    fleet.run(40.0)
    res = fleet.drain()
    order = fleet.pampering_order()
    fleet.close()
    return tape.events, res, order


# ------------------------------------------- bit-identity property tests


@given(workload_strategy)
@settings(max_examples=10, deadline=None)
def test_concurrent_raw_stream_bit_identical(raw):
    """Concurrent advancement replays the sequential loop's exact global
    event stream — same events, same order, same timestamps, same serving
    replicas — with and without a fault plan, and the reconciled
    pampering order (global F_j sequence assignment) matches too."""
    for plan, wd in [(None, None), (FaultPlan().crash(0, 1.5), 2.0)]:
        seq_ev, seq_res, seq_ord = _raw_run(raw, plan=plan, watchdog=wd)
        con_ev, con_res, con_ord = _raw_run(
            raw, plan=plan, watchdog=wd, fleet_workers=3
        )
        assert con_ev == seq_ev
        assert con_res.jct == seq_res.jct
        assert con_res.finish == seq_res.finish
        assert con_ord == seq_ord


@given(workload_strategy)
@settings(max_examples=6, deadline=None)
def test_concurrent_with_steal_bit_identical(raw):
    """The steal configuration slices both modes at the same interval
    targets, so sequential-with-steal and concurrent-with-steal agree
    event for event (including the AgentRequeued migrations)."""
    kw = dict(steal_threshold=1.3, steal_interval=0.5)
    seq_ev, seq_res, seq_ord = _raw_run(raw, **kw)
    con_ev, con_res, con_ord = _raw_run(raw, fleet_workers=3, **kw)
    assert con_ev == seq_ev
    assert con_res.jct == seq_res.jct
    assert con_ord == seq_ord


@given(st.integers(min_value=0, max_value=10_000),
       st.sampled_from([True, False]))
@settings(max_examples=6, deadline=None)
def test_concurrent_closed_loop_and_suspend_identical(seed, accrual):
    """Service-level identity across closed-loop sessions (in-band
    advancement during concurrent slices) and think-time suspensions,
    under both GPS accrual stances."""

    def run(**fleet):
        rng = np.random.default_rng(seed)
        specs = specs_from_closed_loop(
            rng, 8, 8.0, classes=("chat", "tooluse")
        )
        svc = AgentService.sim(
            "justitia", replicas=2, total_kv=768.0, token_events=True,
            think_time_accrual=accrual, **fleet,
        )
        handles = [svc.submit(s) for s in specs]
        svc.run(5.0)
        res = svc.drain()
        streams = {
            h.agent_id: [
                (type(e).__name__, e.time, getattr(e, "replica", None))
                for e in h.events
            ]
            for h in handles
        }
        return res, streams

    seq_res, seq_streams = run()
    con_res, con_streams = run(fleet_workers=2)
    assert con_streams == seq_streams
    assert con_res.jct == seq_res.jct
    assert con_res.event_counts == seq_res.event_counts


# --------------------------------------------------------- work stealing


@given(st.integers(min_value=0, max_value=10_000))
@settings(max_examples=8, deadline=None)
def test_steal_never_migrates_admitted_or_suspended(seed):
    """Every stolen agent was queued and cold at the moment of the steal:
    no RequestAdmitted / AgentSuspended event for it precedes its
    AgentRequeued timestamp, and completions are conserved."""
    rng = np.random.default_rng(seed)
    raw = [
        (
            float(rng.uniform(0.0, 3.0)),
            [[(int(rng.integers(80, 300)), int(rng.integers(10, 50)))]
             for _ in range(int(rng.integers(1, 3)))],
        )
        for _ in range(int(rng.integers(6, 14)))
    ]
    tape_events, res, _ = _raw_run(
        raw, total_kv=400.0, steal_threshold=1.2, steal_interval=0.25,
        fleet_workers=3,
    )
    steal_t = {}
    for name, aid, args, _rep in tape_events:
        if name == "on_requeued":
            steal_t.setdefault(aid, args[-1])   # first migration time
    for name, aid, args, _rep in tape_events:
        if aid in steal_t and name in ("on_admit", "on_suspend"):
            assert args[-1] >= steal_t[aid] - 1e-9, (
                f"agent {aid} had {name} at {args[-1]} before its steal "
                f"at {steal_t[aid]}"
            )
    assert len(res.finish) == len(raw)


def test_steal_threshold_validation():
    with pytest.raises(ValueError, match="steal_threshold"):
        _fleet(steal_threshold=1.0)
    with pytest.raises(ValueError, match="steal_interval"):
        _fleet(steal_threshold=1.5, steal_interval=0.0)
    with pytest.raises(ValueError, match="replicated fleet"):
        AgentService.sim("justitia", replicas=1, fleet_workers=2)


def test_steal_carries_virtual_finish():
    """A steal's clock surgery: an un-reconciled pending arrival moves
    wholesale; a reconciled one keeps its recorded F_j (the pampering
    order cannot change) while its GPS share leaves the source clock."""
    g = GlobalVirtualClock([10.0, 10.0])
    g.register(0, 1, 0.0, 50.0)
    g.register(0, 2, 1.0, 50.0)
    # agent 1 reconciled, agent 2 still pending at steal time
    g.reconcile(0.5)
    f1 = g.virtual_finish[1]
    assert g.steal(1, 0, 1, 1.0, 50.0) == pytest.approx(f1)
    assert g.steal(2, 0, 1, 1.0, 50.0) is None
    snap = g.reconcile(2.0)
    assert g.virtual_finish[1] == pytest.approx(f1)   # carried, not redone
    assert g.replica_of[1] == 1 and g.replica_of[2] == 1
    assert snap.time == 2.0
    with pytest.raises(ValueError, match="dead"):
        g.fail_replica(0)
        g.steal(2, 0, 1, 3.0, 50.0)


def test_backend_cancel_only_never_admitted():
    """Backend.cancel is the authoritative steal gate: queued whole-stage
    agents withdraw silently, anything ever admitted refuses."""
    b = SimBackend("justitia", total_kv=200.0)
    b.submit(AgentSpec(stages=[[InferenceSpec(50, 20)]], arrival=5.0), 0)
    b.submit(AgentSpec(stages=[[InferenceSpec(50, 20)]], arrival=0.0), 1)
    assert b.cancel(0)            # still in the arrival heap
    assert not b.cancel(0)        # already gone
    b.run(0.5)                    # agent 1 admitted and decoding
    assert not b.cancel(1)
    res = b.drain()
    assert set(res.finish) == {1}


# ------------------------------------------------- least_loaded satellite


def test_least_loaded_normalizes_by_capacity():
    """2:1 capacity fleet, 6 far-future agents: the capacity-normalized
    router places 4:2 (proportional), where the raw-count router used to
    alternate 3:3 and overload the small replica."""
    children = [
        SimBackend("justitia", total_kv=1024.0),
        SimBackend("justitia", total_kv=512.0),
    ]
    fleet = ReplicatedBackend(children, router="least_loaded")
    assert fleet.virtual_capacities[0] == 2 * fleet.virtual_capacities[1]
    for aid in range(6):
        fleet.submit(
            AgentSpec(stages=[[InferenceSpec(60, 20)]], arrival=1e6), aid
        )
    picks = [fleet.assignment[a] for a in range(6)]
    assert picks == [0, 1, 0, 0, 1, 0]
    assert fleet.live_agents == [4, 2]


def test_least_loaded_homogeneous_unchanged():
    """Equal capacities: normalization divides by a constant, so the
    placement sequence is the classic fewest-live-agents alternation."""
    children = [SimBackend("justitia", total_kv=512.0) for _ in range(3)]
    fleet = ReplicatedBackend(children, router="least_loaded")
    for aid in range(6):
        fleet.submit(
            AgentSpec(stages=[[InferenceSpec(60, 20)]], arrival=1e6), aid
        )
    assert [fleet.assignment[a] for a in range(6)] == [0, 1, 2, 0, 1, 2]


# ------------------------------------------- watchdog diagnostics satellite


def test_queue_depth_snapshot_labels_dead_replicas():
    """After a failover the diagnostic snapshot reports live replicas'
    in-flight counts and labels the dead one explicitly instead of
    counting its stranded queue as drainable backlog."""
    plan = FaultPlan().crash(0, 1.0)
    fleet = _fleet(plan=plan, watchdog_timeout=0.5, watchdog_retries=1)
    for aid, spec in enumerate(_specs(
        [(0.0, [[(200, 40)]]), (0.1, [[(200, 40)]]), (0.2, [[(200, 40)]])]
    )):
        fleet.submit(spec, aid)
    fleet.run(10.0)
    assert fleet.dead_replica_indices == (0,)
    depths = fleet._queue_depths()
    assert depths[0] == "dead"
    for k in (1, 2):
        assert isinstance(depths[k], int)
    fleet.drain()


# ------------------------------------------------------- streaming mode


def test_streaming_mode_drops_per_agent_state():
    """retain_agents=False + retain_results=False: per-agent fleet and
    sim bookkeeping drains to zero once everything completes and
    compact() has swept the clock — the 1M-agent bench's memory gate in
    miniature."""
    children = [
        SimBackend("justitia", total_kv=512.0, retain_results=False)
        for _ in range(2)
    ]
    fleet = ReplicatedBackend(
        children, router="round_robin", retain_agents=False,
        fleet_workers=2,
    )
    done = []
    class Tap:
        def on_agent_complete(self, aid, t, replica=None):
            done.append(aid)
        def __getattr__(self, name):
            if name.startswith("on_"):
                return lambda *a, **k: None
            raise AttributeError(name)
    fleet.set_listener(Tap())
    n = 40
    for aid in range(n):
        fleet.submit(
            AgentSpec(stages=[[InferenceSpec(60, 10)]],
                      arrival=0.05 * aid), aid,
        )
    fleet.run(30.0)
    fleet.compact(fleet.now)
    assert len(done) == n
    assert not fleet._specs and not fleet._arrival0 and not fleet.assignment
    assert not fleet.global_clock.virtual_finish
    assert all(not c.sim._by_id for c in fleet.children)
    assert not fleet._compact_done
    fleet.close()


def _paced_specs(seed: int, n: int, *, window: float, stages: int = 2):
    rng = np.random.default_rng(seed)
    return [
        AgentSpec(
            stages=[[InferenceSpec(int(rng.integers(120, 400)),
                                   int(rng.integers(20, 80)))]
                    for _ in range(stages)],
            arrival=float(rng.uniform(0.0, window)),
        )
        for _ in range(n)
    ]


def test_heterogeneous_fleet_wide_replicas_serve_more():
    """A 2:1 mixed-capacity fleet under ``least_loaded`` with stealing
    armed: the wide replicas complete strictly more agents than the narrow
    ones, and the concurrent run matches the sequential one, reconciled
    virtual times included."""
    runs = []
    for workers in (None, 4):
        children = [SimBackend("justitia", total_kv=m)
                    for m in (2400.0, 2400.0, 1200.0, 1200.0)]
        fleet = ReplicatedBackend(
            children, router="least_loaded", seed=7, fleet_workers=workers,
            steal_threshold=1.3, steal_interval=0.5,
        )
        for aid, spec in enumerate(_paced_specs(7, 48, window=10.0)):
            fleet.submit(spec, aid)
        fleet.run(200.0)
        res = fleet.drain()
        fleet.close()
        runs.append((res.finish, res.jct, res.metrics))
    (fin_a, jct_a, met_a), (fin_b, jct_b, met_b) = runs
    assert fin_a == fin_b and jct_a == jct_b
    assert met_a["virtual_times"] == met_b["virtual_times"]
    served = [row["agents"] for row in met_b["per_replica"]]
    assert served[0] + served[1] > served[2] + served[3]


def _streaming_run(workers, n: int = 2000, seed: int = 7):
    """Pace ``n`` one-stage agents through a streaming fleet (children
    keep no results, the fleet keeps no agents) at 60% of its capacity,
    compacting behind a 10 s horizon after every batch."""
    children = [SimBackend("justitia", total_kv=1200.0,
                           retain_results=False) for _ in range(4)]
    fleet = ReplicatedBackend(
        children, router="round_robin", seed=seed, fleet_workers=workers,
        steal_threshold=1.3, steal_interval=0.5, retain_agents=False,
    )
    tape = RawTape()
    fleet.set_listener(tape)
    rng = np.random.default_rng(seed)
    specs = [AgentSpec(stages=[[InferenceSpec(int(rng.integers(80, 240)),
                                              int(rng.integers(10, 40)))]])
             for _ in range(n)]
    mean_cost = np.mean([s.resolved_costs()[0] for s in specs])
    rate = 0.6 * sum(fleet.virtual_capacities) / mean_cost
    peak = 0
    for lo in range(0, n, 500):
        for aid in range(lo, lo + 500):
            specs[aid].arrival = aid / rate
            fleet.submit(specs[aid], aid)
        peak = max(peak, len(fleet._specs))
        fleet.run((lo + 500) / rate)
        fleet.compact(max(0.0, (lo + 500) / rate - 10.0))
    t = n / rate
    while sum(c.in_flight for c in children) > 0:
        t += 5.0
        fleet.run(t)
    fleet.drain()
    snap = fleet.compact(fleet.now)
    residual = (fleet._specs, fleet.assignment,
                fleet.global_clock.virtual_finish, fleet._compact_done,
                *(c.sim._by_id for c in children))
    assert not any(residual), "per-agent state left after the last compact"
    steals = len(fleet._steals)
    fleet.close()
    return tape.events, snap.virtual_times, steals, peak


def test_streaming_fleet_concurrent_matches_sequential():
    """Streaming mode with stealing armed: the concurrent fleet emits the
    sequential fleet's exact event stream and reconciled clock, completes
    every agent, and tracks a bounded window of agents, not all of them."""
    seq = _streaming_run(None)
    con = _streaming_run(4)
    assert con[:3] == seq[:3]
    done = [e for e in seq[0] if e[0] == "on_agent_complete"]
    assert len(done) == 2000
    assert max(seq[3], con[3]) < 1000


# ------------------------------------------------------- engine backend


@pytest.fixture(scope="module")
def tiny_model():
    import jax

    from repro.configs import get_config
    from repro.models import Model

    cfg = get_config("granite-3-2b").reduced(vocab=128)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def test_engine_fleet_concurrent_bit_identical(tiny_model):
    model, params = tiny_model

    def run(**fleet):
        svc = AgentService.engine(
            model, params, "justitia", replicas=2, router="round_robin",
            pool_tokens=256, block_size=16, max_batch=2, cache_len=64,
            token_scale=1, time_scale=1.0, **fleet,
        )
        for i in range(4):
            svc.submit(AgentSpec(
                stages=[[InferenceSpec(16, 20)], [InferenceSpec(12, 12)]],
                arrival=0.5 * i, name=f"a{i}",
            ))
        svc.run(3.0)
        res = svc.drain()
        return res

    seq = run()
    con = run(fleet_workers=2)
    assert con.jct == seq.jct
    assert con.event_counts == seq.event_counts
    assert con.metrics["fleet_workers"] == 2


# ------------------------------------------- one device per replica

#: serves a fixed workload on a 4-replica reduced engine fleet (advanced
#: on 4 threads) over ``argv[1]`` virtual CPU devices; prints where each
#: replica's params, cache and slot state live, and what the fleet served
_DEVICE_FLEET = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={sys.argv[1]}"
    )
    import json
    import jax
    from repro.api import AgentService
    from repro.api.backend import AgentSpec, InferenceSpec
    from repro.configs import get_config
    from repro.models import Model

    model = Model(get_config("granite-3-2b").reduced(vocab=128))
    params = model.init(jax.random.PRNGKey(0))
    svc = AgentService.engine(
        model, params, "justitia", replicas=4, router="round_robin",
        pool_tokens=256, block_size=16, max_batch=2, cache_len=64,
        token_scale=1, time_scale=1.0, fleet_workers=4,
    )
    for i in range(8):
        svc.submit(AgentSpec(
            stages=[[InferenceSpec(16 + i, 12)], [InferenceSpec(12, 8)]],
            arrival=0.5 * i, name=f"a{i}",
        ))
    res = svc.drain()
    placed = [
        [sorted(d.id for d in x.devices())
         for x in (e.params["embed"], e.cache["k"], e._d_state)]
        for e in (c.engine for c in svc.backend.children)
    ]
    print("RESULTS::" + json.dumps({
        "n_devices": len(jax.devices()),
        "placed": placed,
        "finish": {str(k): v for k, v in sorted(res.finish.items())},
        "tokens": {str(h.agent_id): list(map(int, h.tokens))
                   for h in svc.handles.values()},
    }))
""")


def _device_fleets(*n_devices: int) -> list[dict]:
    """Run ``_DEVICE_FLEET`` once per device count, side by side."""
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _DEVICE_FLEET, str(n)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        for n in n_devices
    ]
    out = []
    for proc in procs:
        stdout, stderr = proc.communicate(timeout=600)
        assert proc.returncode == 0, stderr[-3000:]
        line = [l for l in stdout.splitlines() if l.startswith("RESULTS::")]
        assert line, stdout[-2000:]
        out.append(json.loads(line[0][len("RESULTS::"):]))
    return out


def test_engine_fleet_one_device_per_replica():
    """On 4 (virtual CPU) devices, replica k's params, cache and slot
    state live on device k — and the fleet serves exactly what the same
    fleet serves with every replica on one device."""
    four, one = _device_fleets(4, 1)
    assert four["n_devices"] == 4 and one["n_devices"] == 1
    assert four["placed"] == [[[k]] * 3 for k in range(4)]
    assert one["placed"] == [[[0]] * 3] * 4
    assert len(four["finish"]) == 8
    assert four["finish"] == one["finish"]
    assert four["tokens"] == one["tokens"]
