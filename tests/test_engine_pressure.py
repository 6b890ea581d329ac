"""Swap-heavy engine tests pinning the device-resident hot path (PR 4)
against the frozen pre-rewrite oracle (``ReferenceServeEngine``), plus the
self-evicted-while-growing regression and listener event-ordering checks.

The workloads here keep the block pool tiny so the same agents swap out
and back in repeatedly — the regime where the rewrite's jitted slot
gather/scatter, O(log n) victim selection, and O(1) swapped-rid membership
all sit on the hot path.  A last test serves round after round of seeded
agents on the same two engines, under every queue policy of the oracle,
and compares them after every drain.
"""

import jax
import numpy as np
import pytest
from _engine_agents import _snapshot, synth_agents

from repro.configs import get_config
from repro.core import InferenceSpec, agent_cost, make_scheduler
from repro.engine import EngineAgent, ReferenceServeEngine, ServeEngine
from repro.models import Model

VOCAB = 256


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("granite-3-2b").reduced(vocab=VOCAB)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def mk_agent(rng, aid, n_inf, p, d, arrival=0, stages=1, cost=None):
    sts = []
    for _ in range(stages):
        sts.append(
            [(rng.integers(0, VOCAB, size=p), d) for _ in range(n_inf)]
        )
    specs = [InferenceSpec(p, d)] * (n_inf * stages)
    return EngineAgent(
        aid, arrival, sts, agent_cost(specs) if cost is None else cost
    )


class EventLog:
    """Duck-typed listener recording the full lifecycle stream.

    Token VALUES are dropped: batched/chunked prefill may differ from the
    reference in float low bits, which can flip an argmax tie — scheduling
    behaviour (what these tests pin) must not depend on sampled values.
    """

    def __init__(self, alloc=None):
        self.events = []
        self.alloc = alloc

    def _note(self, kind, *args):
        self.events.append((kind, args))
        if self.alloc is not None:
            self.alloc.check_invariants()

    def on_arrival(self, aid, t):
        self._note("arrival", aid, t)

    def on_admit(self, aid, rid, t):
        self._note("admit", aid, rid, t)

    def on_swap_out(self, aid, rid, t):
        self._note("swap_out", aid, rid, t)

    def on_swap_in(self, aid, rid, t):
        self._note("swap_in", aid, rid, t)

    def on_token(self, aid, rid, tok, t):
        self._note("token", aid, rid, None, t)

    def on_stage_complete(self, aid, stage, t):
        self._note("stage", aid, stage, t)

    def on_agent_complete(self, aid, t):
        self._note("done", aid, t)


def run_engine(cls, model, params, sched_name, agents, *, listener=None,
               **kw):
    kw.setdefault("pool_tokens", 320)
    kw.setdefault("max_batch", 4)
    kw.setdefault("cache_len", 128)
    sched = make_scheduler(sched_name, float(kw["pool_tokens"]))
    eng = cls(model, params, sched, listener=listener, **kw)
    for a in agents:
        eng.submit_agent(a)
    done = eng.run_until_idle(max_iters=100_000)
    eng.alloc.check_invariants()
    return eng, done


def pressure_agents(seed=0, n=4):
    """Agents whose concurrent KV demand is ~3x the 320-token pool."""
    rng = np.random.default_rng(seed)
    return [mk_agent(rng, i, 2, 40, 48, arrival=2 * i) for i in range(n)]


@pytest.mark.parametrize("sched_name", ["justitia", "vtc"])
def test_swap_heavy_pressure_matches_reference_and_orders_events(
    tiny_model, sched_name
):
    """Tiny pool, repeated swap cycles: the optimized engine must drain
    without stalling, keep allocator invariants at EVERY lifecycle event,
    emit a per-request event stream in legal order, and reproduce the
    reference engine's stream exactly (token values aside)."""
    model, params = tiny_model

    logs = {}
    engines = {}
    for cls in (ServeEngine, ReferenceServeEngine):
        log = EventLog()
        eng, done = run_engine(
            cls, model, params, sched_name, pressure_agents(),
            listener=log,
        )
        # checked at every event too, via EventLog.alloc in the next test
        assert set(done) == {0, 1, 2, 3}, cls.__name__
        logs[cls], engines[cls] = log, eng

    new, ref = engines[ServeEngine], engines[ReferenceServeEngine]
    # swap-heavy by construction
    assert new.metrics["swaps"] > 0
    assert new.alloc.swap_events > 0
    # identical completion iterations, clock, and counters
    assert new.completions == ref.completions
    assert new.now == ref.now
    for key in ("tokens", "prefills", "swaps", "decode_steps"):
        assert new.metrics[key] == ref.metrics[key], key
    # identical event streams (order AND stamps)
    assert logs[ServeEngine].events == logs[ReferenceServeEngine].events

    # per-request lifecycle legality on the optimized stream
    state = {}
    for kind, args in logs[ServeEngine].events:
        if kind not in ("admit", "swap_out", "swap_in", "token"):
            continue
        rid = args[1]
        prev = state.get(rid, "new")
        if kind == "admit":
            assert prev == "new", f"rid {rid} admitted twice"
            state[rid] = "running"
        elif kind == "swap_out":
            assert prev == "running", f"rid {rid} swapped out while {prev}"
            state[rid] = "swapped"
        elif kind == "swap_in":
            assert prev == "swapped", f"rid {rid} swapped in while {prev}"
            state[rid] = "running"
        else:  # token
            assert prev == "running", f"rid {rid} decoded while {prev}"


def test_pressure_run_holds_allocator_invariants_at_every_event(
    tiny_model
):
    """check_invariants (including the incremental used-token counter)
    must hold at every single lifecycle event of a swap-heavy run, not
    just at drain."""
    model, params = tiny_model
    sched = make_scheduler("justitia", 320.0)
    eng = ServeEngine(
        model, params, sched, pool_tokens=320, max_batch=4, cache_len=128
    )
    log = EventLog(alloc=eng.alloc)
    eng.listener = log
    for a in pressure_agents(seed=1):
        eng.submit_agent(a)
    done = eng.run_until_idle(max_iters=100_000)
    assert len(done) == 4
    assert eng.metrics["swaps"] > 0
    assert any(kind == "swap_out" for kind, _ in log.events)


def test_self_evicted_while_growing_regression(tiny_model):
    """An elephant agent (worst scheduler key) whose own token growth
    exhausts the pool must evict ITSELF and stop decoding that step —
    the O(1) swapped-rid membership check must behave exactly like the
    reference's linear scan (regression for engine.py's post-swap check).
    """
    model, params = tiny_model

    def agents():
        rng2 = np.random.default_rng(3)
        # elephant: huge predicted cost => worst Justitia key.  Both fit
        # the 6-block pool at admission (2 blocks each) but their combined
        # growth (2x 57 tokens) exhausts it mid-decode, so the append that
        # trips first evicts the elephant — sometimes while the elephant
        # itself is the sequence being grown (the self-eviction path).
        eleph = mk_agent(rng2, 0, 1, 16, 40, cost=1e9)
        mouse = mk_agent(rng2, 1, 1, 16, 40, arrival=1)
        return [eleph, mouse]

    results = {}
    for cls in (ServeEngine, ReferenceServeEngine):
        log = EventLog()
        eng, done = run_engine(
            cls, model, params, "justitia", agents(),
            listener=log, pool_tokens=96, max_batch=2, cache_len=128,
            block_size=16,
        )
        assert set(done) == {0, 1}
        results[cls] = (eng, log)

    new_eng, new_log = results[ServeEngine]
    ref_eng, ref_log = results[ReferenceServeEngine]
    # the elephant really was evicted while growing: a swap_out of agent 0
    # with both requests running and no admission in between
    swap_outs = [a for k, a in new_log.events if k == "swap_out"]
    assert any(a[0] == 0 for a in swap_outs), "elephant never self-evicted"
    # after its swap_out, agent 0 must emit no token until its swap_in
    seen_out = False
    for kind, args in new_log.events:
        if kind == "swap_out" and args[0] == 0:
            seen_out = True
        elif kind == "swap_in" and args[0] == 0:
            seen_out = False
        elif kind == "token" and args[0] == 0:
            assert not seen_out, "self-evicted request kept decoding"
    # and the whole stream matches the reference bit-for-bit
    assert new_log.events == ref_log.events
    assert new_eng.completions == ref_eng.completions


# --------------------------------------------------- chunked prefill regime


def test_prefill_chunked_matches_one_shot_prefill(tiny_model):
    """Model-level: the genuinely-chunked dense prefill path must produce
    the same logits and cache as one-shot prefill (lens-masked, mixed
    per-row lengths), including after a decode continuation."""
    import jax.numpy as jnp

    model, params = tiny_model
    rng = np.random.default_rng(11)
    lens = jnp.asarray([50, 37, 12], jnp.int32)
    toks = jnp.asarray(rng.integers(0, VOCAB, size=(3, 50)), jnp.int32)
    batch = {"tokens": toks, "lens": lens}
    lg1, c1 = model.prefill(params, batch, cache_len=96)
    lg2, c2 = model.prefill_chunked(params, batch, cache_len=96, chunk=16)
    assert (c1["kv_pos"] == c2["kv_pos"]).all()
    assert jnp.max(jnp.abs(lg1 - lg2)) < 1e-4
    nxt = jnp.argmax(lg1[:, -1:], -1).astype(jnp.int32)
    d1, _ = model.decode(params, c1, nxt, lens)
    d2, _ = model.decode(params, c2, nxt, lens)
    assert jnp.max(jnp.abs(d1 - d2)) < 1e-4


def test_chunked_prefill_engine_matches_reference_completions(tiny_model):
    """Engine-level: with prompts spanning several prefill chunks, both
    engines must agree on completions, clock, and counters.  (on_admit
    stamps legitimately differ in this regime: the optimized engine
    stamps at pass-start `now`, the reference at its retired mid-pass
    clock bump — see ROADMAP 'Engine hot path'.)"""
    model, params = tiny_model

    def agents():
        rng2 = np.random.default_rng(5)
        return [
            mk_agent(rng2, 0, 2, 100, 20),
            mk_agent(rng2, 1, 1, 70, 16, arrival=2),
            mk_agent(rng2, 2, 1, 90, 12, arrival=4),
        ]

    results = {}
    for cls in (ServeEngine, ReferenceServeEngine):
        eng, done = run_engine(
            cls, model, params, "justitia", agents(),
            pool_tokens=2048, max_batch=4, cache_len=128,
            prefill_chunk=32,
        )
        assert set(done) == {0, 1, 2}
        results[cls] = eng

    new, ref = results[ServeEngine], results[ReferenceServeEngine]
    assert new.metrics["prefills"] == ref.metrics["prefills"] == 4
    assert new.completions == ref.completions
    assert new.now == ref.now
    for key in ("tokens", "decode_steps", "swaps"):
        assert new.metrics[key] == ref.metrics[key], key


def test_run_until_slicing_matches_reference_with_prefill_cost(tiny_model):
    """Regression: a fused decode window must not run past ``run(until)``
    when the admission pass itself advanced the clock (multi-chunk
    prefill cost) — an online arrival submitted at the slice boundary
    must land at the same iteration on both engines."""
    model, params = tiny_model

    def drive(cls):
        rng = np.random.default_rng(9)
        sched = make_scheduler("justitia", 2048.0)
        eng = cls(model, params, sched, pool_tokens=2048, max_batch=4,
                  cache_len=128, prefill_chunk=32)
        eng.submit_agent(mk_agent(rng, 0, 1, 100, 24))
        for until in (5, 9, 14, 30):
            eng.run(until)
            assert eng.now >= until
        eng.submit_agent(mk_agent(rng, 1, 1, 40, 8, arrival=eng.now))
        done = eng.run_until_idle(max_iters=10_000)
        return eng.now, done

    assert drive(ServeEngine) == drive(ReferenceServeEngine)


# ------------------------------------------------ oracle over drain rounds


@pytest.mark.parametrize("sched_name", ["justitia", "vtc", "vllm-fcfs"])
def test_engine_matches_reference_across_drain_rounds(tiny_model, sched_name):
    """The same two engine instances serve round after round of seeded
    agents, each round submitted after the last one drained: under a roomy
    pool (no swaps) and a tight one (swap cycles), the optimized engine
    must match the frozen reference's completions, clock and counters
    after every drain, with the allocator's invariants intact."""
    model, params = tiny_model
    swapped = False
    for pool in (8192, 256):
        engines = [
            cls(model, params, make_scheduler(sched_name, float(pool)),
                pool_tokens=pool, max_batch=4, cache_len=96)
            for cls in (ServeEngine, ReferenceServeEngine)
        ]
        for rnd in range(2):
            for eng in engines:
                for a in synth_agents(rnd, 6, aid0=6 * rnd):
                    eng.submit_agent(a)
                eng.run_until_idle(max_iters=100_000)
                eng.alloc.check_invariants()
            new, ref = (_snapshot(e) for e in engines)
            assert new == ref, (pool, rnd)
            assert len(new["completions"]) == 6 * (rnd + 1)
        swapped |= new["swaps"] > 0
    assert swapped, "the tight pool never swapped"
