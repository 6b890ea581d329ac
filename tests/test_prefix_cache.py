"""Prefix-aware KV reuse subsystem (PR 6): refcount/COW invariants on
``PrefixAwareAllocator``, eviction safety, the sim-vs-engine hit-fraction
correspondence through the AgentService facade, and ``locality_fair``'s
hit rate against ``justitia`` on a contended chat fleet."""

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.api import (
    AgentHooks,
    AgentService,
    AgentSpec,
    EngineBackend,
    PrefixHit,
    SimBackend,
    specs_from_closed_loop,
)
from repro.configs import get_config
from repro.core import InferenceSpec
from repro.kvcache import BlockAllocator, OutOfBlocks
from repro.kvcache.prefix import PrefixAwareAllocator
from repro.models import Model


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("granite-3-2b").reduced(
        vocab=256, d_model=64, n_heads=2, n_kv_heads=2, d_ff=128,
        head_dim=16,
    )
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


# ------------------------------------------------------------ allocator unit


def _prompt(sid: int, n: int, shared: int = 32) -> list:
    """Deterministic canonical stream: ``shared`` tokens common to every
    sid, then a per-sid suffix (same construction the workloads use)."""
    rng = np.random.default_rng(1000 + sid)
    tail = rng.integers(0, 50_000, size=max(0, n - shared))
    head = np.arange(shared)[: min(shared, n)]
    return [int(t) for t in np.concatenate([head, tail])[:n]]


def test_admit_prefix_shares_blocks_and_counts_hits():
    a = PrefixAwareAllocator(total_tokens=256, block_size=16)
    a1, h1 = a.admit_prefix(1, _prompt(1, 48))
    assert h1 == 0 and a.hit_tokens == 0
    a2, h2 = a.admit_prefix(2, _prompt(2, 48))
    # 32 shared tokens = 2 full blocks dedup'd; suffix block private
    assert h2 == 32
    assert a1.block_table[:2] == a2.block_table[:2]
    assert a1.block_table[2] != a2.block_table[2]
    # occupancy stays LOGICAL: sharing dedups physical blocks only
    assert a.used_tokens == 96
    assert a.match_tokens(_prompt(3, 48)) == 32
    a.check_invariants()


def test_partial_tail_block_stays_private():
    a = PrefixAwareAllocator(total_tokens=256, block_size=16)
    a.admit_prefix(1, _prompt(1, 40))       # 2 full blocks + 8-token tail
    _, hit = a.admit_prefix(2, _prompt(1, 40))
    assert hit == 32                         # tail never matches
    assert a.cached_blocks == 2
    a.check_invariants()


def test_eviction_never_touches_live_sequences():
    """Pool exhaustion evicts only unreferenced cached blocks: a live
    chain is pinned, and the evicted blocks can't alias any live table."""
    a = PrefixAwareAllocator(total_tokens=128, block_size=16)  # 8 blocks
    a.admit_prefix(1, _prompt(1, 48))       # live: 3 blocks, all cached
    a.admit_prefix(2, _prompt(2, 48))       # shares 2, 1 fresh
    a.release(2)                             # seq 2's chain -> LRU
    assert a.evictions == 0
    # 4 physical blocks held, 4 free; a 5-block admission must evict
    alloc3, _ = a.admit_prefix(3, [9_999_000 + i for i in range(80)])
    assert a.evictions >= 1
    live_blocks = set(a.seq(1).block_table) | set(alloc3.block_table)
    assert len(live_blocks) == len(a.seq(1).block_table) + len(
        alloc3.block_table
    )
    # seq 1's chain survived eviction pressure intact
    assert a.match_tokens(_prompt(1, 48)) == 48
    a.check_invariants()


def test_eviction_drains_leaf_first():
    """Released chains enter the LRU deepest-first, so eviction takes the
    leaf before its parent and interior blocks never orphan children."""
    a = PrefixAwareAllocator(total_tokens=64, block_size=16)   # 4 blocks
    a.admit_prefix(1, _prompt(1, 48))
    a.release(1)
    assert a.cached_blocks == 3
    a.admit(2, 30)                           # 2 blocks: evicts 1 (4-3-2+1)
    assert a.evictions == 1
    # the surviving 2-block chain is exactly the prompt's first 2 blocks
    assert a.match_tokens(_prompt(1, 48)) == 32
    a.check_invariants()


def test_fork_then_append_is_copy_on_write():
    a = PrefixAwareAllocator(total_tokens=256, block_size=16)
    a.admit_prefix(1, _prompt(1, 48))
    fork = a.fork(1, 2, n_tokens=24)         # mid-block 2: shared cursor
    assert fork.block_table[:2] == a.seq(1).block_table[:2]
    assert a.cow_copies == 0
    assert a.append_token(2)                 # unshares block 2
    assert a.cow_copies == 1
    assert fork.block_table[0] == a.seq(1).block_table[0]
    assert fork.block_table[1] != a.seq(1).block_table[1]
    # the original keeps its cached chain and full prompt match
    assert a.match_tokens(_prompt(1, 48)) == 48
    a.check_invariants()


def test_swap_roundtrip_rematches_chain():
    a = PrefixAwareAllocator(total_tokens=256, block_size=16)
    a.admit_prefix(1, _prompt(1, 48))
    a.append_tokens(1, 10)
    a.swap_out(1)
    a.check_invariants()
    assert a.swap_in(1)
    assert a.seq(1).n_tokens == 58
    # prompt blocks re-registered: a later prompt still shares them
    _, hit = a.admit_prefix(2, _prompt(1, 48))
    assert hit == 48
    a.check_invariants()


# -------------------------------------------------------- allocator property


@given(
    ops=st.lists(
        st.tuples(
            st.sampled_from(
                ["admit", "admit_raw", "grow", "growk", "fork",
                 "swap", "swapin", "release"]
            ),
            st.integers(0, 5),
            st.integers(1, 90),
        ),
        max_size=100,
    )
)
@settings(max_examples=80, deadline=None)
def test_prefix_allocator_invariants_random_ops(ops):
    """Block conservation, exact refcounts/used_tokens, LRU consistency,
    and referenced-block pinning — whatever the operation sequence.

    Extends ``check_invariants`` with the eviction-safety property the
    PR 6 design note promises: a block referenced by ANY live sequence
    never reappears on the free list (no eviction of live state)."""
    a = PrefixAwareAllocator(total_tokens=192, block_size=16)  # 12 blocks
    live: set = set()
    fork_id = 100
    for op, sid, n in ops:
        try:
            if op == "admit" and sid not in live:
                a.admit_prefix(sid, _prompt(sid % 3, n))
                live.add(sid)
            elif op == "admit_raw" and sid not in live:
                a.admit(sid, n)
                live.add(sid)
            elif op == "grow" and sid in live and not a.seq(sid).swapped:
                a.append_token(sid)
            elif op == "growk" and sid in live and not a.seq(sid).swapped:
                a.append_tokens(sid, n % 24)
            elif op == "fork" and sid in live and not a.seq(sid).swapped:
                a.fork(sid, fork_id, 1 + n % a.seq(sid).n_tokens)
                live.add(fork_id)
                fork_id += 1
            elif op == "swap" and sid in live and not a.seq(sid).swapped:
                a.swap_out(sid)
            elif op == "swapin" and sid in live and a.seq(sid).swapped:
                a.swap_in(sid)
            elif op == "release" and sid in live:
                a.release(sid)
                live.discard(sid)
        except OutOfBlocks:
            pass
        a.check_invariants()
        # used_tokens is LOGICAL occupancy: block sharing can push it
        # past physical capacity, but never past one pool per live seq
        assert a.used_tokens <= 192 * max(1, len(live))
        free = set(a._free)
        for nd in a._nodes.values():
            if nd.refcount > 0:
                assert nd.block not in free, "referenced block freed"


def test_prefix_allocator_matches_base_when_content_free():
    """Content-free admissions make the prefix allocator behave exactly
    like the base allocator (free-count accounting included)."""
    base = BlockAllocator(total_tokens=128, block_size=16)
    pref = PrefixAwareAllocator(total_tokens=128, block_size=16)
    for alloc in (base, pref):
        alloc.admit(1, 30)
        alloc.append_tokens(1, 20)
        alloc.admit(2, 40)
        alloc.swap_out(1)
        alloc.release(2)
        alloc.check_invariants()
    assert base.free_blocks == pref.free_blocks
    assert base.used_tokens == pref.used_tokens
    assert pref.cached_blocks == 0 and pref.hit_tokens == 0


# ------------------------------------------- sim vs engine hit fractions


def _family_specs(token_scale: int):
    """Two-agent chat-like fleet with hand-built canonical streams whose
    shared prefix (256) and prompt lengths (384/640) are exact multiples
    of ``block_size * token_scale``, so block and stride rounding vanish
    and the engine's realized hit equals the sim's analytic hit."""
    shared = np.arange(256, dtype=np.int64) + 7_000
    streamA = np.concatenate([shared, np.arange(1024) + 100_000])
    streamB = np.concatenate([shared, np.arange(1024) + 200_000])
    specs = []
    for aid, (stream, arrival) in enumerate(
        [(streamA, 0.0), (streamB, 40.0)]
    ):
        specs.append(
            AgentSpec(
                stages=[
                    [InferenceSpec(384, 16)],
                    [InferenceSpec(640, 16)],
                ],
                arrival=arrival,
                prompt_ids=[[stream[:384]], [stream[:640]]],
                cached_hints=[[0.0], [384.0]],
                prefix_group="fam",
                shared_prefix=256.0,
                name=f"a{aid}",
            )
        )
    return specs


def test_sim_engine_hit_fractions_match(tiny_model):
    """The engine's content-hash realized hit fractions must equal the
    simulator's analytic model in the rounding-free regime: ample pool
    (no eviction), aligned prompt lengths, staggered arrivals."""
    model, params = tiny_model
    sim = AgentService(
        SimBackend("justitia", total_kv=8192.0, prefix_cache=True)
    )
    sim.submit_many(_family_specs(1))
    sim_res = sim.drain()
    eng = AgentService(
        EngineBackend(
            model, params, "justitia", pool_tokens=1024, max_batch=4,
            cache_len=256, token_scale=8, prefix_cache=True,
        )
    )
    eng.submit_many(_family_specs(8))
    eng_res = eng.drain()
    sim_hf = sim_res.metrics["hit_fractions"]
    eng_hf = eng_res.metrics["hit_fractions"]
    # agent 0: 0/384 then own 384/640 -> 384/1024; agent 1: the seeded
    # family prefix 256/384 then 384/640 -> 640/1024 (scale-free)
    assert sim_hf[0] == pytest.approx(0.375)
    assert sim_hf[1] == pytest.approx(0.625)
    assert eng_hf[0] == pytest.approx(sim_hf[0])
    assert eng_hf[1] == pytest.approx(sim_hf[1])
    assert sim_res.metrics["prefill_tokens_saved"] == pytest.approx(1024.0)
    assert eng_res.metrics["prefill_tokens_saved"] == 128  # 1024 / scale


def test_cache_off_backends_report_no_hits(tiny_model):
    model, params = tiny_model
    for svc in (
        AgentService(SimBackend("justitia", total_kv=8192.0)),
        AgentService(
            EngineBackend(model, params, "justitia", pool_tokens=1024,
                          max_batch=4, cache_len=256, token_scale=8)
        ),
    ):
        svc.submit_many(_family_specs(1))
        res = svc.drain()
        assert res.metrics.get("prefill_tokens_saved", 0) == 0
        assert res.metrics.get("hit_fractions", {}) in ({}, None) or all(
            v == 0.0 for v in res.metrics["hit_fractions"].values()
        )


def test_prefix_hit_events_and_hooks(tiny_model):
    """PrefixHit events reach both the recorder and per-agent hooks, and
    carry backend-native cached/prefill token counts."""
    model, params = tiny_model
    seen: list = []
    hooks = AgentHooks(on_prefix_hit=seen.append)
    svc = AgentService(
        EngineBackend(
            model, params, "justitia", pool_tokens=1024, max_batch=4,
            cache_len=256, token_scale=8, prefix_cache=True,
        )
    )
    for spec in _family_specs(8):
        svc.submit(spec, hooks=hooks)
    svc.drain()
    assert svc.recorder.event_counts.get("PrefixHit", 0) >= 2
    assert all(isinstance(ev, PrefixHit) for ev in seen)
    assert {ev.agent_id for ev in seen} == {0, 1}
    for ev in seen:
        assert 0 < ev.cached <= ev.prefill


# ------------------------------------------ locality against fair queuing


def _chat_fleet_run(model, params, sched: str):
    """A closed-loop chat fleet of 32 sessions on a pool of about 1.5
    scaled prompts per 4 slots: the free list stays exhausted, so idle
    session chains face LRU eviction between turns and admission order
    moves the hit rate."""
    svc = AgentService.engine(
        model, params, sched, pool_tokens=384, max_batch=4, cache_len=512,
        prefill_chunk=32, token_scale=8, time_scale=1.0, seed=0,
        prefix_cache=True, record_events=False,
    )
    specs = specs_from_closed_loop(np.random.default_rng(7), 32, 30.0,
                                   classes=("chat",))
    svc.submit_many(specs)
    res = svc.drain()
    eng = svc.backend.engine
    eng.alloc.check_invariants()
    saved = res.metrics["prefill_tokens_saved"]
    assert saved > 0, f"{sched}: the cache served nothing"
    return saved / sum(eng.agent_prefill_tokens.values()), max(
        res.jct.values())


def test_locality_fair_beats_justitia_hit_rate_within_delay_bound(
    tiny_model,
):
    """Selective pampering is fair but cache-oblivious: on the contended
    chat fleet, ``locality_fair`` must serve more prefill from cache than
    ``justitia`` while its worst JCT stays within 1.15x of justitia's."""
    model, params = tiny_model
    hit_j, max_j = _chat_fleet_run(model, params, "justitia")
    hit_l, max_l = _chat_fleet_run(model, params, "locality_fair")
    assert hit_l > hit_j
    assert max_l <= 1.15 * max_j
