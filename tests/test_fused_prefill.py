"""Fused prefill-in-window tests (the PR 7 tentpole).

``fused_prefill=True`` rides each admitted prompt's uncached suffix
through the jitted decode windows one chunk-slice per ``lax.scan`` step
instead of charging a blocking whole-prefill pass at admission.  Pinned
here:

  * **fused-off oracle** — with the flag off (the default) the engine
    stays bit-identical to the frozen ``ReferenceServeEngine`` (same
    strictly-additive rule the prefix cache obeys);
  * **token equivalence** — the fused path computes the SAME token
    values as the unfused path (``Model.prefill_slice`` is numerically
    identical to ``prefill_chunked``), only the clock accounting moves;
  * **gating** — ring-buffer (sliding-window) caches are rejected: the
    slice writer assumes full-cache row addressing;
  * **prefix-cache composition** — fused admission still serves cached
    prefixes (including whole-prompt hits, which skip the fused stream
    entirely and decode from the zero-clock head write);
  * **scheduling-free windows** (minihyp) — with a prompt in flight the
    window sizer's new trigger (prefill-slice exhaustion / admission
    becoming possible mid-window) keeps every scheduling event on a
    window boundary: admissions and swaps at pass starts,
    stage-submitting completions and pf exhaustion only on a window's
    LAST step, and the fused stream never overshoots the prompt by a
    full slice.
"""

import jax
import numpy as np
import pytest
from _engine_agents import _snapshot, synth_agents
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config
from repro.core import InferenceSpec, agent_cost, make_scheduler
from repro.engine import EngineAgent, ReferenceServeEngine, ServeEngine
from repro.models import Model

VOCAB = 256


_MODEL_CACHE = {}


def _tiny_model():
    if "m" not in _MODEL_CACHE:
        cfg = get_config("granite-3-2b").reduced(vocab=VOCAB)
        model = Model(cfg)
        _MODEL_CACHE["m"] = (model, model.init(jax.random.PRNGKey(0)))
    return _MODEL_CACHE["m"]


@pytest.fixture(scope="module")
def tiny_model():
    return _tiny_model()


class TokenTap:
    """Listener that records each request's sampled token sequence."""

    def __init__(self):
        self.tokens = {}

    def on_token(self, agent_id, rid, tok, now):
        self.tokens.setdefault(rid, []).append(int(tok))


def _drain(model, params, agents, *, fused, sched="justitia", **kw):
    kw.setdefault("pool_tokens", 2048)
    kw.setdefault("max_batch", 4)
    kw.setdefault("cache_len", 256)
    kw.setdefault("prefill_chunk", 8)
    tap = TokenTap()
    eng = ServeEngine(
        model, params, make_scheduler(sched, float(kw["pool_tokens"])),
        fused_prefill=fused, listener=tap, **kw
    )
    for a in agents:
        eng.submit_agent(a)
    done = eng.run_until_idle()
    eng.alloc.check_invariants()
    return eng, done, tap.tokens


def test_fused_off_bit_identical_to_reference(tiny_model):
    """The flag-off engine must remain the reference engine, bit for bit
    (completions, clock, token/prefill/swap/decode-step counts)."""
    model, params = tiny_model
    for sched in ("justitia", "vtc"):
        snaps = {}
        for cls in (ServeEngine, ReferenceServeEngine):
            eng = cls(
                model, params, make_scheduler(sched, 256.0),
                pool_tokens=256, max_batch=4, cache_len=96,
            )
            for a in synth_agents(3, 10):
                eng.submit_agent(a)
            eng.run_until_idle(max_iters=5_000_000)
            eng.alloc.check_invariants()
            snaps[cls.__name__] = _snapshot(eng)
        assert snaps["ServeEngine"] == snaps["ReferenceServeEngine"], sched


def test_fused_token_values_match_unfused(tiny_model):
    """prefill_slice must reproduce prefill_chunked's numerics exactly:
    every request's sampled token sequence is identical under both
    admission paths (only the clock accounting differs)."""
    model, params = tiny_model
    plain = _drain(model, params, synth_agents(5, 8), fused=False)
    fused = _drain(model, params, synth_agents(5, 8), fused=True)
    assert fused[0].metrics["fused_slices"] > 0
    assert fused[2] == plain[2]
    assert fused[1].keys() == plain[1].keys()


def test_fused_rejects_ring_cache():
    """Sliding-window ring caches address rows mod window; the slice
    writer assumes full-cache addressing, so construction must fail."""
    cfg = get_config("h2o-danube-1.8b").reduced(
        vocab=VOCAB, d_model=64, n_heads=2, n_kv_heads=2, d_ff=128,
    )
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    assert cfg.sliding_window and cfg.sliding_window < 256
    with pytest.raises(ValueError, match="fused_prefill"):
        ServeEngine(
            model, params, make_scheduler("justitia", 256.0),
            pool_tokens=256, max_batch=2, cache_len=256,
            fused_prefill=True,
        )


def test_fused_composes_with_prefix_cache(tiny_model):
    """Fused admission still serves cached prefixes.  Three agents share
    a block-aligned prompt head; the third repeats the first's prompt
    exactly, so its whole prompt hits and it must decode straight from
    the zero-clock head write (no fused slices of its own)."""
    model, params = tiny_model
    rng = np.random.default_rng(9)
    head = rng.integers(0, VOCAB, size=32)      # two 16-token blocks
    prompts = [
        np.concatenate([head, rng.integers(0, VOCAB, size=16)]),
        np.concatenate([head, rng.integers(0, VOCAB, size=16)]),
    ]
    prompts.append(prompts[0].copy())           # whole-prompt repeat
    agents = [
        EngineAgent(
            i, 40 * i, [[(p, 12)]], agent_cost([InferenceSpec(len(p), 12)])
        )
        for i, p in enumerate(prompts)
    ]
    eng, done, toks = _drain(
        model, params, agents, fused=True,
        prefix_cache=True, block_size=16,
    )
    assert set(done) == {0, 1, 2}
    assert eng.metrics["prefix_hits"] >= 2
    assert eng.metrics["prefill_tokens_saved"] >= 32 + 48
    assert all(len(t) == 12 for t in toks.values())
    # the repeat's prompt was fully cached: its admission streamed no
    # slices, so total slices cover only the three uncached suffixes
    chunk = eng.prefill_chunk
    expected = sum(-(-n // chunk) for n in (48, 16, 0) if n)
    assert eng.metrics["fused_slices"] == expected


# ------------------------------------------- scheduling-free fused windows


class SpyEngine(ServeEngine):
    """Records every decode window (start iteration, width) and the
    iteration at which a fused prefill stream exhausted its prompt."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.windows = []
        self.pf_exhaust = []

    def _decode_once(self, limit=None):
        t0 = self.now
        pf = self._pf
        k = super()._decode_once(limit)
        self.windows.append((t0, k))
        if pf is not None and self._pf is None:
            self.pf_exhaust.append((self.now, pf.total, pf.written))
        return k


class TriggerTap:
    """Records the engine iteration of every scheduling event."""

    def __init__(self):
        self.pass_start = []       # admissions / swaps: pass boundaries
        self.stage_complete = []   # (agent_id, stage, now)

    def on_admit(self, agent_id, rid, now):
        self.pass_start.append(now)

    def on_swap_out(self, agent_id, rid, now):
        self.pass_start.append(now)

    def on_swap_in(self, agent_id, rid, now):
        self.pass_start.append(now)

    def on_stage_complete(self, agent_id, stage, now):
        self.stage_complete.append((agent_id, stage, now))


@given(
    st.integers(min_value=0, max_value=2**31 - 1),
    st.integers(min_value=2, max_value=6),
    st.sampled_from([4, 8, 16]),                 # prefill_chunk
    st.sampled_from([256, 2048]),                # swap pressure / roomy
    st.sampled_from(["justitia", "vtc"]),
)
@settings(max_examples=12, deadline=None)
def test_fused_windows_are_scheduling_free(
    seed, n_agents, chunk, pool, sched
):
    """ROADMAP invariant, extended by PR 7: a fused decode window spans
    no scheduling trigger.  Admissions and swaps may only happen at pass
    starts (the iteration right after a window ends); stage-SUBMITTING
    completions (a stage with a successor — the ones that schedule new
    work) and prefill-slice exhaustion only on a window's LAST step,
    never strictly inside; and exhaustion overshoots the prompt by less
    than one slice (the new ``ceil(remaining/chunk)`` cap is tight).
    Final-stage completions are exempt: with empty queues they schedule
    nothing, and the window may legally span them (module doc)."""
    model, params = _tiny_model()
    agents = synth_agents(seed, n_agents)
    n_stages = {a.agent_id: len(a.stages) for a in agents}
    tap = TriggerTap()
    eng = SpyEngine(
        model, params, make_scheduler(sched, float(pool)),
        pool_tokens=pool, max_batch=4, cache_len=96,
        prefill_chunk=chunk, listener=tap, fused_prefill=True,
    )
    for a in agents:
        eng.submit_agent(a)
    eng.run_until_idle(max_iters=5_000_000)
    eng.alloc.check_invariants()

    starts = {t0 for t0, _ in eng.windows}
    last_steps = {t0 + k - 1 for t0, k in eng.windows}
    interior = set()
    for t0, k in eng.windows:
        interior.update(range(t0 + 1, t0 + k - 1))

    for t in tap.pass_start:
        assert int(t) in starts, f"admission/swap at {t} not a pass start"
        assert int(t) not in interior, "admission/swap inside a window"
    submitting = [
        (aid, stage, t) for aid, stage, t in tap.stage_complete
        if stage < n_stages[aid] - 1
    ]
    for aid, stage, t in submitting:
        assert int(t) in last_steps, (
            f"agent {aid} stage {stage} (has a successor) completed at "
            f"{t}, not on a window's last step"
        )
        assert int(t) not in interior, "stage boundary inside a window"
    for now, total, written in eng.pf_exhaust:
        assert now in last_steps, (
            f"prefill exhaustion at {now} not on a window's last step"
        )
        assert written - total < chunk, (
            f"fused stream overshot the prompt: wrote {written} of "
            f"{total} (chunk {chunk})"
        )

