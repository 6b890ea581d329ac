"""The ``mamba2_hybrid`` stack (granite-4.0-h) against its plain reference.

The reference is the benchmark's family module
``bench/reference/mamba2_hybrid.py`` (float32, the per-step Mamba-2
recurrence, no cache or batching), loaded by path so that the repository
keeps one.  The model is built at a CPU size from the published
configuration file with its widths shrunk, in float32, with the
reference's seeded random weights.
"""

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import InferenceSpec, agent_cost, make_scheduler
from repro.engine import EngineAgent, ServeEngine
from repro.engine import engine as E
from repro.models import Model
from repro.models import ssm

ROOT = Path(__file__).resolve().parents[1]

#: prompts of different lengths, padded to one 64-token bucket
LENS = np.array([37, 20, 9])
BUCKET, SLICE, DECODE = 64, 24, 8
#: widest logit gap over the logits' spread.  The float32 program and the
#: float32 reference differ only by the order of their sums (the chunked
#: SSD scan against the per-step recurrence, batched against one-sequence
#: matmuls): 4.2e-6.  The state rounded to bfloat16 reads 9.7e-4.  5e-5
#: leaves the first 12 times its room and the second 19 times above it
REL_TOL = 5e-5


def _family():
    path = ROOT / "bench" / "reference" / "mamba2_hybrid.py"
    name = "bench_reference_mamba2_hybrid"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


@pytest.fixture(scope="module")
def tiny():
    """(family, dims, model, params): the published configuration with its
    widths cut to a CPU size, two periods of (mamba, attention, mamba)."""
    fam = _family()
    cfg = json.loads(
        (ROOT / "bench" / "configs" / "granite-4.0-h-micro.json").read_text()
    )
    cfg.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        shared_intermediate_size=128, intermediate_size=128, vocab_size=256,
        mamba_n_heads=2, mamba_d_state=16,
        layer_types=["mamba", "attention", "mamba"] * 2,
        num_hidden_layers=6, torch_dtype="float32",
    )
    dims = fam.Dims.from_config(cfg)
    model = Model(dataclasses.replace(get_config("granite-4.0-h-micro"),
                                      **fam.program_fields(dims)))
    params = fam.make_params(dims, jax.random.PRNGKey(0))
    return fam, dims, model, params


def _worst_gap(fam, dims, model, params):
    """Ragged prefill in several slices, then DECODE teacher-forced decode
    steps: the widest gap between the program's logits and the
    reference's full forward, over the logits' spread."""
    rng = np.random.default_rng(0)
    seqs = rng.integers(0, dims.vocab, (len(LENS), BUCKET)).astype(np.int32)
    ref = np.stack([np.asarray(fam.logits(dims, "f32", params,
                                          jnp.asarray(s))) for s in seqs])
    rows = np.arange(len(LENS))
    toks = np.where(np.arange(BUCKET)[None] < LENS[:, None], seqs, 0)
    # fresh functions: each call traces the model as the test patched it
    prefill = jax.jit(lambda p, b: model.prefill_chunked(p, b, BUCKET, SLICE))
    decode = jax.jit(lambda *a: model.decode(*a))
    lg, cache = prefill(
        params, {"tokens": jnp.asarray(toks), "lens": jnp.asarray(LENS)}
    )
    gaps = [np.abs(np.asarray(lg[:, 0]) - ref[rows, LENS - 1]).max()]
    pos = LENS.copy()
    for _ in range(DECODE):
        lg, cache = decode(params, cache, jnp.asarray(seqs[rows, pos])
                           [:, None], jnp.asarray(pos))
        gaps.append(np.abs(np.asarray(lg[:, 0]) - ref[rows, pos]).max())
        pos = pos + 1
    return max(gaps) / ref.std()


def test_ragged_prefill_then_decode_matches_the_reference(tiny):
    assert _worst_gap(*tiny) < REL_TOL


def test_the_tolerance_tells_a_missing_mask(tiny, monkeypatch):
    """Padded positions that update the state break the comparison."""
    chunked = ssm.mamba2_forward_chunked
    monkeypatch.setattr(
        ssm, "mamba2_forward_chunked",
        lambda *a, n_valid=None, **kw: chunked(*a, **kw),
    )
    assert _worst_gap(*tiny) > REL_TOL


def test_the_tolerance_tells_a_bf16_state(tiny, monkeypatch):
    """The SSM state rounded to bfloat16 after every prefill slice and
    every decode step breaks the comparison."""
    def rounded(fn):
        def call(*a, **kw):
            y, (st, cv) = fn(*a, **kw)
            return y, (st.astype(jnp.bfloat16).astype(st.dtype), cv)
        return call

    monkeypatch.setattr(ssm, "mamba2_forward_chunked",
                        rounded(ssm.mamba2_forward_chunked))
    monkeypatch.setattr(ssm, "mamba2_decode", rounded(ssm.mamba2_decode))
    assert _worst_gap(*tiny) > REL_TOL


def _agent(aid, prompts, decode):
    stage = [(np.asarray(p, np.int32), decode) for p in prompts]
    specs = [InferenceSpec(len(p), decode) for p in prompts]
    return EngineAgent(aid, 0, [stage], agent_cost(specs))


class Tokens:
    def __init__(self):
        self.tokens = {}

    def on_token(self, agent_id, rid, tok, now):
        self.tokens.setdefault(rid, []).append(int(tok))


def _engine(model, params, **kw):
    return ServeEngine(model, params, make_scheduler("justitia", 2048.0),
                       pool_tokens=2048, max_batch=4, cache_len=128,
                       prefill_chunk=SLICE, max_window=4, **kw)


def test_engine_admits_two_prompts_in_one_batched_pass(tiny):
    _, dims, model, params = tiny
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, dims.vocab, n) for n in (30, 11)]
    eng = _engine(model, params)
    eng.submit_agent(_agent(0, prompts, 6))
    eng.step()
    m = eng.metrics
    assert (m["prefills"], m["prefill_passes"]) == (2, 1)
    assert (m["prefill_tokens"], m["prefill_rows"]) == (41, 2 * BUCKET)


def test_swap_round_trip_keeps_a_slots_state_bit_identical(tiny):
    _, dims, model, params = tiny
    rng = np.random.default_rng(2)
    eng = _engine(model, params)
    eng.submit_agent(_agent(0, [rng.integers(0, dims.vocab, n)
                                for n in (30, 11, 17)], 12))
    eng.step()
    before = {req.rid: jax.tree.map(np.asarray,
                                    E._gather_slot_jit(eng.cache, slot))
              for slot, req in eng.slot_req.items()}
    assert eng._swap_out_worst()
    (rid,) = eng._swapped_rids
    eng._admit()
    assert not eng._swapped_rids
    slot = next(s for s, r in eng.slot_req.items() if r.rid == rid)
    after = jax.tree.map(np.asarray, E._gather_slot_jit(eng.cache, slot))
    for name, leaf in after.items():
        np.testing.assert_array_equal(leaf, before[rid][name], err_msg=name)


@pytest.mark.parametrize("arch", ["granite-4.0-h-micro", "zamba2-2.7b"])
def test_engine_serves_a_hybrid_as_each_prompt_alone(arch):
    """Two prompts of different lengths share a batch and its admission
    pass; each streams the greedy tokens its prompt gives alone through
    ``Model.prefill`` and ``Model.decode``, so the admission scatter and
    the decode window write and read the right rows of every state leaf."""
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, n) for n in (21, 9)]
    tap = Tokens()
    eng = _engine(model, params, listener=tap)
    eng.submit_agent(_agent(0, prompts, 5))
    eng.run_until_idle()
    assert eng.metrics["prefill_passes"] == (1 if model.ragged_prefill
                                             else 2)
    prefill = jax.jit(model.prefill, static_argnames="cache_len")
    decode = jax.jit(model.decode)
    for prompt, rid in zip(prompts, sorted(tap.tokens)):
        lg, cache = prefill(
            params, {"tokens": jnp.asarray(prompt[None], jnp.int32)},
            cache_len=128,
        )
        want, pos = [], len(prompt)
        tok = jnp.argmax(lg[:, -1], -1)[:, None].astype(jnp.int32)
        for _ in range(5):
            lg, cache = decode(params, cache, tok,
                               jnp.asarray([pos], jnp.int32))
            tok = jnp.argmax(lg[:, -1], -1)[:, None].astype(jnp.int32)
            want.append(int(tok[0, 0]))
            pos += 1
        assert tap.tokens[rid] == want, (arch, rid)


def test_engine_refuses_prefix_reuse_of_recurrent_state(tiny):
    _, _, model, params = tiny
    with pytest.raises(ValueError, match="recurrent state"):
        _engine(model, params, prefix_cache=True)
    with pytest.raises(ValueError, match="recurrent state"):
        _engine(model, params, suspend_retention="spill")


@pytest.mark.parametrize("arch, field", [
    ("granite-3-2b", {"position": "alibi"}),
    ("granite-3-2b", {"residual_multiplier": 0.22}),
    ("zamba2-2.7b", {"residual_multiplier": 0.22}),
])
def test_config_refuses_what_no_stack_serves(arch, field):
    """A position encoding no attention applies, or a residual multiplier
    on a stack that would ignore it, is refused, not served silently."""
    with pytest.raises(ValueError):
        dataclasses.replace(get_config(arch), **field)
