"""A run with the timed path broken underneath comes out not correct.

Each test serves the CPU-sized configuration of ``data/tiny.json`` through
``run.run_cell`` (the chip check skipped) with one fault planted in the
engine's decode window, the program that produces every streamed token, and
checks that ``correct`` is false.  The exchange between chips is not among
the faults: every cell runs on one chip.  The limit is the tiny
configuration's own, set from its readings (``data/tiny.json``).
"""

import functools
import json

import jax
import jax.numpy as jnp
import pytest

from conftest import DATA
from repro.engine import engine as E

LIMIT = json.loads((DATA / "tiny.json").read_text())["check"][
    "logit_gap_limit"]
SEED = 4000000123


def test_a_sound_run_is_correct(tiny):
    res = tiny(SEED, limit=LIMIT)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["checks"]["logit_gap"]["value"] <= LIMIT


@functools.partial(jax.jit, static_argnums=(0, 1))
def _stale_window(model, k, params, cache, state):
    """The decode window with its cache returned unchanged: each step
    attends over keys and values the window never wrote."""

    def body(state, _):
        last_tok, pos, rem = state
        logits, _ = model.decode(params, cache, last_tok[:, None], pos)
        nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
        live = rem > 0
        state = jnp.stack([jnp.where(live, nxt, last_tok),
                           jnp.where(live, pos + 1, pos),
                           rem - live.astype(rem.dtype)])
        return state, nxt

    state, toks = jax.lax.scan(body, state, None, length=k)
    return cache, state, toks


def _altered_token(model, k, params, cache, state):
    """The first streamed token of every window comes out one off."""
    cache, state, toks = ORIG(model, k, params, cache, state)
    return cache, state, toks.at[0].set((toks[0] + 1) % model.cfg.vocab)


def _half_batch(model, k, params, cache, state):
    """The upper half of the slots is left out of the step: their state
    does not advance and their tokens come back as the zeros the output
    buffer starts from."""
    half = state.shape[1] // 2
    before = jnp.array(state[:, half:])
    cache, state, toks = ORIG(model, k, params, cache, state)
    return cache, state.at[:, half:].set(before), toks.at[:, half:].set(0)


ORIG = E._decode_window_jit


@pytest.mark.parametrize("fault", [_stale_window, _altered_token,
                                   _half_batch])
def test_a_broken_decode_window_is_not_correct(tiny, monkeypatch, fault):
    monkeypatch.setattr(E, "_decode_window_jit", fault)
    res = tiny(SEED, limit=LIMIT)
    assert not res["correct"], res["checks"]
