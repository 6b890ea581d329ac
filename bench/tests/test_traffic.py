"""The traffic generator: seeded, fixed work per cell, requests that fit."""

import json

import numpy as np
import pytest

import run

agents = run.load_module(run.BENCH / "traffic", "agents")
MIXES = ["agents-burst", "agents-backlog"]


def mix(name):
    return json.loads((run.BENCH / "traffic" / f"{name}.json").read_text())


def flat(traffic):
    return [(a.cls, [(p.tolist(), d) for p, d in a.requests])
            for a in traffic.agents]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_traffic(name):
    a = agents.build(mix(name), 2**33 + 1, 40, 1000)
    b = agents.build(mix(name), 2**33 + 1, 40, 1000)
    assert flat(a) == flat(b)
    assert a.arrivals == b.arrivals and a.clients == b.clients


@pytest.mark.parametrize("name", MIXES)
def test_seeds_serve_the_same_work(name):
    """Seeds draw other prompt tokens for the same agents, in the same
    order, at the same times."""
    a = agents.build(mix(name), 3, 40, 1000)
    b = agents.build(mix(name), 4, 40, 1000)
    sizes = lambda t: [
        (a.cls, tuple(tuple((len(p), d) for p, d in st) for st in a.stages))
        for a in t.agents]
    assert sizes(a) == sizes(b)
    assert flat(a) != flat(b)
    assert a.arrivals == b.arrivals


def test_open_loop_arrivals_lie_in_the_window():
    t = agents.build(mix("agents-burst"), 5, 40, 1000)
    arr = np.asarray(t.arrivals)
    assert len(arr) == len(t.agents) == round(
        mix("agents-burst")["rate_agents_per_s"] * 40)
    assert (np.diff(arr) >= 0).all() and arr.min() >= 0 and arr.max() < 40


@pytest.mark.parametrize("name,config", [
    ("agents-burst", "granite-3-2b"), ("agents-backlog", "h2o-danube-1.8b")])
@pytest.mark.parametrize("seconds", [10, 51])
def test_every_request_fits_the_cache(name, config, seconds):
    cfg = run.load_config(config)
    t = agents.build(mix(name), 9, seconds, cfg["vocab_size"])
    longest = max(len(p) + d + 1 for a in t.agents for p, d in a.requests)
    assert longest <= cfg["engine"]["cache_len"]
    toks = np.concatenate([p for a in t.agents for p, _ in a.requests])
    assert toks.min() >= 0 and toks.max() < cfg["vocab_size"]


def test_small_classes_at_their_lengths():
    """The pools keep the paper's small classes at real token lengths: CC's
    prompts run past one 512-token prefill chunk."""
    m = mix("agents-backlog")
    pool = agents.pool(m, m["pool_agents"])
    assert {c for c, _ in pool} == {"EV", "FV", "CC", "ALFWI", "KBQAV"}
    assert max(p for c, st in pool if c == "CC" for s in st
               for p, _ in s) > 512
