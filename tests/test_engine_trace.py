"""The engine's host phases (``repro.engine.trace``): one profiler span and
one self-time counter per phase, a few per decode window and none per
token; the host stamps each request carries; and the names of the jitted
programs that the chip benchmark finds in a device trace.

Pinned here:

  * **self times add up** — the phases' self times sum to the wall time
    of the steps that opened them (exactly so for the helper on a fake
    clock, within the steps' own call overhead for the engine);
  * **per window, not per token** — each span name is opened a fixed
    number of times per step, window, prefill pass or swap, on both the
    batched and the fused admission paths;
  * **stamps** — every admitted request was queued before it was popped
    for admission, and the stamps touch no scheduling decision: tokens,
    completions and the oracle counters still equal the frozen
    ``ReferenceServeEngine``;
  * **program names** — ``bench/metrics/decode_step_ms.py`` finds the
    decode programs by the XLA module names ``jit__decode_window_jit``
    and ``jit__fused_window_jit``, and the breakdown lists
    ``jit__prefill_write_jit``; a rename fails here instead of silently
    leaving ``decode_step_ms`` unread.
"""

import importlib.util
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from _engine_agents import _snapshot, synth_agents

import repro.engine.engine as engine_mod
from repro.configs import get_config
from repro.core import make_scheduler
from repro.engine import ReferenceServeEngine, ServeEngine
from repro.engine import trace as trace_mod
from repro.engine.trace import Phases
from repro.models import Model

VOCAB = 256
PHASES = ("step", "admit", "prefill", "swap", "prep", "device_wait",
          "replay")
ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("granite-3-2b").reduced(vocab=VOCAB)
    model = Model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


class TokenTap:
    def __init__(self):
        self.tokens = {}

    def on_token(self, agent_id, rid, tok, now):
        self.tokens.setdefault(rid, []).append(int(tok))


def test_phase_self_times_on_a_fake_clock(monkeypatch):
    """Outer [0, 10) holds [2, 5) and [6, 7), the first of which holds
    [3, 4): each counter gets its self time, and they add up to 10."""
    clock = iter([0.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 10.0])
    monkeypatch.setattr(trace_mod.time, "perf_counter", lambda: next(clock))
    metrics = {"outer_s": 0.0}
    phase = Phases(metrics).phase
    with phase("outer", now=3):
        with phase("a"):
            with phase("b"):
                pass
        with phase("b"):
            pass
    assert metrics == {"outer_s": 6.0, "a_s": 2.0, "b_s": 2.0}


def _serve(model, params, monkeypatch, *, fused, **kw):
    """Drain seeded agents step by step; returns the engine, its taps and
    the wall time of its steps."""
    names, passes, queued = [], [0], []
    real_span = jax.profiler.TraceAnnotation
    real_prefill = engine_mod._prefill_write_jit

    def span(name, **args):
        names.append(name)
        return real_span(name, **args)

    def prefill(*a, **k):
        passes[0] += 1
        return real_prefill(*a, **k)

    tap = TokenTap()
    eng = ServeEngine(
        model, params, make_scheduler("justitia", 256.0), pool_tokens=256,
        max_batch=4, cache_len=96, prefill_chunk=8, fused_prefill=fused,
        listener=tap, **kw,
    )
    eng.warmup()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", span)
    monkeypatch.setattr(engine_mod, "_prefill_write_jit", prefill)
    push = eng.waiting.push

    def record(req):
        queued.append(req)
        push(req)

    eng.waiting.push = record
    for a in synth_agents(3, 10):
        eng.submit_agent(a)
    steps, wall = 0, 0.0
    while eng.busy or eng.pending or eng._resumes:
        if not eng.busy:
            eng.now = max(eng.now, int(eng._next_wake(eng.now)))
        t0 = time.perf_counter()
        eng.step()
        wall += time.perf_counter() - t0
        steps += 1
    eng.alloc.check_invariants()
    return eng, {"names": names, "passes": passes[0], "queued": queued,
                 "steps": steps, "wall": wall, "tokens": tap.tokens}


@pytest.mark.parametrize("fused", [False, True], ids=["batched", "fused"])
def test_phases_count_windows_not_tokens(tiny_model, monkeypatch, fused):
    model, params = tiny_model
    eng, run = _serve(model, params, monkeypatch, fused=fused)
    m, names, steps = eng.metrics, run["names"], run["steps"]
    assert len(eng.completions) == 10

    selfs = [m[p + "_s"] for p in PHASES]
    assert min(selfs) >= 0.0
    # the steps' own call overhead lies outside every phase
    assert 0.9 * run["wall"] <= sum(selfs) <= run["wall"]

    assert set(names) <= {"engine." + p for p in PHASES}
    assert names.count("engine.step") == names.count("engine.admit") == steps
    assert names.count("engine.device_wait") == m["windows"]
    assert names.count("engine.replay") == m["windows"]
    assert m["windows"] <= names.count("engine.prep") <= steps
    assert names.count("engine.prefill") == m["prefill_passes"]
    assert run["passes"] == m["prefill_passes"] <= m["prefills"]
    # fused admission prefills inside the windows: no pass without a
    # cached prefix head to write
    assert m["prefill_passes"] > 0 or fused
    assert names.count("engine.swap") >= m["swaps"]
    # a window carries many tokens, and no span is opened per token
    assert m["tokens"] > 2 * m["windows"]

    for req in run["queued"]:
        assert 0.0 < req.t_queued <= req.t_admit
    assert len(run["queued"]) == m["prefills"]


def test_stamps_leave_the_served_path_as_the_reference(tiny_model,
                                                      monkeypatch):
    """Tokens, completions, clock and the oracle counters equal the frozen
    reference engine's under swap pressure."""
    model, params = tiny_model
    eng, run = _serve(model, params, monkeypatch, fused=False)
    assert eng.metrics["swaps"] > 0
    tap = TokenTap()
    ref = ReferenceServeEngine(
        model, params, make_scheduler("justitia", 256.0), pool_tokens=256,
        max_batch=4, cache_len=96, prefill_chunk=8, listener=tap,
    )
    for a in synth_agents(3, 10):
        ref.submit_agent(a)
    ref.run_until_idle(max_iters=5_000_000)
    assert _snapshot(eng) == _snapshot(ref)
    assert run["tokens"] == tap.tokens


def _decode_programs() -> tuple:
    path = ROOT / "bench" / "metrics" / "decode_step_ms.py"
    spec = importlib.util.spec_from_file_location("decode_step_ms", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.DECODE_PROGRAMS


def test_jitted_program_names_the_benchmark_matches(tiny_model):
    model, params = tiny_model
    B, L, K, C = 2, 64, 2, 8
    cache = jax.eval_shape(lambda p: model.init_cache(p, B, L), params)
    state = jax.ShapeDtypeStruct((3, B), jnp.int32)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    lowered = {
        "_decode_window_jit": engine_mod._decode_window_jit.lower(
            model, K, params, cache, state),
        "_fused_window_jit": engine_mod._fused_window_jit.lower(
            model, K, C, params, cache, state, i32(K, C), i32(3)),
        "_prefill_write_jit": engine_mod._prefill_write_jit.lower(
            model, L, C, params, cache, i32(B, 16), i32(B), i32(B)),
    }
    for fn, low in lowered.items():
        assert f"module @jit_{fn} " in low.as_text()
    decode = _decode_programs()
    for fn in ("_decode_window_jit", "_fused_window_jit"):
        assert any(p in f"jit_{fn}" for p in decode)
    assert not any(p in "jit__prefill_write_jit" for p in decode)
