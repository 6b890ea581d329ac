"""The engine's host phases as the benchmark reads them: the counters behind
``host_gap_ms`` and ``prefill_pass_ms``, and ``phases.py``'s split of the
device's idle time by the innermost span open, on a hand-made trace, on
the recorded v5e trace (which has no engine spans) and on a CPU traced
run."""

import gzip
import json

import numpy as np
import pytest

import devtrace
import phases
from conftest import BENCH, DATA
from test_trace import hand_trace

MS = 1_000_000     # ns


def test_idle_goes_to_the_innermost_span():
    """``hand_trace``'s window [0, 10) ms, busy [1, 4) and [6, 7), with
    engine phases inside ``service.run`` [0, 5): step [0.5, 4.5) holding
    admit [0.5, 1.5) (holding prefill [0.8, 1.2)), device_wait
    [1.5, 3.5) and replay [3.5, 4.5)."""
    eng = [["step", 0.5 * MS, 4 * MS], ["admit", 0.5 * MS, 1 * MS],
           ["prefill", 0.8 * MS, 0.4 * MS], ["device_wait", 1.5 * MS, 2 * MS],
           ["replay", 3.5 * MS, 1 * MS]]
    ex = hand_trace()
    split = phases.idle_split(ex, eng, (1000.0, 1000.010))
    assert split == pytest.approx({
        "service.run": 0.0010,                   # [0, 0.5) and [4.5, 5)
        "service.run/engine.admit": 0.0003,      # [0.5, 0.8)
        "service.run/engine.prefill": 0.0002,    # [0.8, 1)
        "service.run/engine.replay": 0.0005,     # [4, 4.5)
        "wait_arrival": 0.0040,                  # [5, 6) and [7, 10)
    })
    s = devtrace.summarize(ex, (1000.0, 1000.010), [])
    assert sum(split.values()) == pytest.approx(s["window_s"] - s["busy_s"])
    # without engine spans the split is devtrace's own
    plain = phases.idle_split(ex, [], (1000.0, 1000.010))
    assert plain == pytest.approx(
        {k: v for k, v in s["idle_by_span"].items() if v > 0})


def test_idle_inside_programs():
    """``hand_trace``'s programs [1, 4), [6, 7) and [9.5, 11.5) ms: only
    [9.5, 10) of the window's idle time lies inside one."""
    assert phases.idle_in_programs(hand_trace(), (1000.0, 1000.010)) == \
        pytest.approx(0.0005)


def test_recorded_v5e_trace_splits_as_devtrace():
    """The recorded trace holds no engine span: the split is
    ``breakdown.idle_gaps``'s, label for label, and sums to the window
    less the busy time."""
    with gzip.open(DATA / "v5e-trace.json.gz", "rt") as f:
        rec = json.load(f)
    ex, window = rec["trace"], tuple(rec["window_perf"])
    s = devtrace.summarize(ex, window, rec["inflight_perf"])
    split = phases.idle_split(ex, [], window)
    assert split == pytest.approx(
        {k: v for k, v in s["idle_by_span"].items() if v > 0}, abs=1e-9)
    assert sum(split.values()) == pytest.approx(s["window_s"] - s["busy_s"])


def test_cumulative_matches_a_loop():
    rng = np.random.default_rng(0)
    iv = devtrace.union(np.sort(rng.uniform(0, 100, (40, 2)), axis=1))
    t = np.sort(rng.uniform(-5, 105, 200))
    loop = [sum(max(0.0, min(b, x) - a) for a, b in iv) for x in t]
    assert phases._cumulative(iv, t) == pytest.approx(loop)


@pytest.fixture
def tiny_cell(monkeypatch):
    """The CPU-sized cell of ``conftest.tiny``, with every per-layer
    metric read (no cell filter) and half the traced span made busy on a
    TPU plane the CPU trace lacks."""
    import jax

    import run

    monkeypatch.setattr(run, "require_tpu", lambda chips: jax.devices())
    monkeypatch.setattr(run, "enable_cache", lambda: None)
    monkeypatch.setattr(run, "load_peaks", lambda: {
        jax.devices()[0].device_kind: {"bf16_flops_per_s": 1e12,
                                       "hbm_bytes_per_s": 1e11}})
    real = devtrace.extract

    def with_device(path):
        ex = real(path)
        (win,) = [s for s in ex["spans"] if s[0] == "traced"]
        half = [win[1], win[2] // 2]
        ex["devices"]["/device:TPU:0"] = {
            "ops": [half], "programs": [["jit__decode_window_jit", *half]]}
        return ex

    monkeypatch.setattr(devtrace, "extract", with_device)
    cfg = json.loads((DATA / "tiny.json").read_text())
    cfg["check"] = dict(cfg["check"], logit_gap_limit=0.03)
    mix = json.loads((DATA / "tiny-mix.json").read_text())
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["per_layer"] = [{k: v for k, v in m.items() if k != "workloads"}
                          for m in bench["per_layer"]]
    cell = {"name": "tiny", "config": "tiny", "traffic": "tiny-mix",
            "chips": 1}
    return bench, cell, cfg, mix


def test_a_traced_run_reads_the_engine_phases(tiny_cell):
    res = phases.traced_run(*tiny_cell, seed=4000000125, seconds=3.0)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert m["host_gap_ms"]["value"] > 0
    assert m["prefill_pass_ms"]["value"] > 0
    ph = res["phases"]
    split = dict(ph["idle_split"])
    assert any(k.startswith("service.run/engine.") for k in split)
    dev = res["device"]
    assert sum(split.values()) == pytest.approx(
        dev["window_s"] - dev["busy_s"], rel=1e-6)
    assert sum(v for _, v in res["breakdown"]["idle_gaps"]) == pytest.approx(
        sum(split.values()), rel=1e-6)
    assert 0 <= ph["idle_in_programs_s"] <= sum(split.values())
    assert 0 <= ph["admit_wait_p90_s"] <= m["queue_wait_p90_s"]["value"]
    assert ph["host_gap_ms_traced"] > 0 and ph["engine_spans_per_s"] > 0
    traced = ph["counters_traced"]
    assert traced["windows"] > 0
    assert {"step_s", "admit_s", "prefill_s", "swap_s", "prep_s",
            "device_wait_s", "replay_s"} <= set(traced)
