"""The reduction from a profiler trace to busy time, program time and idle
time by host span: on a hand-made trace, and on one recorded on a v5e."""

import gzip
import json

import pytest

import devtrace
from conftest import DATA

MS = 1_000_000     # ns


def hand_trace():
    """Window [0, 10) ms on the host clock 1000.0 s.  Device ops busy
    [1, 3) and [2, 4) (overlapping) and [6, 7) ms; host spans:
    service.run [0, 5), wait_arrival [5, 10)."""
    return {
        "devices": {"/device:TPU:0": {
            "ops": [[1 * MS, 2 * MS], [2 * MS, 2 * MS], [6 * MS, 1 * MS],
                    [12 * MS, 1 * MS]],
            "programs": [["jit__decode_window_jit", 1 * MS, 3 * MS],
                         ["jit__prefill_write_jit", 6 * MS, 1 * MS],
                         ["jit__decode_window_jit", 9.5 * MS, 2 * MS]],
        }},
        "spans": [["traced", 0, 10 * MS], ["service.run", 0, 5 * MS],
                  ["wait_arrival", 5 * MS, 5 * MS]],
        "lines": {},
    }


def test_union_merges_overlaps():
    u = devtrace.union([[5, 6], [0, 2], [1, 3], [3, 4], [7, 7]])
    assert u.tolist() == [[0, 4], [5, 6]]


def test_summary_by_hand():
    s = devtrace.summarize(hand_trace(), (1000.0, 1000.010),
                           [[1000.0005, 1000.0065]])
    assert s["window_s"] == pytest.approx(0.010)
    assert s["busy_s"] == pytest.approx(0.004)        # [1,4) and [6,7)
    # in flight [0.5, 6.5) ms: busy [1, 4) and [6, 6.5) -> idle 2.5 ms
    assert s["inflight_s"] == pytest.approx(0.006)
    assert s["idle_inflight_s"] == pytest.approx(0.0025)
    # programs clipped to the window: decode 3 + 0.5 ms, prefill 1 ms
    assert s["programs"]["jit__decode_window_jit"] == pytest.approx(0.0035)
    assert s["programs"]["jit__prefill_write_jit"] == pytest.approx(0.001)
    # idle [0,1) and [4,5) under service.run, [5,6) and [7,10) waiting
    assert s["idle_by_span"]["service.run"] == pytest.approx(0.002)
    assert s["idle_by_span"]["wait_arrival"] == pytest.approx(0.004)


def busy_by_loop(ops, lo, hi):
    """Busy time the plain way: walk the operations in start order."""
    total, end = 0.0, lo
    for start, dur in sorted(ops):
        a, b = max(start, end), min(start + dur, hi)
        if b > a:
            total += b - a
        end = max(end, min(start + dur, hi))
    return total


def test_recorded_v5e_trace():
    """A quarter second of granite-3-2b serving on one v5e, cut from a
    traced window: the reduction agrees with a plain loop over the
    operations, programs never overlap, and the idle time split by host
    span adds up to the window less the busy time."""
    with gzip.open(DATA / "v5e-trace.json.gz", "rt") as f:
        rec = json.load(f)
    ex = rec["trace"]
    s = devtrace.summarize(ex, tuple(rec["window_perf"]),
                           rec["inflight_perf"])
    (dev,) = ex["devices"].values()
    assert len(dev["ops"]) > 10_000
    assert s["window_s"] == pytest.approx(0.25)
    assert s["busy_s"] == pytest.approx(
        busy_by_loop(dev["ops"], 0, 0.25e9) / 1e9)
    assert 0 < s["busy_s"] <= s["window_s"]
    assert sum(s["programs"].values()) <= s["window_s"] + 1e-9
    assert any("decode_window" in name for name in s["programs"])
    assert sum(s["idle_by_span"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"])
    assert 0 <= s["idle_inflight_s"] <= s["inflight_s"] <= s["window_s"]


def test_a_traced_run_reads_its_own_trace(tiny, monkeypatch):
    """A ``--trace 1`` run at the CPU size, driven end to end: the profiler
    records the benchmark's ``traced`` span, and the result carries the
    per-layer metrics, ``busy_s``/``window_s`` and a breakdown.  The CPU
    trace has no device plane, so one busy half of the span is added as
    a TPU's would be."""
    real = devtrace.extract

    def with_device(path):
        ex = real(path)
        (win,) = [s for s in ex["spans"] if s[0] == "traced"]
        half = [win[1], win[2] // 2]
        ex["devices"]["/device:TPU:0"] = {
            "ops": [half], "programs": [["jit__decode_window_jit", *half]]}
        return ex

    monkeypatch.setattr(devtrace, "extract", with_device)
    res = tiny(4000000124, seconds=3.0, limit=0.03, trace=True)
    assert res["correct"], res["checks"]
    dev = res["device"]
    assert dev["busy_s"] == pytest.approx(dev["window_s"] / 2, rel=1e-3)
    assert {"steps_per_window", "slot_occupancy", "decode_step_ms",
            "device_idle_frac"} <= set(res["metrics"])
    assert res["breakdown"]["device_ops"][0][0] == "jit__decode_window_jit"
