#!/usr/bin/env python3
"""The engine's own host phases in one traced window.

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s>

``ServeEngine`` opens a profiler span ``engine.<phase>`` around each host
phase of its loop (``repro.engine.trace``: step, admit, prefill, swap,
prep, device_wait, replay), and stamps each request when it is queued
(``t_queued``) and when admission pops it (``t_admit``).  ``run.py`` reads
the benchmark's own spans and the engine's counters; this script serves
one window as ``run.py --trace 1`` does, prints that run's result line,
and adds under ``phases``:

* ``idle_split``: the device's idle seconds in the traced part, each put
  down to the innermost span open at that instant: ``service.run/engine.
  replay`` is idle time inside the benchmark's ``service.run`` span while
  the engine replayed tokens, ``service.run`` alone the span's own time
  outside every engine phase.  The labels sum to the traced window less
  the device's busy time, as ``breakdown.idle_gaps`` does;
* ``idle_in_programs_s``: the part of that idle time that falls inside a
  program's span, between the operations of one program;
* ``admit_wait_p90_s``: p90, over the requests ready in the window, of
  ``t_admit - t_queued`` less the tracer's stalls: the scheduler's own
  queue, without the harness's lateness or the prefill pass;
* ``host_gap_ms`` over the untraced part of the window and over the traced
  part (the cost of recording the spans), ``engine_spans_per_s``, and the
  growth of every engine counter over the traced part.

To read the request stamps it wraps the engine's waiting queue's ``push``
(an observer: the request goes on unchanged).
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ENGINE_PREFIX = "engine."


def engine_spans(path: str) -> list:
    """``[[phase, start_ns, dur_ns], ...]`` of the trace's ``engine.*``
    host spans (``phase`` without the prefix)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(ENGINE_PREFIX):
                    out.append([ev.name[len(ENGINE_PREFIX):], ev.start_ns,
                                ev.duration_ns])
    return out


def _paint(edges: np.ndarray, spans, lo: float, hi: float) -> list:
    """The innermost of ``spans`` ([name, start, dur]) covering each
    segment between consecutive ``edges``: spans painted longest first, so
    a phase nested in another overwrites it."""
    label = [""] * (len(edges) - 1)
    for name, s, d in sorted(spans, key=lambda x: -x[2]):
        a, b = max(s, lo), min(s + d, hi)
        if b <= a:
            continue
        i, j = np.searchsorted(edges, [a, b])
        label[i:j] = [name] * (j - i)
    return label


def _cumulative(iv: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Length of the disjoint sorted intervals ``iv`` before each ``t``."""
    if not len(iv):
        return np.zeros(len(t))
    full = np.concatenate([[0.0], np.cumsum(iv[:, 1] - iv[:, 0])])
    j = np.searchsorted(iv[:, 1], t, side="right")   # intervals ended
    part = np.where(j < len(iv),
                    np.clip(t - iv[np.minimum(j, len(iv) - 1), 0], 0, None),
                    0.0)
    return full[j] + part


def idle_split(ex: dict, eng_spans: list, window_perf: tuple) -> dict:
    """The device's idle seconds in the traced window by innermost span.

    ``ex`` is ``devtrace.extract``'s (benchmark spans, device operations),
    ``eng_spans`` is ``engine_spans``'s, ``window_perf`` the traced
    window on the host clock.  Averaged over the devices that ran
    operations."""
    import devtrace

    (win,) = [s for s in ex["spans"] if s[0] == "traced"]
    w0 = float(win[1])
    w1 = w0 + (window_perf[1] - window_perf[0]) * 1e9
    bench = [s for s in ex["spans"] if s[0] != "traced"]
    edges = np.unique(np.clip(np.asarray(
        [w0, w1] + [t for _, s, d in bench + eng_spans for t in (s, s + d)],
        np.float64), w0, w1))
    outer = _paint(edges, bench, w0, w1)
    inner = _paint(edges, eng_spans, w0, w1)
    labels = [
        (f"{o}/{ENGINE_PREFIX}{i}" if i else o) if o
        else (ENGINE_PREFIX + i if i else "other")
        for o, i in zip(outer, inner)
    ]
    devs = [d for d in ex["devices"].values() if d.get("ops")]
    out: dict[str, float] = {}
    for dev in devs:
        ops = np.asarray(dev["ops"], np.float64).reshape(-1, 2)
        busy = devtrace.clip(devtrace.union(
            np.stack([ops[:, 0], ops[:, 0] + ops[:, 1]], 1)), w0, w1)
        idle = np.diff(_cumulative(devtrace.gaps(busy, w0, w1), edges))
        for label, t in zip(labels, idle):
            if t > 0:
                out[label] = out.get(label, 0.0) + t / 1e9 / len(devs)
    return out


def idle_in_programs(ex: dict, window_perf: tuple) -> float:
    """The device's idle seconds in the traced window that fall inside a
    program's span (``XLA Modules``): gaps between the operations of one
    program, as against gaps between programs.  Averaged over the devices
    that ran operations."""
    import devtrace

    (win,) = [s for s in ex["spans"] if s[0] == "traced"]
    w0 = float(win[1])
    w1 = w0 + (window_perf[1] - window_perf[0]) * 1e9
    devs = [d for d in ex["devices"].values() if d.get("ops")]
    total = 0.0
    for dev in devs:
        ops = np.asarray(dev["ops"], np.float64).reshape(-1, 2)
        busy = devtrace.clip(devtrace.union(
            np.stack([ops[:, 0], ops[:, 0] + ops[:, 1]], 1)), w0, w1)
        progs = devtrace.clip(devtrace.union(
            [[s, s + d] for _, s, d in dev.get("programs", [])]), w0, w1)
        total += devtrace.length(devtrace.intersect(
            devtrace.gaps(busy, w0, w1), progs)) / 1e9
    return total / len(devs)


def host_gap_ms(start: dict, end: dict):
    """``host_gap_ms``'s reading between two engine counter snapshots."""
    import run

    phases = run.load_module(BENCH / "metrics", "host_gap_ms").HOST_PHASES
    windows = end["windows"] - start["windows"]
    if not windows:
        return None
    return sum(end[k] - start[k] for k in phases) / windows * 1e3


def admit_wait_p90_s(run, queued: dict):
    """p90 over the window's requests of admission pop less queue push,
    outside the tracer's stalls."""
    import stats

    return stats.percentile(
        [run.unpaused(queued[r.rid].t_queued, queued[r.rid].t_admit)
         for r in run.window_requests()], 90)


def traced_run(bench: dict, cell: dict, cfg: dict, mix: dict, seed: int,
               seconds: float) -> dict:
    """Serve one traced window of a cell (as ``run.run_cell`` does with
    ``trace``); ``run.py``'s result line with ``phases`` added."""
    import devtrace
    import loadgen
    import run
    import stats

    s = run.setup(cell, cfg, [mix], seed, seconds)
    engine = s.service.backend.engine
    queued: dict = {}
    push = engine.waiting.push

    def record(req):
        queued[req.rid] = req
        push(req)

    engine.waiting.push = record
    tracer = run.Tracer()
    served = loadgen.serve(
        s.service, s.traffics[0], s.specs[0], seconds=seconds,
        drain_cap_s=mix["drain_cap_s"],
        max_window=cfg["engine"]["max_window"], trace_s=mix["trace_s"],
        tracer=tracer, compile_count=s.compiles,
    )
    mem = s.devs[0].memory_stats() or {}
    path = devtrace.find_xplane(tracer.dir)
    ex, eng = devtrace.extract(path), engine_spans(path)
    shutil.rmtree(tracer.dir, ignore_errors=True)
    summary = devtrace.summarize(ex, served.trace_window,
                                 served.inflight_intervals())
    r = stats.Run(served=served, traffic=s.traffics[0], family=s.family,
                  dims=s.dims, engine=cfg["engine"], peak=s.peak,
                  setup_s=s.setup_s, memory=mem, trace=summary)
    t0, t1 = served.trace_window
    before, after = served.counters_trace
    phases = {
        "idle_split": sorted(idle_split(ex, eng, served.trace_window).items(),
                             key=lambda kv: -kv[1]),
        "idle_in_programs_s": idle_in_programs(ex, served.trace_window),
        "admit_wait_p90_s": admit_wait_p90_s(r, queued),
        "host_gap_ms_untraced": host_gap_ms(served.counters_open, before),
        "host_gap_ms_traced": host_gap_ms(before, after),
        "engine_spans_per_s": len(eng) / (t1 - t0),
        "counters_traced": {k: after[k] - before[k] for k in after},
    }
    result = run.finish(bench, cell, s, r, seed, True)
    result["phases"] = phases
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    import run

    print(json.dumps(traced_run(*run.load_cell(args.workload), args.seed,
                                args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
