"""The profiler trace of a ``--trace 1`` run, and its reduction to numbers.

The benchmark opens host spans of its own (``bench.<name>``, through
``jax.profiler.TraceAnnotation``) around every call into the service, so the
device's idle gaps can be put down to what the host was doing.  The span
``bench.traced`` covers the traced part of the window; its start was
stamped on the host clock too, which puts host stamps and trace times on
one clock.

``extract`` reads an ``.xplane.pb`` into a small plain structure: device
operation and program intervals, and the benchmark's host spans.
``summarize`` reduces that structure to busy time, per-program device time
and idle time by host span, inside the window.  Both are checked on a
recorded trace from a TPU v5e in ``tests/``.
"""

from __future__ import annotations

import glob
import os

import numpy as np

SPAN_PREFIX = "bench."
#: device trace lines: operations (what "busy" counts) and whole programs
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def span(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {found}")
    return found[0]


def extract(path: str) -> dict:
    """The trace's device intervals and the benchmark's host spans.

    Returns ``{"devices": {plane: {"ops": [[start_ns, dur_ns], ...],
    "programs": [[name, start_ns, dur_ns], ...]}}, "spans": [[name,
    start_ns, dur_ns], ...], "lines": {plane: {line: n_events}}}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans, lines = {}, [], {}
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:")
        lines[plane.name] = {}
        for line in plane.lines:
            n = 0
            if is_dev and line.name == OPS_LINE:
                ops = devices.setdefault(plane.name, {}).setdefault("ops", [])
                for ev in line.events:
                    ops.append([ev.start_ns, ev.duration_ns])
                    n += 1
            elif is_dev and line.name == MODULES_LINE:
                progs = devices.setdefault(plane.name, {}).setdefault(
                    "programs", [])
                for ev in line.events:
                    progs.append([ev.name, ev.start_ns, ev.duration_ns])
                    n += 1
            elif not is_dev:
                for ev in line.events:
                    n += 1
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append([ev.name[len(SPAN_PREFIX):],
                                      ev.start_ns, ev.duration_ns])
            else:
                n = sum(1 for _ in line.events)
            lines[plane.name][line.name] = n
    return {"devices": devices, "spans": spans, "lines": lines}


def union(intervals) -> np.ndarray:
    """Sorted disjoint [start, end) rows covering the given intervals."""
    iv = np.asarray(intervals, np.float64).reshape(-1, 2)
    iv = iv[iv[:, 1] > iv[:, 0]]
    if not len(iv):
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    first = np.flatnonzero(np.r_[True, iv[1:, 0] > ends[:-1]])
    last = np.r_[first[1:] - 1, len(iv) - 1]
    return np.stack([iv[first, 0], ends[last]], 1)


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(np.asarray(iv, np.float64).reshape(-1, 2), lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def length(iv) -> float:
    iv = np.asarray(iv, np.float64).reshape(-1, 2)
    return float(np.sum(iv[:, 1] - iv[:, 0]))


def intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Intersection of two unions of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return np.asarray(out, np.float64).reshape(-1, 2)


def gaps(busy: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The complement of ``busy`` (disjoint, sorted) inside [lo, hi)."""
    edges = np.concatenate([[lo], np.asarray(busy).ravel(), [hi]])
    iv = edges.reshape(-1, 2)
    return iv[iv[:, 1] > iv[:, 0]]


def summarize(ex: dict, window_perf: tuple, inflight_perf) -> dict:
    """Reduce an extracted trace to the window's device numbers.

    ``window_perf`` is the traced window's (start, end) on the host clock
    (``time.perf_counter`` seconds), its start that of the
    ``bench.traced`` span; ``inflight_perf`` the host-clock
    intervals in which at least one request was in flight.  Times out are
    in seconds, averaged over the devices that ran operations."""
    (win,) = [s for s in ex["spans"] if s[0] == "traced"]
    w0 = float(win[1])
    t0, t1 = window_perf
    to_ns = lambda t: w0 + (t - t0) * 1e9
    w1 = to_ns(t1)
    inflight = clip(union([[to_ns(a), to_ns(b)] for a, b in inflight_perf]),
                    w0, w1)
    devs = [d for d in ex["devices"].values() if d.get("ops")]
    if not devs:
        raise RuntimeError("the trace holds no device operation")
    # the benchmark's spans below the window do not overlap one another
    spans: dict[str, list] = {}
    for name, s, d in ex["spans"]:
        if name != "traced":
            spans.setdefault(name, []).append([s, s + d])
    spans = {k: union(v) for k, v in spans.items()}
    n = len(devs)
    busy_s, idle_inflight = [], []
    programs: dict[str, float] = {}
    idle_by_span: dict[str, float] = {}
    for dev in devs:
        ops = np.asarray(dev["ops"], np.float64).reshape(-1, 2)
        busy = clip(union(np.stack([ops[:, 0], ops[:, 0] + ops[:, 1]], 1)),
                    w0, w1)
        busy_s.append(length(busy) / 1e9)
        idle_inflight.append(
            (length(inflight) - length(intersect(busy, inflight))) / 1e9
        )
        for name, s, d in dev.get("programs", []):
            programs[name] = (programs.get(name, 0.0)
                              + length(clip([[s, s + d]], w0, w1)) / 1e9 / n)
        idle = gaps(busy, w0, w1)
        left = length(idle)
        for label, iv in spans.items():
            t = length(intersect(idle, iv))
            idle_by_span[label] = idle_by_span.get(label, 0.0) + t / 1e9 / n
            left -= t
        idle_by_span["other"] = idle_by_span.get("other", 0.0) + left / 1e9 / n
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": float(np.mean(busy_s)),
        "inflight_s": length(inflight) / 1e9,
        "idle_inflight_s": float(np.mean(idle_inflight)),
        "programs": programs,
        "idle_by_span": idle_by_span,
    }
