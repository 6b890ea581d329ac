"""Drive the service through one measured window, stamping host time.

Agents are submitted through ``AgentService.submit`` when their wall-clock
due time passes; the service advances through ``AgentService.run``
``max_window`` engine iterations at a time, and the loop sleeps while
nothing is in flight.  Every stamp is ``time.perf_counter()``
taken in the service's own hooks.  Arrivals stop when the window closes;
what arrived is then drained, up to ``drain_cap_s`` seconds.

The engine streams every token it samples except the first, which its
prefill samples and feeds straight back; the ``on_admit`` hook reads that
token from the engine's slot (``slot_last_tok``) so the check can replay
the whole served sequence.

A traced run traces only the last ``trace_s`` seconds of the window: a
v5e trace holds some 250,000 operations a second, and writing them out
stalls the host for many seconds.  The profiler starts there and stops
when the window closes; both stalls are recorded in ``paused`` so that a
reader can take the benchmark's own instrumentation out of a wait.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import numpy as np

import devtrace


@dataclasses.dataclass
class Req:
    agent: int
    stage: int
    rid: int
    ready: float              # stage start: agent due time or previous stage end
    admit: float
    first_token: Optional[int] = None   # sampled by the prefill, not streamed
    tokens: list = dataclasses.field(default_factory=list)
    stamps: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class AgentRun:
    index: int                # position in the traffic's sequence
    due: float
    submit: float
    stage_ready: list
    done: Optional[float] = None
    rids: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Served:
    t0: float                 # window opens
    seconds: float            # nominal window length
    t_close: float            # when the loop saw the window closed
    t_end: float              # when draining stopped
    agents: list              # AgentRun, in submission order
    reqs: dict                # rid -> Req
    counters_open: dict       # engine metrics when the window opened
    counters_close: dict      # ... when it closed
    counters_end: dict        # ... when draining stopped
    compiles_in_window: int
    trace_window: Optional[tuple] = None    # (start, end) of the traced part
    counters_trace: Optional[tuple] = None  # engine metrics at its two ends
    paused: list = dataclasses.field(default_factory=list)  # tracer stalls

    def inflight_intervals(self) -> list:
        """Host-clock intervals in which an agent was in flight (an agent
        between two stages has its next stage submitted at once)."""
        return [[a.due, a.done if a.done is not None else self.t_end]
                for a in self.agents]


def serve(service, traffic, specs, *, seconds: float, drain_cap_s: float,
          max_window: int, trace_s: float = 0.0, tracer=None,
          compile_count: Callable[[], int] = lambda: 0) -> Served:
    """Serve one window.  ``tracer`` (with ``start()`` and ``stop()``)
    traces the window's last ``trace_s`` seconds."""
    from repro.api.events import AgentHooks

    engine = service.backend.engine
    agents: list[AgentRun] = []
    reqs: dict[int, Req] = {}
    free_due: list[float] = []        # closed loop: clients ready to submit

    def hooks(a: AgentRun) -> AgentHooks:
        def on_admit(ev):
            r = Req(a.index, len(a.stage_ready) - 1, ev.rid,
                    a.stage_ready[-1], time.perf_counter())
            for slot, req in engine.slot_req.items():
                if req.rid == ev.rid:
                    r.first_token = int(engine.slot_last_tok[slot])
            reqs[ev.rid] = r
            a.rids.append(ev.rid)

        def on_token(ev):
            r = reqs[ev.rid]
            r.tokens.append(int(ev.token))
            r.stamps.append(time.perf_counter())

        def on_stage_complete(ev):
            a.stage_ready.append(time.perf_counter())

        def on_complete(ev):
            a.done = time.perf_counter()
            if traffic.loop == "closed":
                free_due.append(a.done)

        return AgentHooks(on_admit=on_admit, on_token=on_token,
                          on_stage_complete=on_stage_complete,
                          on_complete=on_complete)

    def submit(i: int, due: float) -> None:
        a = AgentRun(i, due, time.perf_counter(), [due])
        agents.append(a)
        with devtrace.span("submit"):
            service.submit(specs[i], hooks=hooks(a))

    def paused_call(fn) -> None:
        p0 = time.perf_counter()
        fn()
        paused.append((p0, time.perf_counter()))

    n = len(specs)
    nxt = 0
    paused: list = []
    traced = None
    trace_window = counters_trace = None
    counters_open = dict(engine.metrics)
    compiles0 = compile_count()
    t0 = time.perf_counter()
    t_close = t_drain = None
    if traffic.loop == "closed":
        free_due.extend([t0] * traffic.clients)
    while True:
        now = time.perf_counter()
        if (tracer is not None and trace_window is None
                and now >= t0 + seconds - trace_s):
            paused_call(tracer.start)
            trace_window = (time.perf_counter(), None)
            counters_trace = (dict(engine.metrics), None)
            # made only now: a span made before the profiler started is
            # never recorded
            traced = devtrace.span("traced")
            traced.__enter__()
        if t_close is None and now >= t0 + seconds:
            t_close = now
            counters_close = dict(engine.metrics)
            compiles = compile_count() - compiles0
            if trace_window is not None:
                traced.__exit__(None, None, None)
                trace_window = (trace_window[0], time.perf_counter())
                counters_trace = (counters_trace[0], dict(engine.metrics))
                paused_call(tracer.stop)
            t_drain = time.perf_counter()
        if t_close is None:
            if traffic.loop == "open":
                while nxt < n and t0 + traffic.arrivals[nxt] <= now:
                    submit(nxt, t0 + traffic.arrivals[nxt])
                    nxt += 1
            else:
                while free_due and nxt < n:
                    submit(nxt, free_due.pop(0))
                    nxt += 1
        inflight = sum(a.done is None for a in agents)
        if inflight:
            if t_drain is not None and now > t_drain + drain_cap_s:
                break
            with devtrace.span("service.run"):
                service.run(service.now + max_window)
        elif t_close is not None:
            break
        else:
            wake = t0 + seconds
            if tracer is not None and trace_window is None:
                wake = min(wake, t0 + seconds - trace_s)
            if traffic.loop == "open" and nxt < n:
                wake = min(wake, t0 + traffic.arrivals[nxt])
            with devtrace.span("wait_arrival"):
                time.sleep(max(0.0, wake - time.perf_counter()))
    return Served(
        t0=t0, seconds=seconds, t_close=t_close, t_end=time.perf_counter(),
        agents=agents, reqs=reqs, counters_open=counters_open,
        counters_close=counters_close, counters_end=dict(engine.metrics),
        compiles_in_window=compiles, trace_window=trace_window,
        counters_trace=counters_trace, paused=paused,
    )


def lateness(served: Served) -> np.ndarray:
    """How late each submission ran after its due time (seconds)."""
    return np.asarray([a.submit - a.due for a in served.agents])
