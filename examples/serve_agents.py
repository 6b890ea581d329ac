"""End-to-end driver: one workload spec, two backends, one AgentService.

    PYTHONPATH=src python examples/serve_agents.py --backend engine
    PYTHONPATH=src python examples/serve_agents.py --backend sim

The full production path in miniature, now behind the unified serving API:
the 9-class agent workload sampler generates task-parallel agents with
synthetic prompts and bursty (Mooncake-like) arrival times; the per-class
TF-IDF+MLP predictor predicts each agent's KV token-time at arrival; the
scheduler (any name registered with ``@register_scheduler``) computes its
priority keys; and :class:`repro.api.AgentService` streams the agents into
the chosen backend *online* — agents are submitted with future arrival
times and enter the system mid-run, exactly like live traffic.

``--backend engine`` runs REAL model prefill/decode steps (paged KV
accounting, swap-on-pressure, non-preemptive admission); ``--backend sim``
runs the identical AgentSpec list on the discrete-event cluster.

``--replicas N`` serves the workload on an N-way
:class:`repro.api.ReplicatedBackend` fleet: the router (``--router``, any
name registered with ``@repro.api.register_router`` — ``round_robin``,
``least_loaded``, or ``memory_cost_aware``, which places by the
predictor's cost estimate) shards agents across N child backends, the
children advance in lockstep, and their per-replica GPS clocks are
reconciled into one global virtual time whose lag is reported in the
backend metrics.  Every lifecycle event then carries the serving replica.

``--stream`` turns on backend-uniform per-token streaming: the engine
always samples real tokens; the sim switches on its discretized
``token_events`` decode model.  The driver then reports each agent's
time-to-first-token and decode throughput straight off the service's
:class:`~repro.api.service.MetricsRecorder` (the same fields the drained
``result.latency`` percentiles are computed from), plus the fleet
TTFT/TBT percentile row after drain.  The engine serves token demands divided by its ``token_scale``,
so its raw event counts are engine-scale; the printed tok/s is converted
back to workload scale, making the throughput figures comparable across
backends.

``--closed-loop`` swaps the fixed task-graph workload for the closed-loop
session family (multi-turn chat + react tool loops): each agent's next
stage is generated by its session callback only after the previous stage
completes, and is resubmitted mid-run through the serving layer.

``--prefix-cache`` turns on prefix-aware KV reuse on either backend (the
engine's content-hash block index over ``repro.kvcache.prefix``, the
sim's analytic hit model).  Pair it with ``--closed-loop``: the session
families carry deterministic canonical prompt streams with a shared
per-family system prefix, so consecutive turns and sibling sessions
actually share cache content.  The drained ``backend metrics`` then
include ``prefill_tokens_saved`` (backend-native token scale) and
``hit_fractions`` (per-agent cached/total prefill fraction), and each
per-agent line reports its hit fraction.

``--fused-prefill`` (engine backend only) streams each admitted prompt
into the jitted decode windows one chunk-slice per iteration instead of
stalling every running decoder for a whole prefill pass at admission —
the TTFT/TBT interference fix ``tests/test_slo_metrics.py`` holds to its
TTFT p99 claim.
All flag combinations (``--stream --prefix-cache --closed-loop
--fused-prefill``) exercise the same serving path.

``--chaos SEED`` (requires ``--replicas > 1``) arms a seeded
:class:`repro.api.FaultPlan` — one replica crashes mid-run — plus the
fleet progress watchdog: the dead child's agents fail over to survivors
with their accrued virtual time carried across, and the drained metrics
report ``replica_failures`` / ``agents_requeued``.  Same seed, same
workload => bit-identical chaos run.

``--fleet-workers N`` (requires ``--replicas > 1``) advances the fleet's
children concurrently on an N-thread pool — the serving output is
bit-identical to the sequential lockstep loop, so the flag is purely a
wall-clock knob.  ``--steal-threshold X`` arms load-triggered work
stealing: every ``--steal-interval`` seconds, a replica whose
capacity-normalized queued backlog exceeds X times the fleet mean
migrates its newest queued (never-admitted) agents to underloaded
replicas; the drained metrics report ``agents_stolen`` / ``steals`` and
each migration fires an ``AgentRequeued`` event.
"""

import argparse
import time

import numpy as np

from repro.api import (
    AgentHooks,
    router_names,
    service_for_backend,
    specs_from_classes,
    specs_from_closed_loop,
)
from repro.api.workload import DEFAULT_CLASSES
from repro.core import scheduler_names
from repro.launch.compile_cache import enable_compile_cache
from repro.predictor import AgentCostPredictor
from repro.workloads import sample_agent


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="engine",
                    choices=("engine", "sim"))
    ap.add_argument("--scheduler", default="justitia",
                    choices=scheduler_names())
    ap.add_argument("--n-agents", type=int, default=8)
    ap.add_argument("--window-s", type=float, default=30.0)
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--router", default="memory_cost_aware",
                    choices=router_names())
    ap.add_argument("--stream", action="store_true",
                    help="per-token streaming on either backend (sim: "
                         "discretized token_events decode model)")
    ap.add_argument("--closed-loop", action="store_true",
                    help="serve the closed-loop session family (multi-turn "
                         "chat / react tool loops) instead of fixed graphs")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="prefix-aware KV reuse (engine: content-hash "
                         "block sharing; sim: analytic hit model); adds "
                         "prefill_tokens_saved + per-agent hit_fractions "
                         "to the backend metrics — best with "
                         "--closed-loop, whose sessions share prompt "
                         "prefixes")
    ap.add_argument("--fused-prefill", action="store_true",
                    help="(engine) stream each admitted prompt into the "
                         "fused decode windows one chunk-slice per "
                         "iteration instead of stalling every decoder "
                         "for a whole prefill pass at admission")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="(replicated fleets) inject a seeded FaultPlan — "
                         "one replica crashes mid-window — with the "
                         "progress watchdog armed; agents on the dead "
                         "replica fail over to survivors")
    ap.add_argument("--watchdog-timeout", type=float, default=None,
                    metavar="S",
                    help="(replicated fleets) suspect a busy, lagging "
                         "replica after S seconds without progress "
                         "(--chaos default: 2%% of the window, min 0.25s)")
    ap.add_argument("--watchdog-retries", type=int, default=None,
                    help="suspect probes before a replica is declared "
                         "dead (fleet default: 3)")
    ap.add_argument("--watchdog-backoff", type=float, default=None,
                    help="multiplier between successive suspect probes "
                         "(fleet default: 2.0)")
    ap.add_argument("--admission-watermark", type=float, nargs=2,
                    default=None, metavar=("LOW", "HIGH"),
                    help="watermark admission control: defer admissions "
                         "when free pool drops below LOW (fraction), "
                         "resume above HIGH")
    ap.add_argument("--suspend-retention", default=None,
                    choices=("hold", "spill", "drop"),
                    help="KV retention for agents suspended through "
                         "tool-call think time (closed-loop sessions "
                         "with think-time families, e.g. 'tooluse'): "
                         "hold pins the stage KV on device, spill copies "
                         "it to host staging, drop releases it and "
                         "re-prefills (cheap with --prefix-cache)")
    ap.add_argument("--fleet-workers", type=int, default=None, metavar="N",
                    help="(replicated fleets) advance the children "
                         "concurrently on an N-thread pool; same plan + "
                         "same workload stays bit-identical to the "
                         "sequential lockstep loop")
    ap.add_argument("--steal-threshold", type=float, default=None,
                    metavar="X",
                    help="(replicated fleets) load-triggered work "
                         "stealing: migrate queued, never-admitted agents "
                         "off a replica whose capacity-normalized backlog "
                         "exceeds X times the fleet mean (X > 1)")
    ap.add_argument("--steal-interval", type=float, default=None,
                    metavar="S",
                    help="workload seconds between stealing passes "
                         "(fleet default: 1.0)")
    ap.add_argument("--closed-loop-classes", default=None,
                    metavar="A,B,...",
                    help="comma-separated closed-loop family names for "
                         "--closed-loop (default: the think-free mix; "
                         "'tooluse' adds think-time suspensions)")
    ap.add_argument("--arch", default=None,
                    help="engine model arch (default h2o-danube-1.8b; "
                         "--fused-prefill defaults to granite-3-2b "
                         "instead, since fused windows need a full-cache "
                         "attention family, not danube's ring buffer)")
    args = ap.parse_args()
    arch = args.arch or (
        "granite-3-2b" if args.fused_prefill else "h2o-danube-1.8b"
    )

    rng = np.random.default_rng(0)

    if args.closed_loop:
        cl_kw = {}
        if args.closed_loop_classes:
            cl_kw["classes"] = tuple(
                c.strip() for c in args.closed_loop_classes.split(",")
                if c.strip()
            )
        specs = specs_from_closed_loop(
            rng, args.n_agents, args.window_s, **cl_kw
        )
    elif args.closed_loop_classes:
        ap.error("--closed-loop-classes requires --closed-loop")
    else:
        # train the per-class cost predictor on a small history
        print("training per-class MLP cost predictors...")
        samples = {}
        for cls in DEFAULT_CLASSES:
            hist = [sample_agent(rng, cls) for _ in range(60)]
            samples[cls] = ([a.prompt for a in hist],
                            [a.true_cost for a in hist])
        predictor = AgentCostPredictor(max_features=48)
        predictor.fit(samples, epochs=300)
        specs = specs_from_classes(
            rng, args.n_agents, args.window_s, predictor=predictor
        )
    fault_plan = None
    watchdog = args.watchdog_timeout
    if args.chaos is not None:
        if args.replicas <= 1:
            ap.error("--chaos requires --replicas > 1")
        from repro.api import FaultPlan

        lo, hi = 0.25 * args.window_s, 0.75 * args.window_s
        fault_plan = FaultPlan.seeded(
            args.chaos, args.replicas, crash_window=(lo, hi)
        )
        if watchdog is None:
            watchdog = max(0.02 * args.window_s, 0.25)
        for f in fault_plan.faults:
            print(f"chaos: replica {f.replica} {f.kind} at "
                  f"t={f.start:.1f}s (watchdog {watchdog:.2f}s)")
    if watchdog is not None and args.replicas <= 1:
        ap.error("--watchdog-timeout requires --replicas > 1")
    if args.fleet_workers is not None and args.replicas <= 1:
        ap.error("--fleet-workers requires --replicas > 1")
    if args.steal_threshold is not None and args.replicas <= 1:
        ap.error("--steal-threshold requires --replicas > 1")
    if args.steal_interval is not None and args.steal_threshold is None:
        ap.error("--steal-interval requires --steal-threshold")
    service = service_for_backend(
        args.backend, args.scheduler, arch=arch,
        pool_tokens=4096,
        replicas=args.replicas, router=args.router,
        stream=args.stream, prefix_cache=args.prefix_cache,
        fused_prefill=args.fused_prefill,
        fault_plan=fault_plan, watchdog_timeout=watchdog,
        watchdog_retries=args.watchdog_retries,
        watchdog_backoff=args.watchdog_backoff,
        admission_watermark=(
            tuple(args.admission_watermark)
            if args.admission_watermark is not None else None
        ),
        suspend_retention=args.suspend_retention,
        fleet_workers=args.fleet_workers,
        steal_threshold=args.steal_threshold,
        steal_interval=args.steal_interval,
    )

    fleet = (f" x{args.replicas} replicas via {args.router}"
             if args.replicas > 1 else "")
    print(f"streaming {args.n_agents} agents into the {args.backend} "
          f"backend{fleet} ({args.scheduler} scheduler, online arrivals "
          f"over {args.window_s:.0f}s)...")
    # engine token counts are engine-scale: convert to workload scale so
    # the printed tok/s is comparable across backends (read off the child
    # backend — the sim has no token_scale and streams full-scale counts)
    children = getattr(service.backend, "children", [service.backend])
    token_scale = getattr(children[0], "token_scale", 1)

    t0 = time.time()
    # TTFT/tok-s come straight off the service's MetricsRecorder — the
    # same fields latency_stats()/slo_stats() aggregate, so the per-agent
    # lines and the drained percentiles cannot drift apart
    rec = service.recorder

    hit_tok: dict[int, list] = {}

    def on_prefix_hit(ev):
        hit_tok.setdefault(ev.agent_id, []).append((ev.cached, ev.prefill))

    def on_complete(ev):
        line = (
            f"  t={ev.time:7.1f}s agent {ev.agent_id} done "
            f"(jct {ev.jct:.1f}s"
            + (f", replica {ev.replica}" if ev.replica is not None else "")
        )
        if args.prefix_cache and ev.agent_id in hit_tok:
            cached = sum(c for c, _ in hit_tok[ev.agent_id])
            line += f", {cached} cached prefill tokens"
        if args.stream and ev.agent_id in rec.first_token:
            handle = service.handles[ev.agent_id]
            ttft = rec.first_token[ev.agent_id] - rec.arrival.get(
                ev.agent_id, handle.arrival
            )
            span = max(ev.time - rec.first_token[ev.agent_id], 1e-9)
            line += (f", ttft {ttft:.2f}s, "
                     f"{handle.token_count * token_scale / span:.1f} "
                     f"tok/s")
        print(line + ")")

    def on_requeued(ev):
        print(f"  t={ev.time:7.1f}s agent {ev.agent_id} requeued "
              f"(replica {ev.from_replica} -> {ev.replica})")

    hooks = AgentHooks(
        on_complete=on_complete,
        on_prefix_hit=on_prefix_hit if args.prefix_cache else None,
        on_requeued=on_requeued if args.chaos is not None else None,
    )
    for spec in specs:
        service.submit(spec, hooks=hooks)
    result = service.drain()
    wall = time.time() - t0

    print(f"served {args.n_agents} agents on backend={result.backend} "
          f"in {wall:.1f}s wall")
    print("jct:", result.stats.row())
    if args.stream and result.latency is not None:
        print("latency:", result.latency.row())
    print("events:", result.event_counts)
    print("backend metrics:", result.metrics)
    for r, stats in result.per_replica.items():
        print(f"replica {r}: {stats.row()}")


if __name__ == "__main__":
    main()
