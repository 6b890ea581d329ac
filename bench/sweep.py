#!/usr/bin/env python3
"""Find a cell's knee once: serve its open-loop traffic at several rates.

    python3 bench/sweep.py --workload <cell> --seconds <s> --seed <n> \
        --rates <r1,r2,...>

One process sets the cell up as ``run.py`` does (``run.setup``), warmed
for every rate's traffic, then serves one window per rate (``run.measure``;
arrival rate in agents/s, the mix's bursts otherwise unchanged) and
drains it.  Per rate it prints one JSON line: the offered and the completed
agent rates inside the window, the agents still in flight when the window
closed, the drain time, the tokens per second and the latency tails.  The
knee is the highest rate whose backlog does not grow through the window; a
cell's rate is fixed in its mix file from it, and the benchmark's runs
never sweep.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    import run

    bench, cell, cfg, mix = run.load_cell(args.workload)
    rates = [float(r) for r in args.rates.split(",")]
    s = run.setup(cell, cfg, [dict(mix, rate_agents_per_s=r) for r in rates],
                  args.seed, args.seconds)
    for k, rate in enumerate(rates):
        r = run.measure(s, k, args.seconds, False)
        served = r.served
        read = lambda m: run.load_module(BENCH / "metrics", m).read(r)
        done_in = sum(a.done is not None and a.done < r.t1
                      for a in served.agents)
        print(json.dumps({
            "rate": rate,
            "offered_agents_per_s": len(served.agents) / args.seconds,
            "completed_agents_per_s": done_in / args.seconds,
            "inflight_at_close": sum(a.done is None or a.done >= r.t1
                                     for a in served.agents),
            "drain_s": served.t_end - served.t_close,
            "unfinished": sum(a.done is None for a in served.agents),
            "requests_in_window": len(r.window_requests()),
            "tokens_per_s": read("tokens_per_s"),
            "ttft_p90_s": read("ttft_p90_s"),
            "jct_mean_s": read("jct_mean_s"),
            "tpot_p90_ms": read("tpot_p90_ms"),
            "decode_steps": r.counter_delta("decode_steps"),
            "wall_s": time.perf_counter() - served.t0,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
