"""Benchmark harness entry point: one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only NAME]

Prints ``name,us_per_call,derived`` CSV rows (scaffold contract) followed by
the human-readable detail lines.
"""

from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark names")
    ap.add_argument("--replicas", default=None,
                    help="comma list for the fleet sweep, e.g. 1,2,4")
    ap.add_argument("--routers", default=None,
                    help="comma list of router names for the fleet sweep")
    args = ap.parse_args()

    from benchmarks.paper_figures import ALL_FIGURES, replica_router_sweep

    sweep_kw = {}
    if args.replicas:
        sweep_kw["replicas"] = tuple(
            int(r) for r in args.replicas.split(",")
        )
    if args.routers:
        sweep_kw["routers"] = args.routers.split(",")

    all_csv, all_detail = [], []
    for fn in ALL_FIGURES:
        if args.only and args.only not in fn.__name__:
            continue
        t0 = time.time()
        csv_rows, detail = fn(**(sweep_kw if fn is replica_router_sweep else {}))
        dt = time.time() - t0
        all_csv.extend(csv_rows)
        all_detail.extend(detail)
        all_detail.append(f"[{fn.__name__} took {dt:.1f}s]")

    print("name,us_per_call,derived")
    for row in all_csv:
        print(row)
    print()
    for line in all_detail:
        print(line)


if __name__ == "__main__":
    main()
